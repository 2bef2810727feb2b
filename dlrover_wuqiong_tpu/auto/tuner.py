"""Online kernel-variant autotuner: measured A/B over trace-time toggles.

Parity: the reference tunes nothing online — `dlrover/python/master/
hyperparams/simple_strategy_generator.py:1` picks a static strategy from
offline heuristics and never revisits it.  On TPU the biggest single-chip
levers left (ROADMAP item 4) are *trace-time* kernel picks — the
`DWT_FA_*` toggles (ops/flash_attention.py:221,488,629) and the fused-K
ladder — whose relative merit depends on shape, backend and chip load, so
a static default leaves throughput on the table.  Chameleon (PAPERS.md)
makes the case for measured, real-time selection; PHOENIX's zero-overhead
principle bounds the design: tuning must never add a device sync the
training loop wasn't already paying.

Redesign, three jax-free pieces (this module imports NO jax so the
`__graft_entry__.py` smoke and the chaos drills can exercise the scorer
math and the persistence roundtrip without a backend):

- ``variant_env`` / ``apply_variant`` — the ONE sanctioned place that
  writes a ``TRACE_ENV_VARS`` name into ``os.environ``.  Those toggles
  are read at TRACE time and ride every framework cache key
  (auto/compile_cache.py:55); an ad-hoc write anywhere else poisons every
  cache keyed on trace env (graftlint's ``env-flip-outside-tuner`` rule
  enforces this module boundary).
- ``InterleavedScorer`` — A/B scoring per the ±10% chip-drift rule
  (CLAUDE.md): candidates are sampled round-robin in the SAME session and
  compared by median-of-interleaved, never back-to-back batches.  The
  clock is injectable so CPU tests converge deterministically.
- ``TuningStore`` — the winner persists to ``$ckpt_dir/perf/tuning.json``
  with the same atomic write-tmp-fsync-rename discipline as the perf
  observatory's baseline store (telemetry/perf.py); corrupt or missing
  files are re-learned, never fatal.  Rows are keyed by the variant
  FAMILY (strategy fingerprint + backend — the tunables themselves stay
  out of the key) and record the winning env, fused-K, and the winner's
  full ``executable_key`` so reports can join against baselines.

``VariantAutotuner`` drives the three online: the trainer feeds it one
perf-observatory window per boundary (zero new readbacks — the windows
reuse the logging-boundary loss sync), it answers with the next candidate
to pre-warm + cut over to (every candidate is a distinct compile-cache
key, so cutover through the warm pool is zero-cold-compile), and on
convergence it persists the winner and surfaces the decision as
PolicyDecision-style history with measured before/after.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from ..common.log import get_logger
from .compile_cache import TRACE_ENV_VARS

logger = get_logger("tuner")

# persisted under the checkpoint dir, next to the baseline store
TUNING_SUBDIR = "perf"
TUNING_FILE = "tuning.json"

# store schema version.  v2 (ISSUE 16) nests each family row as
# {"winner": rec, "shapes": {shape_class: rec}} — per-geometry winners
# (ROADMAP 4c) with the family-wide winner as the fallback for unseen
# shapes.  v1 shapeless rows migrate forward on load (served as the
# family winner, upgraded in place on the next atomic publish) — no
# re-learning.  Record keys stay ADD-ONLY.
_SCHEMA = 2

#: how many recent non-numerics window losses anchor the divergence guard
_LOSS_REF_WINDOW = 8


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    if n == 0:
        return 0.0
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


# ------------------------------------------------------------------ env

def env_signature() -> Tuple[str, ...]:
    """Current values of the trace-time toggles, in TRACE_ENV_VARS order.

    This tuple IS the variant identity of the running process: it rides
    the in-process fused-step cache key (auto/accelerate.py) and the
    trainer's compiled-modes set, mirroring how `executable_key`
    (telemetry/perf.py) and `train_step_cache_key` fold the same values.
    """
    return tuple(os.environ.get(k, "") for k in TRACE_ENV_VARS)


def _set_trace_env(env: Dict[str, str]) -> Dict[str, Optional[str]]:
    """Write trace-env toggles; returns the previous values for restore.

    The ONLY sanctioned writer of TRACE_ENV_VARS names (graftlint
    `env-flip-outside-tuner`).  An empty-string value unsets the toggle —
    the kernels treat unset and "" differently for DWT_FA_STREAMED
    (ops/flash_attention.py:631), so "" must genuinely delete.
    """
    prev: Dict[str, Optional[str]] = {}
    for name, value in env.items():
        if name not in TRACE_ENV_VARS:
            raise ValueError(
                f"{name} is not a trace-time toggle (TRACE_ENV_VARS) — "
                f"the tuner only owns {TRACE_ENV_VARS}")
        prev[name] = os.environ.get(name)
        if value == "" or value is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = str(value)
    return prev


@contextlib.contextmanager
def variant_env(env: Dict[str, str]) -> Iterator[None]:
    """Scoped trace-env flip: compile/measure a candidate, then restore.

    Every A/B site in the repo (probes, chaos drills, the autotuner
    itself) routes through here so the flip is paired with its restore
    and visibly sanctioned.  Tracing/compiling a candidate MUST happen
    inside the `with` block — the toggles are read at trace time.
    """
    prev = _set_trace_env(env)
    try:
        yield
    finally:
        for name, old in prev.items():
            if old is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = old


def apply_variant(env: Dict[str, str]) -> None:
    """Process-lifetime variant application (no restore).

    Used at cutover (the trainer adopts the winner) and by warm-pool
    children applying a spec's `trace_env` before the first trace.
    """
    _set_trace_env(env)


# ------------------------------------------------------------- variants


@dataclass(frozen=True)
class Variant:
    """One tunable configuration: a trace-env dict plus optional fused-K.

    `env` covers only TRACE_ENV_VARS names; a missing name means "leave
    as-is", an empty string means "unset".  `fused_steps=0` means "keep
    the current K" (sentinel, mirrors PolicyDecision's no-change zeros).

    ADD-ONLY fields (ISSUE 16): `axis` labels the tunable family the
    variant explores ("quant", "pack", "stream", "attn", "remat", "k" —
    "" = untagged) so `order_variants` can match it against the
    observatory's op-category split; `numerics=True` marks a variant
    that changes the LOSS TRAJECTORY (fp8/int8 — unlike the layout-
    neutral DWT_FA_*/remat axes), which subjects it to the autotuner's
    loss-divergence guard and gates it behind the trainer's explicit
    `tune_numerics` opt-in.
    """

    name: str
    env: Dict[str, str] = field(default_factory=dict)
    fused_steps: int = 0
    axis: str = ""
    numerics: bool = False

    def signature(self) -> Tuple[str, ...]:
        """TRACE_ENV_VARS-ordered values this variant pins (others "")."""
        return tuple(self.env.get(k, "") for k in TRACE_ENV_VARS)


def default_variants(backend: str = "cpu",
                     include_k: Tuple[int, ...] = (), *,
                     numerics: bool = False,
                     remat_policies: Tuple[str, ...] = ()) -> List[Variant]:
    """The stock candidate matrix over the trace-toggle space.

    Kept deliberately small — each candidate costs one warm-pool compile
    and `windows_per_variant` measurement windows.  The pack-width sweep
    only pays on TPU (the CPU fallback never reaches the Pallas kernels),
    so CPU defaults stay at the fused/unfused/streamed axes.

    `remat_policies` appends the remat-policy ladder (ops/remat.py names,
    applied through the trace-time DWT_REMAT_POLICY override) — callers
    pass it only when the model actually remats, otherwise the variants
    compile to the identical program and just burn windows.  `numerics`
    opts in the loss-trajectory-changing quant axis (fp8 dense matmul via
    DWT_FP8_DENSE); it is OFF by default and the trainer only enables it
    behind `TrainingArgs.tune_numerics` with the loss-divergence guard
    armed.
    """
    variants = [
        Variant("default", {}),
        Variant("no-fused", {"DWT_FA_NO_FUSED": "1"}, axis="attn"),
        Variant("streamed", {"DWT_FA_STREAMED": "1"}, axis="stream"),
    ]
    if backend == "tpu":
        variants += [
            Variant("pack4", {"DWT_FA_PACK": "4"}, axis="pack"),
            Variant("unstreamed", {"DWT_FA_STREAMED": "0"}, axis="stream"),
        ]
    for policy in remat_policies:
        variants.append(Variant(f"remat-{policy}",
                                {"DWT_REMAT_POLICY": str(policy)},
                                axis="remat"))
    if numerics:
        variants.append(Variant("fp8-dense", {"DWT_FP8_DENSE": "1"},
                                axis="quant", numerics=True))
    for k in include_k:
        variants.append(Variant(f"fused-k{k}", {}, fused_steps=int(k),
                                axis="k"))
    return variants


#: variant axis → the op category whose dominance makes the axis worth
#: trying first (observatory-driven search, ROADMAP 4d).  Quant variants
#: shrink matmul bytes/FLOPs; pack/stream reshape the attention
#: collective/streaming behavior.  Unmapped axes score 0 and keep their
#: declaration order after the targeted ones.
AXIS_CATEGORIES = {"quant": "matmul", "pack": "collective",
                   "stream": "collective"}


def order_variants(variants: List[Variant],
                   category_medians: Optional[Dict[str, float]], *,
                   incumbent: str = "default") -> List[Variant]:
    """Order the candidate matrix by the baseline's op-category split.

    Replaces the fixed declaration-order seed with a measured one: each
    variant scores the fraction of device time the baseline store
    attributes to its target category (AXIS_CATEGORIES), so a
    matmul-bound executable tries quant variants first and a
    collective-bound one tries pack/stream first.  The incumbent always
    sorts first (its windows anchor every comparison), ties keep
    declaration order, and an empty/absent profile returns the input
    unchanged — the interleaving itself (InterleavedScorer's
    least-sampled-first round-robin) is untouched, only the within-round
    order moves.
    """
    cats = {str(c): max(float(s), 0.0)
            for c, s in (category_medians or {}).items()}
    total = sum(cats.values())
    if total <= 0.0:
        return list(variants)

    def score(v: Variant) -> float:
        target = AXIS_CATEGORIES.get(v.axis, "")
        return cats.get(target, 0.0) / total if target else 0.0

    index = {v.name: i for i, v in enumerate(variants)}
    return sorted(variants, key=lambda v: (v.name != incumbent,
                                           -score(v), index[v.name]))


def shape_class(batch: int, seq: int, dims: str = "") -> str:
    """Geometry class key for per-shape winners (ROADMAP 4c).

    batch × seq × a model-dims fingerprint (e.g. "d768x12" — width ×
    depth): a winner learned at 1k seq mis-tunes 4k, so the store keys
    winners per geometry with the family-wide winner as the fallback for
    unseen shapes.
    """
    key = f"b{int(batch)}-s{int(seq)}"
    return f"{key}-{dims}" if dims else key


# --------------------------------------------------------------- scorer


class InterleavedScorer:
    """Median-of-interleaved A/B scoring with hysteresis.

    Run-to-run noise can exceed the difference between two variants, so
    candidates must be sampled round-robin in the same session; the
    median of interleaved samples cancels slow drift that would bury a
    back-to-back comparison.  `winner()` applies a hysteresis margin: a
    challenger must beat the incumbent's median by more than
    `hysteresis` (relative) or the incumbent is kept — statistically
    tied variants never flap.
    """

    def __init__(self, candidates: List[str], *,
                 min_samples: int = 3,
                 hysteresis: float = 0.05,
                 clock: Callable[[], float] = time.monotonic) -> None:
        if not candidates:
            raise ValueError("scorer needs at least one candidate")
        if len(set(candidates)) != len(candidates):
            raise ValueError(f"duplicate candidate names: {candidates}")
        self.candidates = list(candidates)
        self.min_samples = max(1, int(min_samples))
        self.hysteresis = float(hysteresis)
        self.clock = clock
        self.samples: Dict[str, List[float]] = {c: [] for c in candidates}

    def next_candidate(self) -> str:
        """Least-sampled candidate, ties broken by declaration order —
        i.e. strict round-robin interleave."""
        return min(self.candidates, key=lambda c: len(self.samples[c]))

    def note(self, name: str, value: float) -> None:
        if name not in self.samples:
            raise KeyError(f"unknown candidate {name!r}")
        self.samples[name].append(float(value))

    def remove(self, name: str) -> None:
        """Drop a candidate mid-search (loss-divergence revert).

        Its samples are discarded — a diverged variant's step times must
        not win the comparison it was disqualified from.  Removing the
        last candidate is a bug upstream (the incumbent is never
        removable in practice), so it raises instead of leaving the
        scorer unable to answer `next_candidate`.
        """
        if name not in self.samples:
            raise KeyError(f"unknown candidate {name!r}")
        if len(self.candidates) == 1:
            raise ValueError("cannot remove the last candidate")
        self.candidates.remove(name)
        del self.samples[name]

    def measure(self, name: str, fn: Callable[[], Any]) -> float:
        """Time one invocation with the injectable clock and record it."""
        t0 = self.clock()
        fn()
        dt = self.clock() - t0
        self.note(name, dt)
        return dt

    def medians(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for name, vals in self.samples.items():
            if vals:
                s = sorted(vals)
                n = len(s)
                out[name] = (s[n // 2] if n % 2
                             else 0.5 * (s[n // 2 - 1] + s[n // 2]))
        return out

    def complete(self) -> bool:
        """Every candidate has at least `min_samples` samples."""
        return all(len(v) >= self.min_samples
                   for v in self.samples.values())

    def winner(self, incumbent: Optional[str] = None) -> Tuple[str, bool]:
        """(winner_name, decided).  Lower median wins; the incumbent is
        kept unless a challenger clears the hysteresis margin."""
        if not self.complete():
            fallback = incumbent if incumbent in self.samples \
                else self.candidates[0]
            return fallback, False
        med = self.medians()
        best = min(med, key=lambda c: (med[c], self.candidates.index(c)))
        if incumbent in med and best != incumbent:
            if med[best] >= med[incumbent] * (1.0 - self.hysteresis):
                return incumbent, True
        return best, True


# ---------------------------------------------------------------- store


def tuning_path(ckpt_dir: str) -> str:
    return os.path.join(ckpt_dir, TUNING_SUBDIR, TUNING_FILE)


def family_key(strategy_fingerprint: str, backend: str) -> str:
    """Stable digest of the NON-tunable executable identity.

    Same ingredients as `executable_key` (telemetry/perf.py:108) minus
    the tunables (fused-K and the trace env) — all variants of one
    training program share a family, so the persisted winner can be
    looked up before the first trace of a later run.
    """
    payload = json.dumps({"strategy": strategy_fingerprint,
                          "backend": backend}, sort_keys=True)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


class TuningStore:
    """Atomic, corrupt-tolerant winner persistence (tuning.json).

    Mirrors the baseline store's discipline (telemetry/perf.py
    BaselineStore): load tolerates a missing/corrupt/truncated file by
    starting empty (the tuner re-learns — never fatal), publish writes
    tmp + fsync + os.replace so a SIGKILL mid-write leaves the previous
    winner intact.
    """

    def __init__(self, path: str) -> None:
        self.path = path
        self._rows: Dict[str, Dict[str, Any]] = self._load()

    def _load(self) -> Dict[str, Dict[str, Any]]:
        try:
            with open(self.path) as f:
                raw = json.load(f)
            if not isinstance(raw, dict):
                raise ValueError("payload is not a dict")
            rows = raw.get("families", {})
            if not isinstance(rows, dict):
                raise ValueError("families is not a dict")
            out: Dict[str, Dict[str, Any]] = {}
            for k, v in rows.items():
                if not isinstance(v, dict):
                    continue
                if "winner" in v or "shapes" in v:  # v2 nested row
                    winner = v.get("winner")
                    shapes = v.get("shapes", {})
                    out[str(k)] = {
                        "winner": dict(winner)
                        if isinstance(winner, dict) else None,
                        "shapes": {str(s): dict(r)
                                   for s, r in shapes.items()
                                   if isinstance(r, dict)}
                        if isinstance(shapes, dict) else {},
                    }
                else:  # v1 flat row: serve as the family winner, no
                    # per-shape knowledge — upgraded in place by the
                    # next atomic publish, never re-learned
                    out[str(k)] = {"winner": dict(v), "shapes": {}}
            return out
        except FileNotFoundError:
            return {}
        except (OSError, ValueError, TypeError) as e:
            logger.warning("tuning store %s unreadable (%s) — re-learning",
                           self.path, e)
            return {}

    def lookup(self, family: str,
               shape: Optional[str] = None) -> Optional[Dict[str, Any]]:
        """Winner record for (family, shape): the exact geometry row when
        one was learned, else the family-wide winner as the fallback."""
        row = self._rows.get(family)
        if not row:
            return None
        if shape:
            rec = row.get("shapes", {}).get(shape)
            if rec:
                return dict(rec)
        winner = row.get("winner")
        return dict(winner) if winner else None

    def rows(self) -> Dict[str, Dict[str, Any]]:
        """Nested view: {family: {"winner": rec|None, "shapes": {...}}}."""
        return {k: {"winner": dict(v["winner"]) if v.get("winner") else None,
                    "shapes": {s: dict(r)
                               for s, r in v.get("shapes", {}).items()}}
                for k, v in self._rows.items()}

    def publish(self, family: str, record: Dict[str, Any],
                shape: Optional[str] = None) -> None:
        """Persist a winner; with `shape`, the record lands in BOTH the
        geometry row and the family winner (latest-wins fallback for
        shapes never tuned)."""
        row = self._rows.setdefault(family, {"winner": None, "shapes": {}})
        row["winner"] = dict(record)
        if shape:
            row.setdefault("shapes", {})[str(shape)] = dict(record)
        payload = {"schema": _SCHEMA, "families": self._rows}
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise


def make_record(variant: Variant, *, executable_key: str,
                fused_steps: int, medians: Dict[str, float],
                windows: int, shape_class: str = "") -> Dict[str, Any]:
    """The persisted winner row (ADD-ONLY keys)."""
    return {
        "variant": variant.name,
        "env": dict(variant.env),
        "fused_steps": int(fused_steps),
        "executable_key": executable_key,
        "medians": {k: float(v) for k, v in medians.items()},
        "windows": int(windows),
        # geometry the winner was learned at ("" = shapeless/v1 rows)
        "shape_class": str(shape_class),
        # persisted cross-process timestamp — wall clock is correct here
        "ts": time.time(),
    }


def load_winner(ckpt_dir: str, family: str,
                shape: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """Startup shortcut: the persisted winner for this family, if any.

    bench.py and the trainer call this before the first trace so later
    runs start on the tuned variant instead of re-searching; the caller
    applies `record["env"]` through `apply_variant` (sanctioned) and
    `record["fused_steps"]` through the normal pre-warm path.  With
    `shape` (a `shape_class` key), the exact-geometry winner is
    preferred and the family-wide winner serves unseen shapes.
    """
    if not ckpt_dir:
        return None
    return TuningStore(tuning_path(ckpt_dir)).lookup(family, shape)


# ------------------------------------------------------------ autotuner


class VariantAutotuner:
    """Online tuning state machine the trainer drives at fusion boundaries.

    Protocol (all calls from the trainer's host loop — no device work):

    - ``current()`` — the variant whose windows are being measured now.
    - ``note_window(step_time_s)`` — one perf-observatory window closed
      for the current variant; returns the NEXT variant to pre-warm and
      cut over to (or None while staying put).  The scorer interleaves,
      so the next variant usually differs from the current one.
    - ``finished`` / ``result()`` — once every candidate has its windows,
      the winner is decided (hysteresis: ties keep the incumbent),
      persisted through the store, and recorded as a PolicyDecision-style
      entry in ``decisions`` with measured before/after medians.

    The tuner never touches jax and never flips env itself mid-run — the
    TRAINER owns applying `Variant.env` (through `apply_variant`) only
    after the warm pool reports the candidate ready, so a cutover never
    pays a cold compile (CLAUDE.md: K and DWT_FA_* changes pre-warm).
    Thread-safety: all state behind one lock; the metrics pump thread
    calls ``note_window`` while the main loop reads ``current()``.

    ISSUE 16 additions: ``category_hint`` (the baseline store's
    op-category split) seeds the candidate order through
    ``order_variants`` and ``max_candidates`` prunes the ordered tail
    (dropped names are logged — no silent caps); ``shape_class`` keys the
    persisted winner per geometry (family winner stays the fallback);
    ``loss_bound`` arms the loss-divergence guard for numerics-changing
    variants — a window whose loss exceeds the rolling reference median
    by more than ``loss_bound`` (relative) REVERTS the variant: it is
    removed from the search, the trainer is answered with the incumbent
    to cut back to, and the revert lands in ``decisions`` as an
    auditable entry (kind "tuner-revert").
    """

    def __init__(self, variants: List[Variant], *,
                 store: Optional[TuningStore] = None,
                 family: str = "",
                 windows_per_variant: int = 3,
                 hysteresis: float = 0.05,
                 incumbent: str = "default",
                 shape_class: str = "",
                 loss_bound: float = 0.0,
                 category_hint: Optional[Dict[str, float]] = None,
                 max_candidates: int = 0,
                 clock: Callable[[], float] = time.monotonic) -> None:
        if not variants:
            raise ValueError("autotuner needs at least one variant")
        if len({v.name for v in variants}) != len(variants):
            raise ValueError("duplicate variant names")
        incumbent = incumbent if incumbent in {v.name for v in variants} \
            else variants[0].name
        ordered = order_variants(list(variants), category_hint,
                                 incumbent=incumbent)
        if max_candidates and len(ordered) > max_candidates:
            kept = ordered[:max_candidates]
            dropped = [v.name for v in ordered[max_candidates:]]
            logger.info("tuner pruned %d low-priority candidates: %s",
                        len(dropped), dropped)
            ordered = kept
        self.variants = {v.name: v for v in ordered}
        self.store = store
        self.family = family
        self.incumbent = incumbent
        self.shape_class = str(shape_class)
        self.loss_bound = float(loss_bound)
        self.scorer = InterleavedScorer(
            [v.name for v in ordered],
            min_samples=windows_per_variant,
            hysteresis=hysteresis, clock=clock)
        self.clock = clock
        self.decisions: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._current = self.incumbent
        self._finished = False
        self._winner: Optional[str] = None
        # rolling losses from non-numerics windows — the divergence
        # reference for the guard (bounded deque-style list)
        self._loss_ref: List[float] = []

    # -- read side -------------------------------------------------

    def current(self) -> Variant:
        with self._lock:
            return self.variants[self._current]

    @property
    def finished(self) -> bool:
        with self._lock:
            return self._finished

    def result(self) -> Optional[Variant]:
        with self._lock:
            return self.variants[self._winner] if self._winner else None

    def snapshot(self) -> Dict[str, Any]:
        """Lossy telemetry view (medians + progress) for reports."""
        with self._lock:
            return {
                "current": self._current,
                "finished": self._finished,
                "winner": self._winner or "",
                "windows": {c: len(s)
                            for c, s in self.scorer.samples.items()},
                "medians": self.scorer.medians(),
            }

    # -- write side ------------------------------------------------

    def note_window(self, step_time_s: float,
                    loss: Optional[float] = None) -> Optional[Variant]:
        """Credit one measured window to the current variant; answer with
        the next variant to pre-warm/cut to, or None when settled.

        `loss` (the window's already-read training loss — zero new device
        syncs) feeds the divergence guard: windows from non-numerics
        variants extend the rolling reference; a numerics variant whose
        loss exceeds the reference median by more than `loss_bound`
        (relative, one-sided — loss naturally declines, only a RISE is
        divergence) is reverted instead of scored.
        """
        revert_decision = None
        with self._lock:
            if self._finished:
                return None
            cur = self.variants[self._current]
            if (loss is not None and self.loss_bound > 0.0
                    and cur.numerics and self._loss_ref):
                ref = _median(self._loss_ref)
                if loss - ref > self.loss_bound * max(abs(ref), 1e-9):
                    nxt, revert_decision = self._revert_locked(
                        cur, float(loss), ref)
                    # fall through below the lock to surface the revert
                    # (and a possible winner if the search just drained)
                else:
                    nxt = self._note_locked(step_time_s)
            else:
                if loss is not None and not cur.numerics:
                    self._loss_ref.append(float(loss))
                    del self._loss_ref[:-_LOSS_REF_WINDOW]
                nxt = self._note_locked(step_time_s)
            winner_args = self._winner_args
            self._winner_args = None
        if revert_decision is not None:
            with self._lock:
                self.decisions.append(revert_decision)
            logger.warning(
                "tuner REVERTED %s: loss %.4f diverged from ref %.4f "
                "(bound %.3f)", revert_decision["reverted"],
                revert_decision["loss"], revert_decision["loss_ref"],
                self.loss_bound)
        if winner_args is not None:
            # winner path: persist + record OUTSIDE the lock (publish
            # fsyncs)
            self._record_decision(*winner_args)
        return nxt

    #: staged (winner, medians, windows) handed from the locked region to
    #: the unlocked persistence step
    _winner_args: Optional[Tuple[Any, ...]] = None

    def _note_locked(self, step_time_s: float) -> Optional[Variant]:
        """Score one window and advance the interleave (lock held)."""
        self.scorer.note(self._current, step_time_s)
        return self._advance_locked()

    def _advance_locked(self) -> Optional[Variant]:
        """Pick the winner (if the search drained) or the next candidate
        to pre-warm (lock held); stages the winner persistence args."""
        if self.scorer.complete():
            name, _ = self.scorer.winner(incumbent=self.incumbent)
            self._winner = name
            self._finished = True
            nxt = None if name == self._current else self.variants[name]
            # converge: current() must answer the winner so the
            # trainer's boundary poll settles on it
            self._current = name
            self._winner_args = (self.variants[name],
                                 self.scorer.medians(),
                                 sum(len(s) for s
                                     in self.scorer.samples.values()))
            return nxt
        nxt_name = self.scorer.next_candidate()
        if nxt_name == self._current:
            return None
        self._current = nxt_name
        return self.variants[nxt_name]

    def _revert_locked(self, degraded: Variant, loss: float,
                       ref: float) -> Tuple[Optional[Variant],
                                            Dict[str, Any]]:
        """Disqualify a diverged numerics variant (lock held).

        The degraded window's step time is NOT scored (a diverged
        variant must not win the race it was thrown out of).  The
        incumbent is answered as the cut-back target — it is always
        already compiled, so the trainer's prewarm gate passes
        immediately and the degraded env never lingers past the
        boundary.  Exception: if the removal drained the search, the
        normal winner path settles it (every measured candidate is
        compiled, so that cutover is warm too).
        """
        self.scorer.remove(degraded.name)
        del self.variants[degraded.name]
        incumbent_var = self.variants[self.incumbent]
        self._current = self.incumbent
        decision = {
            "decision_id": f"tune-revert-{degraded.name}",
            "kind": "tuner-revert",
            "variant": self.incumbent,
            "reverted": degraded.name,
            "env": dict(incumbent_var.env),
            "fused_steps": incumbent_var.fused_steps,
            "loss": float(loss),
            "loss_ref": float(ref),
            "loss_bound": self.loss_bound,
            "windows": sum(len(s) for s in self.scorer.samples.values()),
            "before": {"loss": float(loss)},
            "after": {"loss": float(ref)},
            "shape_class": self.shape_class,
        }
        if self.scorer.complete():
            # the removal drained the search — settle through the
            # normal winner path (stages persistence args).  A None
            # answer means winner == incumbent (the degraded variant is
            # gone, _current is already the incumbent), which is exactly
            # the cut-back target.
            return self._advance_locked() or incumbent_var, decision
        return incumbent_var, decision

    def cutover(self, variant: Variant) -> None:
        """The trainer confirms it switched execution to `variant`."""
        with self._lock:
            if variant.name in self.variants:
                self._current = variant.name

    def _record_decision(self, winner: Variant,
                         medians: Dict[str, float],
                         windows: int) -> None:
        before = medians.get(self.incumbent, 0.0)
        after = medians.get(winner.name, 0.0)
        decision = {
            "decision_id": f"tune-{self.family or 'local'}-{windows}",
            "kind": "tuner",
            "variant": winner.name,
            "env": dict(winner.env),
            "fused_steps": winner.fused_steps,
            "before": {"step_time_s": before},
            "after": {"step_time_s": after},
            "windows": windows,
            "shape_class": self.shape_class,
        }
        with self._lock:
            self.decisions.append(decision)
        logger.info("tuner decided: %s (median %.4fs -> %.4fs over %d "
                    "windows)", winner.name, before, after, windows)
        if self.store is not None and self.family:
            try:
                from .compile_cache import TRACE_ENV_VARS as _vars
                exe_env = {k: winner.env.get(k, "") for k in _vars}
                record = make_record(
                    winner,
                    executable_key=self._winner_executable_key(winner),
                    fused_steps=winner.fused_steps,
                    medians=medians, windows=windows,
                    shape_class=self.shape_class)
                record["exe_env"] = exe_env
                self.store.publish(self.family, record,
                                   shape=self.shape_class or None)
            except OSError as e:  # persistence is best-effort
                logger.warning("tuning winner not persisted: %s", e)

    def _winner_executable_key(self, winner: Variant) -> str:
        """The winner's FULL executable identity, joinable against the
        baseline store.  Computed under the winner's env (scoped flip —
        executable_key reads os.environ at call time)."""
        try:
            from ..telemetry.perf import executable_key as _ek
        except Exception:  # noqa: BLE001 — telemetry optional in smokes
            return ""
        ctx = self._exe_key_ctx or {}
        with variant_env(dict(winner.env)):
            return _ek(ctx.get("strategy_fingerprint", self.family),
                       int(winner.fused_steps
                           or ctx.get("fused_steps", 1) or 1),
                       ctx.get("backend", "cpu"))

    _exe_key_ctx: Optional[Dict[str, Any]] = None

    def bind_executable_context(self, *, strategy_fingerprint: str,
                                fused_steps: int, backend: str) -> None:
        """Trainer provides the identity ingredients once at startup so
        the persisted record carries a real executable_key."""
        self._exe_key_ctx = {
            "strategy_fingerprint": strategy_fingerprint,
            "fused_steps": int(fused_steps),
            "backend": backend,
        }
