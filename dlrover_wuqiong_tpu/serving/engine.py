"""Slot-based continuous-batching decode engine (three jitted programs).

Parity: vLLM's continuous batching (the reference's serving backend,
`atorch/atorch/rl/model_engine/model_engine.py:35`) keeps a paged KV
cache and admits/evicts requests every iteration.  On TPU the same idea
must survive XLA's static-shape contract, so the design inverts: the
cache is a fixed ``(max_slots, max_len)`` ring and ALL dynamism lives in
traced *values* (positions, active masks, slot indices), never in
shapes.  Three programs compile once per (spec, model, quant, backend):

- ``admit``: prefill one request's prompt through a one-row mini cache
  (`lax.scan` over the static ``max_prompt_len``), sample its first
  token with ``fold_in(request_key, prompt_len)``, and
  `dynamic_update_slice` the mini cache into the big buffers at a
  *traced* slot index.
- ``decode``: `lax.scan` of ``fused_tokens`` steps over the shared
  forward (rl/generation.py `forward_step`) with a per-row position
  vector; inactive rows are frozen via ``jnp.where`` (their pos/tok do
  not advance).  ONE dispatch and ONE host readback — the (K, S) token
  block — per window (the fused K-step dispatch rule).
- retirement is free: the active mask is a host-side input, so freeing
  a slot is a host array write at the window boundary.

Correctness of stale cache state (pad positions beyond a prompt, a
previous tenant's kv) is by WRITE-THEN-ATTEND: row r attends position p
only when its pos >= p, and the forward at pos == p (over)writes p
before attending, so garbage is never read.  Every op is row-
independent, which makes a request's tokens a pure function of
(weights, prompt, seed) — independent of batch composition and slot
churn (the equivalence invariant tests/test_serving.py pins).

The engine's ``cache_key`` folds spec + model + quant
into the framework compile-cache registry (auto/compile_cache.py), and
`auto/warm_pool.py` accepts a ``serve`` WarmSpec field to AOT-compile
these programs ahead of a cutover.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..auto.compile_cache import (
    CACHE_DIR_ENV,
    canonicalize,
    note_train_step_served,
)
from ..models.gpt import GPTConfig
from ..ops.quantization import (
    dequantize_int8_blockwise,
    fp8_dequantize,
    fp8_quantize,
    quantize_int8_blockwise,
)
from ..rl.generation import forward_step, init_caches

_QUANT_MODES = ("", "int8", "fp8")


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """Static shape/compile parameters of one serving engine.

    Everything here is part of the compile-cache key: changing any field
    is a new executable (warm-pool it before cutover).  ``top_k`` is
    engine-static rather than per-request — a per-request top-k would
    change the sampling program shape.
    """

    max_slots: int = 4        # batch rows / concurrent requests
    max_len: int = 128        # per-slot KV length (prompt + generated)
    max_prompt_len: int = 32  # static prefill scan length
    fused_tokens: int = 8     # K decode steps per dispatch
    quant: str = ""           # "" | "int8" | "fp8" decode weights
    top_k: int = 0            # 0 = full softmax


def serve_step_cache_key(model_config: Any, spec: ServeSpec,
                         backend: Optional[str] = None) -> str:
    """Digest of everything the serving trace depends on (the serving
    counterpart of auto/compile_cache.train_step_cache_key)."""
    payload = {
        "kind": "serve",
        "model": canonicalize(model_config),
        "spec": canonicalize(spec),
        "backend": backend or jax.default_backend(),
        "jax": jax.__version__,
    }
    blob = json.dumps(payload, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


# ------------------------------------------------------------ quant store


def _quantize_tree(params: Dict, mode: str) -> Tuple[Dict, Dict]:
    """Split params into a (store, meta) pair: `store` holds arrays (the
    jit argument — weights must be arguments, not closure constants, so
    a weight refresh never retraces), `meta` holds the static dequant
    recipe per leaf (closure — it IS part of the trace)."""
    store: Dict = {}
    meta: Dict = {}

    def rec(src, dst, mdst):
        for k, v in src.items():
            if isinstance(v, dict):
                dst[k], mdst[k] = {}, {}
                rec(v, dst[k], mdst[k])
                continue
            arr = jnp.asarray(v)
            # quantize matrices/embeddings; 1-D leaves (bias, LN) stay
            # exact — they are tiny and scale-sensitive
            if mode and arr.ndim >= 2 and \
                    jnp.issubdtype(arr.dtype, jnp.floating):
                if mode == "int8":
                    q, s = quantize_int8_blockwise(arr)
                else:
                    q, s = fp8_quantize(arr)
                dst[k] = {"q": q, "s": s}
                mdst[k] = (mode, int(arr.size), tuple(arr.shape))
            else:
                dst[k] = arr
                mdst[k] = None

    rec(params, store, meta)
    return store, meta


def _materialize(store: Dict, meta: Dict, dtype) -> Dict:
    """Dequantize the store back into a forward-ready param tree
    (traced — runs once per dispatch inside the jitted programs)."""
    out: Dict = {}
    for k, m in meta.items():
        if isinstance(m, dict):
            out[k] = _materialize(store[k], m, dtype)
        elif m is None:
            out[k] = store[k]
        else:
            mode, size, shape = m
            leaf = store[k]
            if mode == "int8":
                out[k] = dequantize_int8_blockwise(
                    leaf["q"], leaf["s"], size, shape, dtype=dtype)
            else:
                out[k] = fp8_dequantize(leaf["q"], leaf["s"],
                                        dtype=dtype).reshape(shape)
    return out


# ------------------------------------------------------------ sampling


def _sample_rows(logits, keys, temps, top_k: int):
    """Per-row sampling: logits (S, V) f32, keys (S, 2) uint32 (already
    position-folded), temps (S,).  temp <= 0 means greedy.  Both the
    sampled and greedy branches are computed and selected with
    ``jnp.where`` — no data-dependent control flow in the program."""
    logits = logits.astype(jnp.float32)
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temps > 0, sampled, greedy)


# ------------------------------------------------------------- engine


class ServingEngine:
    """Owns the big KV buffers (device) + slot registers (host).

    Device state is ONLY the per-layer cache buffers; the small per-slot
    registers (next token, position, active mask, PRNG key, temperature)
    live host-side and ride into each dispatch as inputs — freeing a
    slot is a host write, no device program.  Cache buffers are donated
    through the admit/decode programs (they only ever originate as
    executable outputs, so the device_put→donate freed-memory hazard in
    CLAUDE.md does not apply).
    """

    def __init__(self, cfg: GPTConfig, params: Dict, spec: ServeSpec,
                 cache_dir: Optional[str] = None):
        if spec.quant not in _QUANT_MODES:
            raise ValueError(f"quant mode {spec.quant!r} not in "
                             f"{_QUANT_MODES}")
        if spec.max_len > cfg.block_size:
            raise ValueError(f"max_len {spec.max_len} exceeds model "
                             f"block_size {cfg.block_size}")
        if not (0 < spec.max_prompt_len <= spec.max_len):
            raise ValueError("need 0 < max_prompt_len <= max_len")
        if spec.max_slots < 1 or spec.fused_tokens < 1:
            raise ValueError("need max_slots >= 1 and fused_tokens >= 1")
        self.cfg = cfg
        self.spec = spec
        self._store, self._meta = _quantize_tree(params, spec.quant)
        self.cache_key = serve_step_cache_key(cfg, spec)
        # registry note: warm restarts can tell whether this topology was
        # compiled by a prior process (tools/warm_report.py aggregates)
        note_train_step_served(
            cache_dir or os.getenv(CACHE_DIR_ENV, ""),
            self.cache_key,
            {"kind": "serve", "spec": dataclasses.asdict(spec)})
        S = spec.max_slots
        # caches start as executable OUTPUTS (jitted zeros), which keeps
        # the donate chain free of device_put-origin arrays
        self.caches = jax.jit(
            lambda: init_caches(cfg, S, spec.max_len))()
        # host-side slot registers
        self.tok = np.zeros(S, np.int32)
        self.pos = np.zeros(S, np.int32)
        self.active = np.zeros(S, bool)
        self.keys = np.zeros((S, 2), np.uint32)
        self.temps = np.ones(S, np.float32)
        self._admit_fn = jax.jit(self._admit_impl, donate_argnums=(0,))
        self._decode_fn = jax.jit(self._decode_impl, donate_argnums=(0,))

    # ------------------------------------------------------------ programs

    def _admit_impl(self, caches, store, prompt, prompt_len, slot, key,
                    temp):
        """Prefill one request; splice its cache into `slot`.

        prompt: (max_prompt_len,) int32, zero-padded.  Pad positions
        beyond prompt_len DO write garbage kv into the mini cache, but
        write-then-attend makes them unreachable: decode at position p
        overwrites p before any row attends it.
        """
        cfg, spec = self.cfg, self.spec
        params = _materialize(store, self._meta, cfg.dtype)
        mini = init_caches(cfg, 1, spec.max_prompt_len)

        def pre(carry, i):
            mini, sel = carry
            logits, mini = forward_step(cfg, params, prompt[i][None, None],
                                        mini, i)
            # keep the logits of the LAST real prompt token
            sel = jnp.where(i == prompt_len - 1,
                            logits.astype(jnp.float32), sel)
            return (mini, sel), None

        (mini, sel), _ = jax.lax.scan(
            pre, (mini, jnp.zeros((1, cfg.vocab_size), jnp.float32)),
            jnp.arange(spec.max_prompt_len))
        # token at absolute position t is sampled with fold_in(key, t):
        # the first generated token sits at position prompt_len
        kf = jax.random.fold_in(key, prompt_len)
        first = _sample_rows(sel, kf[None], temp[None], spec.top_k)[0]
        out = []
        for (big_k, big_v), (mk, mv) in zip(caches, mini):
            big_k = jax.lax.dynamic_update_slice(big_k, mk, (slot, 0, 0, 0))
            big_v = jax.lax.dynamic_update_slice(big_v, mv, (slot, 0, 0, 0))
            out.append((big_k, big_v))
        return out, first.astype(jnp.int32)

    def _decode_impl(self, caches, store, tok, pos, active, keys, temps):
        """K fused decode steps over all slots; returns (K, S) tokens."""
        cfg, spec = self.cfg, self.spec
        params = _materialize(store, self._meta, cfg.dtype)
        L = spec.max_len

        def step(carry, _):
            caches, tok, pos = carry
            pos_s = jnp.minimum(pos, L - 1)
            logits, caches = forward_step(cfg, params, tok[:, None],
                                          caches, pos_s)
            nxt = pos_s + 1
            kf = jax.vmap(jax.random.fold_in)(keys, nxt)
            sampled = _sample_rows(logits, kf, temps,
                                   spec.top_k).astype(tok.dtype)
            # frozen slots: pos/tok do not advance (jnp.where, not cond)
            tok = jnp.where(active, sampled, tok)
            pos = jnp.where(active, nxt, pos)
            return (caches, tok, pos), sampled

        (caches, _, _), toks = jax.lax.scan(
            step, (caches, tok, pos), None, length=spec.fused_tokens)
        return caches, toks

    # ------------------------------------------------------------- host API

    def free_slots(self) -> List[int]:
        return [i for i in range(self.spec.max_slots)
                if not self.active[i]]

    def admit(self, slot: int, prompt: List[int], seed: int,
              temperature: float = 1.0, max_new_tokens: int = 0) -> int:
        """Admit a request into a free slot; returns its FIRST generated
        token (the one readback this boundary op pays — it is also the
        time-to-first-token mark)."""
        spec = self.spec
        plen = len(prompt)
        if not (0 < plen <= spec.max_prompt_len):
            raise ValueError(f"prompt length {plen} not in "
                             f"(0, {spec.max_prompt_len}]")
        if plen + max(1, max_new_tokens) > spec.max_len:
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_len {spec.max_len}")
        if self.active[slot]:
            raise ValueError(f"slot {slot} is occupied")
        padded = np.zeros(spec.max_prompt_len, np.int32)
        padded[:plen] = np.asarray(prompt, np.int32)
        key = np.asarray(jax.random.PRNGKey(seed), np.uint32)
        self.caches, first = self._admit_fn(
            self.caches, self._store, jnp.asarray(padded),
            jnp.int32(plen), jnp.int32(slot), jnp.asarray(key),
            jnp.float32(temperature))
        first_tok = int(first)  # boundary readback (TTFT mark)
        self.tok[slot] = first_tok
        self.pos[slot] = plen
        self.active[slot] = True
        self.keys[slot] = key
        self.temps[slot] = temperature
        return first_tok

    def retire(self, slot: int):
        """Free a slot — host write only; the row freezes via the active
        mask on the next dispatch and its cache is overwritten by the
        next tenant (write-then-attend)."""
        self.active[slot] = False

    def decode_window(self) -> np.ndarray:
        """One fused K-token dispatch over all slots.

        Returns the (K, S) token block — the single host readback of the
        window; rows of inactive slots are garbage and must be masked by
        the caller's slot bookkeeping.
        """
        self.caches, toks = self._decode_fn(
            self.caches, self._store, jnp.asarray(self.tok),
            jnp.asarray(self.pos), jnp.asarray(self.active),
            jnp.asarray(self.keys), jnp.asarray(self.temps))
        out = np.asarray(toks)  # the ONE readback per fused window
        k = self.spec.fused_tokens
        act = self.active
        if act.any():
            self.tok[act] = out[-1, act]
            self.pos[act] += k
        return out

    def sync_from_trainer(self, params: Dict):
        """One-hop weight refresh from a live trainer (compose with
        rl/hybrid.HybridEngine.sync_to_decode for the mesh hop).  Same
        tree structure → the store stays a jit *argument* and no program
        retraces; in-flight requests keep their caches (they continue
        under the new weights, the standard continuous-batching
        contract)."""
        store, meta = _quantize_tree(params, self.spec.quant)
        if jax.tree_util.tree_structure((store, meta)) != \
                jax.tree_util.tree_structure((self._store, self._meta)):
            raise ValueError("refreshed params have a different tree "
                             "structure — build a new engine")
        self._store = store
