"""Shared-memory staging of sharded `jax.Array` pytrees.

Parity: reference `elastic_agent/torch/ckpt_saver.py:65-341` (`TensorMeta`,
`SharedMemoryHandler.save_state_dict`, `_write_shared_memory`) — pickle-free
tensor staging in POSIX shm so the agent process can persist checkpoints
asynchronously while training continues.

TPU redesign: a checkpoint is a pytree of `jax.Array`s that may be sharded over
the global device mesh.  Each training process stages the *addressable* shards
of every leaf (device→host DMA + one memcpy into shm).  Restore rebuilds either
numpy leaves (local/global) or `jax.Array`s via
`jax.make_array_from_single_device_arrays` when a sharding is supplied.
"""

from __future__ import annotations

import json
import math
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..common.log import get_logger
from ..common.multi_process import SharedMemoryBuffer
from .integrity import DIGEST_ALGO, digest_bytes

logger = get_logger("shm_handler")

try:  # bfloat16/f8 numpy dtypes
    import ml_dtypes  # noqa: F401

    _EXTRA_DTYPES = {
        "bfloat16": np.dtype(ml_dtypes.bfloat16),
        "float8_e4m3fn": np.dtype(ml_dtypes.float8_e4m3fn),
        "float8_e5m2": np.dtype(ml_dtypes.float8_e5m2),
    }
except ImportError:  # pragma: no cover
    _EXTRA_DTYPES = {}

_HEADER_SIZE = 1 << 20  # fixed 1MB header region
# header layout: [0:8] big-endian json length (0 = empty/invalid, published
# LAST for crash consistency), [8:12] crc of the json bytes (a bit flip in
# the header itself must not yield a parseable-but-wrong meta), [12:12+n]
# the json.  Payload starts at _HEADER_SIZE.
_HDR_JSON_OFF = 12


def _np_dtype(name: str) -> np.dtype:
    if name in _EXTRA_DTYPES:
        return _EXTRA_DTYPES[name]
    return np.dtype(name)


@dataclass
class TensorMeta:
    """Location of one array shard inside the shm segment."""

    name: str
    dtype: str
    shape: List[int]  # shard (local) shape
    offset: int
    nbytes: int
    global_shape: List[int] = field(default_factory=list)
    # per-dim [start, stop) of this shard within the global array
    index: List[List[int]] = field(default_factory=list)
    # crc of this shard's staged bytes (-1 = legacy writer, fails the
    # trust boundary's verification on purpose)
    digest: int = -1

    def to_dict(self):
        return {
            "name": self.name, "dtype": self.dtype, "shape": self.shape,
            "offset": self.offset, "nbytes": self.nbytes,
            "global_shape": self.global_shape, "index": self.index,
            "digest": self.digest,
        }

    @classmethod
    def from_dict(cls, d):
        return cls(**d)


def _leaf_refs(name: str, value: Any) -> List[Tuple[str, Any, List[int],
                                                    List[List[int]]]]:
    """Expand one pytree leaf into (name, array_ref, global_shape, index).

    `array_ref` stays a device array (single-device `jax.Array` shard) when the
    leaf is a `jax.Array` — no host transfer happens here, so the caller can
    batch-issue async D2H copies across the whole checkpoint before
    materializing any of them (reference stages per-tensor synchronously;
    batching keeps the per-transfer round-trip off the blocking path).
    """
    entries = []
    if hasattr(value, "addressable_shards"):  # jax.Array
        global_shape = list(value.shape)
        unique: Dict[tuple, Any] = {}
        for shard in value.addressable_shards:
            idx = []
            for dim, sl in enumerate(shard.index):
                start = sl.start if sl.start is not None else 0
                stop = sl.stop if sl.stop is not None else global_shape[dim]
                idx.append((start, stop))
            key = tuple(idx)
            if key not in unique:  # skip replicas of the same slice
                unique[key] = shard.data
        whole = len(unique) == 1 and next(iter(unique)) == tuple(
            (0, s) for s in global_shape)
        for i, (key, ref) in enumerate(unique.items()):
            ename = name if whole else f"{name}#shard{i}"
            entries.append((ename, ref, global_shape,
                            [list(se) for se in key]))
    else:
        host = np.asarray(value)
        entries.append((name, host, list(host.shape),
                        [[0, s] for s in host.shape]))
    return entries


def flatten_state_dict(state: Any) -> Dict[str, Any]:
    """Pytree → flat {path: leaf} with '/'-joined string paths."""
    import jax

    flat = {}
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    for path, leaf in leaves:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            elif hasattr(p, "name"):
                parts.append(str(p.name))
            else:
                parts.append(str(p))
        flat["/".join(parts) if parts else "leaf"] = leaf
    return flat


class SharedMemoryHandler:
    """Owns one shm segment staging one process's checkpoint shards."""

    def __init__(self, local_rank: int, job_name: str = "dwt",
                 create: bool = False):
        self._name = f"{job_name}_ckpt_shm_{local_rank}"
        self.local_rank = local_rank
        self._buf: Optional[SharedMemoryBuffer] = None
        self._lock = threading.Lock()

    @property
    def shm_name(self) -> str:
        return self._name

    def _ensure_size(self, needed: int):
        if self._buf is None or self._buf.size < needed:
            if self._buf is not None:
                self._buf.close()
            size = 1 << max(20, math.ceil(math.log2(needed)))
            self._buf = SharedMemoryBuffer(self._name, create=True, size=size)

    def attach(self) -> bool:
        try:
            if self._buf is None:
                self._buf = SharedMemoryBuffer(self._name)
            return True
        except FileNotFoundError:
            return False

    def enough_space(self, state: Any) -> bool:
        return True  # segment grows on demand

    # ----------------------------------------------------------------- write

    def save_state_dict(self, state: Any, step: int = 0,
                        extra_meta: Optional[Dict] = None):
        """Stage a pytree of arrays into shm (blocking part of a flash save).

        Two-phase to minimize blocking time: (1) walk the tree collecting
        device-shard references and issue ONE async D2H copy per shard so all
        transfers pipeline; (2) materialize each (already in flight) and memcpy
        into shm.  Metadata (dtype/shape/nbytes) is available without any
        transfer, so the segment is sized and the header written up front.
        """
        flat = flatten_state_dict(state)
        refs: List[Tuple[str, Any, List[int], List[List[int]]]] = []
        for name, leaf in flat.items():
            refs.extend(_leaf_refs(name, leaf))
        for _, ref, _, _ in refs:  # batch-start all device→host transfers
            if hasattr(ref, "copy_to_host_async"):
                try:
                    ref.copy_to_host_async()
                except Exception:  # noqa: BLE001 — backend may not support it
                    pass
        metas: List[TensorMeta] = []
        offset = _HEADER_SIZE
        for ename, ref, gshape, index in refs:
            dtype = np.dtype(ref.dtype)
            nbytes = int(np.prod(ref.shape)) * dtype.itemsize
            metas.append(TensorMeta(
                name=ename, dtype=dtype.name, shape=list(ref.shape),
                offset=offset, nbytes=nbytes, global_shape=gshape,
                index=index))
            offset += nbytes
        extra = dict(extra_meta or {})
        # creator pid: the saver-startup sweeper reaps segments whose
        # creator died (same dead-pid pattern as SharedLock)
        extra.setdefault("_pid", os.getpid())
        with self._lock:
            self._ensure_size(offset)
            buf = self._buf.buf
            # crash-consistency: invalidate the segment first, write payload,
            # publish the header LAST.  A crash mid-staging leaves length=0
            # (reader sees "no checkpoint"), never a header describing
            # partially-written payload — critical now that staging runs in a
            # background drain thread overlapping training.
            buf[0:8] = (0).to_bytes(8, "big")
            for meta, (_, ref, _, _) in zip(metas, refs):
                # np.ascontiguousarray promotes 0-d to 1-d; meta keeps shape
                host = np.ascontiguousarray(np.asarray(ref))
                view = host.view(np.uint8).reshape(-1)
                buf[meta.offset:meta.offset + meta.nbytes] = view
                # digest the staged bytes: restore (any tier) refuses to
                # hand a flipped/torn shard to device_put
                meta.digest = digest_bytes(view.tobytes())
            header = {
                "step": step,
                "algo": DIGEST_ALGO,
                "metas": [m.to_dict() for m in metas],
                "extra": extra,
            }
            header_bytes = json.dumps(header).encode()
            if len(header_bytes) + _HDR_JSON_OFF > _HEADER_SIZE:
                raise ValueError("checkpoint meta header exceeds 1MB")
            buf[8:12] = digest_bytes(header_bytes).to_bytes(4, "big")
            buf[_HDR_JSON_OFF:_HDR_JSON_OFF + len(header_bytes)] = \
                header_bytes
            buf[0:8] = len(header_bytes).to_bytes(8, "big")

    # ------------------------------------------------------------------ read

    def load_header(self) -> Optional[Dict]:
        if not self.attach():
            return None
        return _parse_header(self._buf.buf)

    def segment_state(self) -> str:
        """"absent" | "empty" | "torn" | "ok" — distinguishes "nothing
        staged" (benign cold start) from a header that is present but
        fails its crc / parse (corruption the restore chain must report).
        """
        if not self.attach():
            return "absent"
        buf = self._buf.buf
        n = int.from_bytes(bytes(buf[0:8]), "big")
        if n == 0:
            return "empty"
        return "ok" if _parse_header(buf) is not None else "torn"

    def load_state_dict(self) -> Optional[Tuple[int, Dict[str, np.ndarray],
                                                List[TensorMeta], Dict]]:
        """Returns (step, {name: np.ndarray}, metas, extra) or None."""
        header = self.load_header()
        if header is None:
            return None
        buf = self._buf.buf
        out: Dict[str, np.ndarray] = {}
        metas = [TensorMeta.from_dict(m) for m in header["metas"]]
        for meta in metas:
            raw = np.frombuffer(
                bytes(buf[meta.offset:meta.offset + meta.nbytes]),
                dtype=_np_dtype(meta.dtype))
            out[meta.name] = raw.reshape(meta.shape)
        return header.get("step", 0), out, metas, header.get("extra", {})

    def iter_shards(self):
        """Yield (meta, memoryview) without copying — for the async saver."""
        header = self.load_header()
        if header is None:
            return
        buf = self._buf.buf
        for m in header["metas"]:
            meta = TensorMeta.from_dict(m)
            yield meta, buf[meta.offset:meta.offset + meta.nbytes]

    def verify(self) -> Tuple[bool, str]:
        """Digest-check every staged shard against its header meta.

        (ok, reason) — reason "" on success, "no-segment" when nothing is
        staged.  A legacy segment without digests FAILS (the trust
        boundary does not grandfather undigested bytes)."""
        from .integrity import verify_segment_entries

        loaded = self.load_state_dict()
        if loaded is None:
            return False, "no-segment"
        _, flat, metas, _ = loaded
        header = self.load_header() or {}
        return verify_segment_entries(metas, flat, header.get("algo", ""))

    def mark_empty(self):
        if self._buf is not None:
            self._buf.buf[0:8] = (0).to_bytes(8, "big")

    def close(self):
        with self._lock:
            if self._buf is not None:
                self._buf.close()
                self._buf = None

    def unlink(self):
        with self._lock:
            if self._buf is None:
                try:
                    self._buf = SharedMemoryBuffer(self._name)
                except FileNotFoundError:
                    return
            self._buf.unlink()
            self._buf.close()
            self._buf = None


# -------------------------------------------------- header / blob helpers


def _parse_header(buf) -> Optional[Dict]:
    """Header json out of a segment buffer/blob; None when empty or torn.

    The 4-byte header crc catches a bit flip in the header region itself —
    without it a flipped byte in a meta's offset/dtype would parse fine
    and misread the payload."""
    if len(buf) < _HDR_JSON_OFF:
        return None
    n = int.from_bytes(bytes(buf[0:8]), "big")
    if n == 0 or n > _HEADER_SIZE - _HDR_JSON_OFF or \
            _HDR_JSON_OFF + n > len(buf):
        return None
    raw = bytes(buf[_HDR_JSON_OFF:_HDR_JSON_OFF + n])
    if digest_bytes(raw) != int.from_bytes(bytes(buf[8:12]), "big"):
        return None
    try:
        return json.loads(raw.decode())
    except ValueError:
        return None


def verify_segment_blob(blob: bytes) -> Tuple[Optional[int], str]:
    """Verify a raw segment copy (replica wire blob) WITHOUT touching shm.

    Returns (step, "") when every shard's digest matches its header meta,
    else (None, reason) — the replica restore path checks the pulled blob
    BEFORE overwriting the local segment, so a corrupt peer copy can
    never clobber local state or reach device_put."""
    header = _parse_header(blob)
    if header is None:
        return None, "torn-header"
    from .integrity import DIGEST_ALGO as _ALGO

    if header.get("algo", "") != _ALGO:
        return None, "algo-mismatch"
    for m in header.get("metas", []):
        d = m.get("digest", -1)
        if d is None or int(d) < 0:
            return None, f"undigested-leaf:{m.get('name')}"
        end = m["offset"] + m["nbytes"]
        if end > len(blob):
            return None, f"truncated-payload:{m.get('name')}"
        if digest_bytes(blob[m["offset"]:end]) != int(d):
            return None, f"leaf-digest-mismatch:{m.get('name')}"
    return header.get("step", 0), ""


def blob_state_dict(blob: bytes) -> Optional[Tuple[int,
                                                   Dict[str, np.ndarray],
                                                   Dict]]:
    """Parse a segment blob into (step, {name: np.ndarray}, extra).

    For the hot-swap hydration path: a survivor holds a DEAD rank's
    segment as wire bytes (replica.fetch_peer) and needs its arrays
    without routing them through the local shm segment (which holds the
    survivor's OWN shards).  Callers must verify first
    (verify_segment_blob) — this helper only decodes; the sanctioned
    route keeps digest verification between the socket and device_put.
    """
    header = _parse_header(blob)
    if header is None:
        return None
    out: Dict[str, np.ndarray] = {}
    for m in header.get("metas", []):
        meta = TensorMeta.from_dict(m)
        raw = np.frombuffer(blob[meta.offset:meta.offset + meta.nbytes],
                            dtype=_np_dtype(meta.dtype))
        out[meta.name] = raw.reshape(meta.shape)
    return header.get("step", 0), out, header.get("extra", {})


# ------------------------------------------------- stale-segment sweeper


def sweep_stale_segments(current_job: str) -> List[str]:
    """Reap orphaned ckpt shm segments whose creator pid is dead.

    POSIX shm outlives hard kills (CLAUDE.md): every SIGKILLed drill or
    crashed run leaks its `{job}_ckpt_shm_{rank}` segments until reboot.
    On saver startup we walk /dev/shm for the framework's naming pattern,
    read each header's creator pid (stamped by save_state_dict), and
    unlink segments whose creator no longer exists — the same dead-pid
    reap SharedLock applies to lock holders (common/multi_process.py).

    Segments of `current_job`, segments with live creators, and segments
    whose header is unreadable (no pid evidence — may be mid-staging by a
    live writer) are left alone.  Returns the reaped names.
    """
    from ..common.multi_process import _pid_alive

    shm_root = "/dev/shm"
    if not os.path.isdir(shm_root):  # non-Linux: nothing to sweep
        return []
    reaped: List[str] = []
    for name in sorted(os.listdir(shm_root)):
        if "_ckpt_shm_" not in name:
            continue
        if current_job and name.startswith(f"{current_job}_ckpt_shm_"):
            continue
        try:
            seg = SharedMemoryBuffer(name)
        except (FileNotFoundError, OSError):
            continue
        try:
            header = _parse_header(seg.buf)
            pid = (header or {}).get("extra", {}).get("_pid")
            if pid is None or _pid_alive(int(pid)):
                continue
            seg.unlink()
            reaped.append(name)
            logger.warning("reaped stale ckpt shm segment %s "
                           "(creator pid %s is dead)", name, pid)
        except Exception:  # noqa: BLE001 — sweeping must never break startup
            logger.exception("stale-segment sweep failed for %s", name)
        finally:
            try:
                seg.close()
            except Exception:  # noqa: BLE001
                pass
    return reaped
