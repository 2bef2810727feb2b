"""Cross-host partitioned embedding service.

Parity: reference KvVariable-on-PS placement —
`tfplus/tfplus/kv_variable/kernels/kv_variable.h:89` tables are sharded
across parameter-server nodes by TF's PS placement, so a vocabulary larger
than one host's memory spreads over the fleet.

TPU redesign: there are no PS nodes — each *worker host* owns a mod-shard
of the key space (`id % num_shards`).  The shard's id→slot control plane
(NativeKvStore) and its device value/optimizer tables stay entirely local
to the owner; only batched lookups and gradient pushes cross hosts, riding
the same framed-JSON control plane as the rest of the framework
(common/comm.py), with row payloads base64-packed.  The input pipeline
calls `gather` (host path, overlaps device compute like any data loading);
the training step treats the gathered rows as a dense jit input whose
cotangent is routed back shard-by-shard via `apply_gradients`.

Flow per batch on worker w:
  ids --mod-shard--> {owner: unique ids}
      local shard:   direct KvEmbedding calls (no copy, no socket)
      remote shards: one batched RPC per owner
  rows reassembled in input order → jit step → grads split the same way.
"""

from __future__ import annotations

import base64
import threading
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..common.comm import RpcClient, RpcServer
from ..common.log import get_logger
from .kv_embedding import KvEmbedding

logger = get_logger("partitioned_emb")


def _pack(a: np.ndarray) -> Dict:
    return {"b64": base64.b64encode(np.ascontiguousarray(a).tobytes())
            .decode("ascii"),
            "dtype": str(a.dtype), "shape": list(a.shape)}


def _unpack(d: Dict) -> np.ndarray:
    return np.frombuffer(base64.b64decode(d["b64"]),
                         dtype=np.dtype(d["dtype"])).reshape(d["shape"])


class EmbeddingShardServer:
    """Serves one key shard's embedding over the control plane.

    Verbs: emb_gather (insert-or-default rows), emb_grads (sparse update),
    emb_stats, emb_export_delta / emb_advance_epoch (incremental ckpt)."""

    def __init__(self, embedding: KvEmbedding, shard_id: int,
                 num_shards: int, host: str = "127.0.0.1", port: int = 0,
                 advertise_host: Optional[str] = None,
                 client_idle_horizon: float = 600.0):
        """Bind `host` (use "0.0.0.0" to serve off-host) and advertise
        `advertise_host` (the address peers dial — required when binding a
        wildcard, since "0.0.0.0:port" is not dialable).

        `client_idle_horizon`: seconds a client may go quiet before its
        dedup cache is evicted.  MUST strictly exceed the RPC client's
        worst-case retry window (timeout x retries + backoff — ~181s at
        the defaults) or a very late retry could double-apply emb_grads;
        the default also clears a multi-minute network stall."""
        self.embedding = embedding
        self.shard_id = shard_id
        self.num_shards = num_shards
        # RpcServer threads one handler per connection; the embedding's own
        # RLock also covers the owner's direct (co-located client) calls
        self._lock = embedding.lock
        # idempotence: at-least-once RPC retries must not re-apply
        # non-idempotent ops.  Mutating-op responses are cached by exact
        # (client, seq) — a replayed retry gets the cached answer instead
        # of a second gradient application.  Read ops (gather/stats) are
        # safe to re-execute (a gather replay at worst re-bumps frequency
        # once) and their row payloads are too large to cache.
        # The client axis is bounded too: every worker restart mints a
        # fresh client uuid, so an unbounded dict grows one dead cache per
        # restart on a long-lived shard server.  Eviction is IDLE-TIME
        # based (a client idle past the RPC retry horizon never replays) —
        # a fixed count cap would evict live clients on large fleets and
        # silently re-enable the double-apply bug this cache prevents.
        self._applied: "OrderedDict[str, Tuple[float, Dict[int, Dict]]]" = \
            OrderedDict()
        self._client_idle_horizon = float(client_idle_horizon)
        self._server = RpcServer(self._handle, host=host, port=port)
        if advertise_host is None:
            if host in ("0.0.0.0", "::", ""):
                raise ValueError("binding a wildcard host needs an "
                                 "explicit advertise_host peers can dial")
            advertise_host = host
        self.addr = f"{advertise_host}:{self._server.port}"

    def start(self):
        self._server.start()
        logger.info("embedding shard %d/%d serving at %s", self.shard_id,
                    self.num_shards, self.addr)

    def stop(self):
        self._server.stop()

    def _check_owned(self, ids: np.ndarray):
        owners = np.abs(ids) % self.num_shards
        if not np.all(owners == self.shard_id):
            raise ValueError(
                f"shard {self.shard_id} received ids it does not own "
                f"(owners seen: {sorted(set(owners.tolist()))})")

    def _handle(self, verb, node_id, node_type, payload):
        if not isinstance(payload, dict) or "op" not in payload:
            raise ValueError("embedding shard expects {'op': ...} payloads")
        op = payload["op"]
        client, seq = payload.get("client"), payload.get("seq")
        mutating = op in ("emb_grads", "emb_advance_epoch")
        with self._lock:
            if mutating and client is not None and seq is not None:
                now = time.monotonic()
                _, cache = self._applied.setdefault(client, (now, {}))
                self._applied[client] = (now, cache)
                self._applied.move_to_end(client)  # keep idle-ordered
                while self._applied:
                    ts, _ = next(iter(self._applied.values()))
                    if now - ts <= self._client_idle_horizon:
                        break
                    self._applied.popitem(last=False)
                if seq in cache:
                    return cache[seq]  # retry replay — do not re-apply
                resp = self._execute(op, payload)
                cache[seq] = resp
                while len(cache) > 32:  # bound per-client memory
                    cache.pop(min(cache))
                return resp
            return self._execute(op, payload)

    def _execute(self, op, payload):
        if op == "emb_gather":
            # ids arrive WITH duplicates: each occurrence must count one
            # frequency sighting, exactly as a direct KvEmbedding lookup
            # would (min_freq admission parity)
            ids = _unpack(payload["ids"]).astype(np.int64)
            self._check_owned(ids)
            slots = self.embedding.lookup_slots(
                ids, insert=payload.get("insert", True))
            rows = np.asarray(self.embedding.gather(slots))
            return {"rows": _pack(rows)}
        if op == "emb_grads":
            ids = _unpack(payload["ids"]).astype(np.int64)
            self._check_owned(ids)
            grads = _unpack(payload["grads"])
            # train=True keeps the min_freq filter: an id the forward
            # read as the null row must not train its real row here
            slots = self.embedding.lookup_slots(ids, insert=False,
                                                train=True)
            self.embedding.apply_gradients(slots, grads)
            return {"ok": True}
        if op == "emb_stats":
            return {"vocab": len(self.embedding.store),
                    "capacity": self.embedding.store.capacity,
                    "shard_id": self.shard_id,
                    "num_shards": self.num_shards}
        if op == "emb_export_delta":
            delta, epoch = self.embedding.export_delta()
            return {"epoch": epoch,
                    "delta": {k: _pack(np.asarray(v))
                              for k, v in delta.items()}}
        if op == "emb_advance_epoch":
            return {"epoch": self.embedding.store.advance_epoch()}
        raise ValueError(f"unknown embedding op {op!r}")


class PartitionedKvEmbedding:
    """Client view over mod-sharded embedding shards.

    `shard_addrs[w]` serves keys with `abs(id) % num_shards == w`.  Pass
    `local=(shard_id, embedding)` for the co-located shard to bypass the
    socket entirely (the common case: each worker hosts one shard)."""

    def __init__(self, dim: int, shard_addrs: List[str],
                 local: Optional[Tuple[int, KvEmbedding]] = None,
                 timeout: float = 60.0):
        import uuid

        self.dim = dim
        self.num_shards = len(shard_addrs)
        self._local_id = local[0] if local else -1
        self._local_emb = local[1] if local else None
        # idempotence tag: servers replay cached responses for retried seqs
        # instead of re-applying non-idempotent ops (grads, freq bumps)
        self._client_id = uuid.uuid4().hex
        self._seq = 0
        self._seq_lock = threading.Lock()
        self._clients: Dict[int, RpcClient] = {
            w: RpcClient(addr, timeout=timeout)
            for w, addr in enumerate(shard_addrs) if w != self._local_id
        }
        # remote shards are independent — dispatch their RPCs concurrently
        # (sequential round-trips would scale latency with num_shards)
        self._pool = (ThreadPoolExecutor(
            max_workers=min(16, max(1, len(self._clients))),
            thread_name_prefix="dwt-emb-rpc")
            if self._clients else None)

    def owners(self, ids: np.ndarray) -> np.ndarray:
        return np.abs(ids) % self.num_shards

    def _next_seq(self) -> int:
        with self._seq_lock:
            self._seq += 1
            return self._seq

    def _tagged(self, payload: Dict) -> Dict:
        payload["client"] = self._client_id
        payload["seq"] = self._next_seq()
        return payload

    def _masks(self, ids: np.ndarray):
        owners = self.owners(ids)
        return {w: owners == w for w in range(self.num_shards)
                if (owners == w).any()}

    def gather(self, ids: np.ndarray, insert: bool = True) -> np.ndarray:
        """(n,) int64 ids → (n, dim) float rows, assembled in input order.

        Ids go to owners WITH duplicates so per-occurrence frequency
        counting (min_freq admission) matches the single-host path."""
        ids = np.ascontiguousarray(ids, np.int64).ravel()
        rows = np.zeros((ids.shape[0], self.dim), np.float32)
        masks = self._masks(ids)
        futures = {}
        for w, mask in masks.items():
            if w != self._local_id:
                futures[w] = self._pool.submit(
                    self._clients[w].report,
                    self._tagged({"op": "emb_gather",
                                  "ids": _pack(ids[mask]),
                                  "insert": insert}))
        for w, mask in masks.items():
            if w == self._local_id:
                with self._local_emb.lock:
                    slots = self._local_emb.lookup_slots(ids[mask],
                                                         insert=insert)
                    shard_rows = np.asarray(self._local_emb.gather(slots),
                                            np.float32)
            else:
                shard_rows = _unpack(
                    futures[w].result()["rows"]).astype(np.float32)
            rows[mask] = shard_rows
        return rows

    def apply_gradients(self, ids: np.ndarray, grads: np.ndarray):
        """Push d(loss)/d(rows) back to the owners (duplicates pre-summed
        host-side so each unique id updates exactly once — the same
        semantics as KvEmbedding.apply_gradients' internal dedup)."""
        ids = np.ascontiguousarray(ids, np.int64).ravel()
        grads = np.asarray(grads, np.float32).reshape(ids.shape[0],
                                                      self.dim)
        futures = []
        local = None
        for w, mask in self._masks(ids).items():
            uniq, inv = np.unique(ids[mask], return_inverse=True)
            summed = np.zeros((uniq.shape[0], self.dim), np.float32)
            np.add.at(summed, inv, grads[mask])
            if w == self._local_id:
                local = (uniq, summed)
            else:
                futures.append(self._pool.submit(
                    self._clients[w].report,
                    self._tagged({"op": "emb_grads", "ids": _pack(uniq),
                                  "grads": _pack(summed)})))
        if local is not None:
            uniq, summed = local
            with self._local_emb.lock:
                # train=True: the min_freq filter routes under-threshold
                # ids to the null row (zero-grad) as the forward did
                slots = self._local_emb.lookup_slots(uniq, insert=False,
                                                     train=True)
                self._local_emb.apply_gradients(slots, summed)
        for f in futures:
            f.result()

    def stats(self) -> List[Dict]:
        out = []
        for w in range(self.num_shards):
            if w == self._local_id:
                out.append({"vocab": len(self._local_emb.store),
                            "capacity": self._local_emb.store.capacity,
                            "shard_id": w, "num_shards": self.num_shards})
            else:
                out.append(self._clients[w].report({"op": "emb_stats"}))
        return out

    def close(self):
        if self._pool is not None:
            self._pool.shutdown(wait=False)
        for c in self._clients.values():
            c.close()
