"""Device-resident relation store: tuple-set columns live on the mesh once.

The paper's MapReduce jobs re-ship every CN's tuple-set relations on every
query; the PR 1-3 runtime inherited that shape — each dispatch stacked the
routed ``text``/``keys`` columns on the host and paid a full host→device
transfer of data that is identical across CNs, queries and tenants.  This
module is the "aggregation equal transformation" idea taken to its logical
end for an accelerator runtime: the statistics *input* never leaves the
workers either.  Following the replication-cost analysis of Afrati & Ullman
(PAPERS.md) and the shares/hypercube line in ``core/shares.py``, only the
small routing metadata (send tables, key-column indices) is replicated per
dispatch; the big columns are uploaded ONCE per (session, tuple set).

``RelationStore`` maps a :class:`repro.core.plan.RelationRef`'s content
fingerprint to device arrays sharded ``P("w")`` over the mesh, padded to the
engine's pow-2 bucket dims so one upload serves every program built for that
signature.  Fact keys are stored FULL width (all ``m`` columns); the device
program selects each CN's columns with a gathered index, so CNs with
different dimension subsets reuse one upload.  Entries are LRU with an
optional byte budget (``max_bytes``); eviction just drops the device buffer
— a later dispatch re-uploads from the descriptor (a counted miss).

Counters follow the runtime convention: ``store_uploads`` / ``store_hits``
(reuse), ``store_upload_bytes`` (cumulative host→device column traffic),
``store_bytes`` (currently resident), ``store_evictions``.  Sessions expose
them through ``stats()`` and per-response engine deltas, so tests and the
``multi_query`` benchmark can assert that warm queries ship ZERO relation
columns.
"""
from __future__ import annotations

import threading
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.plan import CNPlan, RelationRef
from repro.data.schema import PAD_ID
from repro.obs import default_registry
from repro.obs import span as obs_span
from repro.runtime.batch import (PlanSignature, RelationSig, bucket_pow2,
                                 x64_flag)
from repro.runtime.cache import LruDict


class StoredColumns(NamedTuple):
    """One tuple-set relation's device-resident padded columns."""

    text: jax.Array      # [P, text_pad, rows_pad] int32, sharded P("w")
    keys: jax.Array      # [P(, m_all), rows_pad] int32, sharded P("w")
    nbytes: int


class RelationStore:
    """Content-addressed LRU of device-resident tuple-set columns.

    One store serves one (schema, mesh) pair — the session owns it.  Keys
    combine the RelationRef fingerprint, the padded dims (so exact-shape and
    bucketed engines coexist) and the ``jax_enable_x64`` flag (programs and
    arrays created under different x64 modes must not alias).
    """

    def __init__(self, mesh: Mesh, max_bytes: Optional[int] = None,
                 metrics=None) -> None:
        if max_bytes is not None and max_bytes < 1:
            raise ValueError(f"max_bytes must be >= 1, got {max_bytes}")
        self.mesh = mesh
        self.max_bytes = max_bytes
        self._sharding = NamedSharding(mesh, P("w"))
        self._entries: LruDict = LruDict()   # key -> StoredColumns
        self._lock = threading.Lock()
        self.metrics = metrics if metrics is not None else default_registry()
        self._c_uploads = self.metrics.counter("store.uploads")
        self._c_hits = self.metrics.counter("store.hits")
        self._c_evictions = self.metrics.counter("store.evictions")
        self._c_upload_bytes = self.metrics.counter("store.upload_bytes")
        # chunked (append-path) entries assembled on DEVICE from resident
        # per-chunk columns: no host->device column traffic, so they count
        # here instead of store.uploads/upload_bytes
        self._c_assembles = self.metrics.counter("store.chunk_assembles")
        self._g_resident = self.metrics.gauge("store.resident_bytes")
        # bumped by clear(): an upload that started before an invalidation
        # must not re-insert pre-invalidation columns after it
        self.epoch = 0

    # legacy attribute views over the registry-owned instruments
    @property
    def uploads(self) -> int:
        return self._c_uploads.value

    @property
    def hits(self) -> int:
        return self._c_hits.value

    @property
    def evictions(self) -> int:
        return self._c_evictions.value

    @property
    def upload_bytes(self) -> int:
        return self._c_upload_bytes.value

    @property
    def chunk_assembles(self) -> int:
        return self._c_assembles.value

    @property
    def resident_bytes(self) -> int:
        return self._g_resident.value

    # -- lookup / upload -----------------------------------------------------

    def columns(self, ref: RelationRef, rows_pad: int,
                text_pad: int) -> StoredColumns:
        """The ref's device columns padded to (rows_pad, text_pad),
        uploading them on first use (or after eviction).

        Refs spanning several append chunks (``ref.chunk_parts()``) are
        assembled on DEVICE from per-chunk entries instead of re-uploading
        the whole column set: each part goes through this same method (a
        part's uid equals the uid of a plain ref over the same rows, so
        pre-append and delta-dispatch uploads alias), then the combined
        entry concatenates the parts' rows and re-pads — bit-identical to
        what a direct upload of the full ref would have produced.  Only the
        parts missing from the store cost host->device traffic, which is
        how an append re-ships one chunk, not the relation.
        """
        key = (ref.uid, rows_pad, text_pad, x64_flag())
        with self._lock:
            cached = self._entries.hit(key)
            if cached is not None:
                self._c_hits.inc()
                return cached
            epoch = self.epoch
        parts = ref.chunk_parts()
        if parts is not None:
            with obs_span("store.chunk_assemble", parts=len(parts),
                          rows_pad=rows_pad, text_pad=text_pad):
                part_cols = [self.columns(p, bucket_pow2(p.shard_rows),
                                          text_pad) for p in parts]
                stored = self._assemble(parts, part_cols, rows_pad, text_pad)
        else:
            with obs_span("store.upload", rows_pad=rows_pad,
                          text_pad=text_pad) as sp:     # outside the lock
                text, keys = ref.store_columns(rows_pad, text_pad)
                nbytes = text.nbytes + keys.nbytes
                sp.args["bytes"] = nbytes
                stored = StoredColumns(
                    text=jax.device_put(text, self._sharding),
                    keys=jax.device_put(keys, self._sharding), nbytes=nbytes)
        with self._lock:
            raced = self._entries.hit(key)
            if raced is not None:      # concurrent uploader won
                self._c_hits.inc()
                return raced
            if parts is not None:
                self._c_assembles.inc()
            else:
                self._c_uploads.inc()
                self._c_upload_bytes.inc(stored.nbytes)
            if self.epoch != epoch:
                # a clear() (data invalidation) overtook this upload: the
                # columns may predate the mutation, and the row-index
                # fingerprint cannot tell — serve this dispatch, cache
                # nothing (the next reference re-reads the base arrays)
                return stored
            resident = self._g_resident.add(stored.nbytes)
            self._entries.put(key, stored)
            if self.max_bytes is not None:
                while resident > self.max_bytes and len(self._entries) > 1:
                    _, dropped = self._entries.popitem(last=False)
                    resident = self._g_resident.add(-dropped.nbytes)
                    self._c_evictions.inc()
            return stored

    def _assemble(self, parts: List[RelationRef],
                  cols: List[StoredColumns], rows_pad: int,
                  text_pad: int) -> StoredColumns:
        """Combine per-chunk device columns into one padded entry.

        Each part entry holds its rows contiguously sharded: device d's
        first ``ceil(n_part / P)`` slots are rows ``d*S .. (d+1)*S`` (flat
        row order preserved, pad at the flat tail), so slicing off the pad,
        flattening and concatenating the chunks recovers the combined row
        order; re-sharding at the COMBINED shard size ``ceil(n_total / P)``
        and re-padding each device to ``rows_pad`` then reproduces EXACTLY
        the array a direct ``ref.store_columns`` upload builds — all on
        device (eager jnp ops + a resharding device_put), no host columns.
        """
        P_dev = parts[0].n_devices

        def rows_flat(a, S, n):
            # [P, *mid, rows_pad] -> [*mid, n]: each device's first S slots,
            # devices concatenated in flat row order, flat tail pad dropped
            a = jnp.moveaxis(a[..., :S], 0, -2)
            return a.reshape(a.shape[:-2] + (P_dev * S,))[..., :n]

        text = jnp.concatenate([rows_flat(c.text, p.shard_rows, p.n_rows)
                                for p, c in zip(parts, cols)], axis=-1)
        keyc = jnp.concatenate([rows_flat(c.keys, p.shard_rows, p.n_rows)
                                for p, c in zip(parts, cols)], axis=-1)
        n_total = int(text.shape[-1])
        S_ref = -(-n_total // P_dev)          # == the combined ref's
        #                                       shard_rows (<= rows_pad)

        def reshard(a, fill):
            # [*mid, n_total] -> [P, *mid, rows_pad], contiguous row shards
            tail = P_dev * S_ref - a.shape[-1]
            a = jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, tail),),
                        constant_values=fill)
            a = jnp.moveaxis(a.reshape(a.shape[:-1] + (P_dev, S_ref)), -2, 0)
            return jnp.pad(a, ((0, 0),) * (a.ndim - 1)
                           + ((0, rows_pad - S_ref),), constant_values=fill)

        text = reshard(text, PAD_ID)
        keyc = reshard(keyc, 0)
        return StoredColumns(
            text=jax.device_put(text, self._sharding),
            keys=jax.device_put(keyc, self._sharding),
            nbytes=int(text.nbytes + keyc.nbytes))

    # -- lifecycle / introspection ------------------------------------------

    def clear(self) -> int:
        """Drop every device buffer (data-mutation invalidation hook);
        returns the number of entries dropped."""
        with self._lock:
            dropped = len(self._entries)
            self._entries.clear()
            self._g_resident.set(0)
            self.epoch += 1        # fence in-flight uploads (see columns())
            return dropped

    def __len__(self) -> int:
        return len(self._entries)

    def stats(self) -> Dict[str, int]:
        (uploads, hits, evictions, up_bytes, assembles,
         resident) = self.metrics.values(
            self._c_uploads, self._c_hits, self._c_evictions,
            self._c_upload_bytes, self._c_assembles, self._g_resident)
        with self._lock:
            return {"store_entries": len(self._entries),
                    "store_uploads": uploads,
                    "store_hits": hits,
                    "store_evictions": evictions,
                    "store_upload_bytes": up_bytes,
                    "store_chunk_assembles": assembles,
                    "store_bytes": resident}


# ---------------------------------------------------------------------------
# dispatch-time argument assembly (used by the engine)
# ---------------------------------------------------------------------------

def _pad_send(send: np.ndarray, cap: int) -> np.ndarray:
    if send.shape[-1] == cap:
        return send
    return np.pad(send, ((0, 0), (0, 0), (0, cap - send.shape[-1])),
                  constant_values=-1)


def _null_send(n_devices: int, cap: int) -> np.ndarray:
    return np.full((n_devices, n_devices, cap), -1, np.int32)


def store_group_args(store: RelationStore, plans: Sequence[CNPlan],
                     sig: PlanSignature, n_stack: int):
    """Device arguments for one stacked signature group on the store path.

    Returns ``((fact, dims), shipped_bytes)`` where ``fact`` / each dim slot
    is ``{"text": [N device arrays], "keys": [N device arrays],
    "send": [N, P, P, C] host, ...}`` — the only HOST payload is the stacked
    send tables plus the fact's key-column indices (``shipped_bytes``
    counts exactly that).  Slots past ``len(plans)`` are null plans: they
    alias the first plan's store-resident columns and route nothing (all
    ``-1`` send), contributing exactly zero to every histogram.  The host
    pad-and-stack runs under the ``store.send_tables`` span.
    """
    pad = n_stack - len(plans)
    rsigs = (sig.fact,) + tuple(sig.dims)
    routes = [[p.fact for p in plans]] + [
        [p.dims[p.included[j]] for p in plans] for j in range(len(sig.dims))]
    # store hits (an upload on a miss) before the send tables are stacked
    cols = [[store.columns(r.ref, rsig.rows, rsig.text_len) for r in rel]
            for rel, rsig in zip(routes, rsigs)]
    with obs_span("store.send_tables", n_stack=n_stack):
        rels = []
        for rel, rsig, cs in zip(routes, rsigs, cols):
            sends = [_pad_send(r.send, rsig.cap) for r in rel]
            if pad:
                cs = cs + [cs[0]] * pad
                sends.extend([_null_send(sends[0].shape[0], rsig.cap)] * pad)
            rels.append({"text": [c.text for c in cs],
                         "keys": [c.keys for c in cs],
                         "send": np.stack(sends)})
        fact, dims = rels[0], rels[1:]
        key_cols = [np.asarray(p.fact.key_cols, np.int32) for p in plans]
        if pad:
            key_cols.extend([key_cols[0]] * pad)
        fact["cols"] = np.stack(key_cols)
    shipped = fact["send"].nbytes + fact["cols"].nbytes + sum(
        d["send"].nbytes for d in dims)
    return (fact, dims), shipped
