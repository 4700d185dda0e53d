"""Device-resident event ring: the eventsmap/perf-buffer analogue.

Reference: upstream cilium's datapath emits events into a kernel perf
ring (``pkg/monitor/agent`` reads it); userspace drains at its own
cadence and the ring overwrites when the consumer lags.  TPU-first
redesign: the ring is a fixed HBM buffer; the fused pipeline appends
**compacted** events (drops + policy verdicts on NEW connections +
1/``trace_sample`` of established-flow traces — exactly the reference's
event economy, where TraceNotify is sampled and established traffic is
counted in the metricsmap, not streamed) entirely on device.  The host
drains asynchronously — so the hot loop never blocks on device→host
transfers.

Ring semantics: wrap-overwrite (newest wins), like the Hubble observer
ring; total appended count is monotone so the host computes loss as
``appended - capacity`` when it lags a full lap.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..datapath.verdict import EV_TRACE, N_OUT, OUT_EVENT

# Decoded ring row: the N_OUT out-columns + packet index within batch
# + batch seq.  On DEVICE each row packs into RING_WORDS u32 (8 B
# instead of 32 B) — the drain is a device->host copy, and its
# bandwidth is the monitor plane's ceiling, so the wire format is
# packed exactly like the reference keeps perf events small.  12 B ->
# 8 B by
# (a) storing the proxy PORT as a 4-bit index into the small listener
# table (there are at most a handful of live redirect listeners —
# upstream allocates them from a ~dozen-wide range) and (b) shrinking
# the batch-seq field to 13 bits (it disambiguates/orders events
# within a drain window; windows are a few dozen batches).
# Packing (see _unpack_rows for the decode):
#   w0: verdict(0..2) | event(3..4) | reason(5..8) | ct(9..11)
#       | proxy_idx(12..15) | id_row(16..31)
#   w1: pkt_idx(0..18) | batch(19..31, wraps)
# The 4-bit reason field holds codes 0..15.  N_REASONS is 13 —
# REASON_DISPATCH_TIMEOUT (10), REASON_RECOVERY_DROP (11) and
# REASON_CLUSTER_OVERFLOW (12) are RESERVED for the serving recovery
# and cluster routing planes (host-synthesized, so they never transit
# this ring today, but the wire width must cover them:
# a drained row's reason decodes through the same DROP_REASON_NAMES
# table).  4 codes (12..15) remain before the field must widen.
# Limits (asserted where they bind): id_row < 2^16, pkt_idx < 2^19
# (batches up to 512k rows), batch seq wraps at 2^13, <= 15 live
# proxy listeners.  Empty slots carry event bits 0b11 (no EV_* code
# uses 3), which is how the drain drops never-written rows.
RING_COLS = N_OUT + 2
COL_PKT_IDX = N_OUT
COL_BATCH = N_OUT + 1
EMPTY_BATCH = 0xFFFFFFFF
RING_WORDS = 2
MAX_PROXY_PORTS = 15
_EMPTY = 0xFFFFFFFF


@jax.tree_util.register_pytree_node_class
@dataclass
class EventRing:
    """Device state of the ring (pytree: threads through jit)."""

    buf: jnp.ndarray  # [capacity, RING_WORDS] uint32 (packed rows)
    # total events ever appended, as TWO u32 words [lo, hi] — a single
    # u32 wraps after 2^32 events (hours at target rates; the reference
    # perf/Hubble rings count in u64) and a wrapped cursor makes drain
    # misread a full ring as nearly empty.  x64 is off under jit, so
    # the 64-bit count is carried as lo + carry-into-hi on device.
    cursor: jnp.ndarray  # [2] uint32

    @staticmethod
    def create(capacity: int = 1 << 15) -> "EventRing":
        assert capacity & (capacity - 1) == 0, "capacity must be 2^k"
        buf = jnp.full((capacity, RING_WORDS), _EMPTY,
                       dtype=jnp.uint32)
        return EventRing(buf=buf, cursor=jnp.zeros((2,), jnp.uint32))

    @property
    def capacity(self) -> int:
        return self.buf.shape[0]

    def tree_flatten(self):
        return ((self.buf, self.cursor), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def ring_append(ring: EventRing, out: jnp.ndarray, batch_id: jnp.ndarray,
                trace_sample: int = 1024,
                valid: jnp.ndarray = None,
                proxy_ports: jnp.ndarray = None) -> EventRing:
    """Compact one batch's out tensor into the ring (pure device op).

    Keeps every non-TRACE event (drops, NEW-connection policy
    verdicts) plus one in ``trace_sample`` established-flow traces
    (``trace_sample=0`` disables trace sampling entirely).

    ``proxy_ports`` is the live listener table ([MAX_PROXY_PORTS]
    uint32, 0-padded): redirect events store the PORT's index in it
    (4 bits on the wire); pass the same table to :func:`ring_drain`
    to restore ports.  Without it redirect events decode with proxy
    port 0.  Runs under the ``ring`` device-stage scope.
    """
    from ..obs.trace import SCOPE_RING

    with jax.named_scope(SCOPE_RING):
        n = out.shape[0]
        idx = jnp.arange(n, dtype=jnp.uint32)
        keep = out[:, OUT_EVENT] != EV_TRACE
        if trace_sample:
            keep = keep | (idx % trace_sample == 0)
        if valid is not None:
            keep = keep & valid
        assert n <= (1 << 19), "pkt_idx packs into 19 bits"
        pos = jnp.cumsum(keep) - 1  # position among kept rows
        count = keep.sum().astype(jnp.uint32)
        mask = ring.capacity - 1
        lo, hi = ring.cursor[0], ring.cursor[1]
        slot = ((lo + pos.astype(jnp.uint32)) & mask).astype(jnp.int32)
        # newest-wins under overflow: when one batch keeps more events than
        # the ring holds, only the newest `capacity` rows write — otherwise
        # duplicate slot indices in one scatter would make the survivor
        # order unspecified
        newest = pos.astype(jnp.uint32) + ring.capacity >= count
        target = jnp.where(keep & newest, slot, ring.capacity)  # OOB dropped
        o = out.astype(jnp.uint32)
        from ..datapath.verdict import (OUT_CT, OUT_ID_ROW, OUT_PROXY,
                                        OUT_REASON, OUT_VERDICT)

        if proxy_ports is None or proxy_ports.shape[0] == 0:
            # an EMPTY table also means "no listeners" — the sharded step
            # passes a zero-length placeholder because shard_map wants a
            # fixed arity (argmax over a 0-wide axis would be an error)
            pidx = jnp.zeros(n, dtype=jnp.uint32)
        else:
            assert proxy_ports.shape[0] <= MAX_PROXY_PORTS, \
                "listener index packs into 4 bits"
            port = o[:, OUT_PROXY]
            hit = port[:, None] == proxy_ports[None, :].astype(jnp.uint32)
            pidx = jnp.where(
                jnp.any(hit, axis=1) & (port != 0),
                jnp.argmax(hit, axis=1).astype(jnp.uint32) + 1,
                jnp.uint32(0))
        # mask each field to its wire width: a value past its width must
        # corrupt only itself, never a neighbor (the empty-slot sentinel
        # lives in the event bits)
        w0 = ((o[:, OUT_VERDICT] & 0x7) | ((o[:, OUT_EVENT] & 0x3) << 3)
              | ((o[:, OUT_REASON] & 0xF) << 5) | ((o[:, OUT_CT] & 0x7) << 9)
              | (pidx << 12) | ((o[:, OUT_ID_ROW] & 0xFFFF) << 16))
        w1 = idx | ((jnp.uint32(batch_id) & jnp.uint32(0x1FFF)) << 19)
        rows = jnp.stack([w0, w1], axis=1)
        buf = ring.buf.at[target].set(rows, mode="drop")
        new_lo = lo + count
        new_hi = hi + (new_lo < lo).astype(jnp.uint32)  # carry
        return EventRing(buf=buf, cursor=jnp.stack([new_lo, new_hi]))


ring_append_jit = jax.jit(ring_append, donate_argnums=0,
                          static_argnames=("trace_sample",))


def serve_step(state, ring: EventRing, hdr: jnp.ndarray,
               now: jnp.ndarray, batch_id: jnp.ndarray,
               trace_sample: int = 1024, valid: jnp.ndarray = None,
               proxy_ports: jnp.ndarray = None, audit: bool = False):
    """The serving-path step: fused datapath + event-ring append in ONE
    executable (one dispatch per batch; out rows that the compaction
    discards are never materialized).  Returns (state, ring)."""
    from ..datapath.verdict import datapath_step

    out, state = datapath_step(state, hdr, now, valid=valid,
                               audit=audit)
    ring = ring_append(ring, out, batch_id, trace_sample=trace_sample,
                       valid=valid, proxy_ports=proxy_ports)
    return state, ring


serve_step_jit = jax.jit(serve_step, donate_argnums=(0, 1),
                         static_argnames=("trace_sample", "audit"))


def serve_step_packed(state, ring: EventRing, packed: jnp.ndarray,
                      now: jnp.ndarray, batch_id: jnp.ndarray,
                      ep, dirn, trace_sample: int = 1024,
                      valid: jnp.ndarray = None,
                      proxy_ports: jnp.ndarray = None,
                      audit: bool = False):
    """Serving path for the packed ingest format (16 B/packet h2d):
    unpack + fused datapath + ring append, ONE dispatch per batch.
    ``valid`` masks the adaptive batcher's padding rows exactly like
    the wide :func:`serve_step` — padding touches neither CT, metrics,
    nor the ring, so each bucket size stays one compiled shape."""
    from ..datapath.verdict import datapath_step_packed

    out, state = datapath_step_packed(state, packed, now, ep, dirn,
                                      valid=valid, audit=audit)
    ring = ring_append(ring, out, batch_id, trace_sample=trace_sample,
                       valid=valid, proxy_ports=proxy_ports)
    return state, ring


serve_step_packed_jit = jax.jit(serve_step_packed, donate_argnums=(0, 1),
                                static_argnames=("trace_sample",
                                                 "audit"))


# -- K-batch superbatch dispatch (the Python-dispatch diet) ----------
# The datapath math was never the serving ceiling — the per-batch
# Python dispatch (lock acquire, arena bookkeeping, one jit call) was
# (ROADMAP item 1: BENCH_churn's no-churn leg reads 334k pps where
# BENCH_serving sustains ~200-260k).  These steps fuse K batches into
# ONE executable: a lax.scan over the K steps runs datapath + ring
# append per step entirely on device, so the host pays one staging
# copy, one lock window, and one dispatch per K batches.  Each K is
# one compiled shape ([K, bucket, cols] — K rides the shape, so the
# compile-log's one-executable guard keys on (rung, mode, K) for
# free), which is why the serving plane restricts K to a small
# power-of-two ladder (DaemonConfig.serving_superbatch_k).
#
# Per-step ``valid`` masks do double duty: within a step they mask
# the adaptive batcher's padding rows exactly like serve_step, and a
# trailing ALL-FALSE step masks an empty slot of a partially-filled
# superbatch (the batcher rounds the ready-batch count up to the K
# ladder) — an empty step touches neither CT, metrics, nor the ring,
# and appends under a batch id the host never recorded.
#
# Atomicity note (the TableVersioner interplay): the whole scan
# captures ONE ``state`` — a concurrent generation flip lands wholly
# before or wholly after the dispatch, never between inner steps, so
# superbatching cannot tear a table mid-scan; what it DOES stretch is
# update-visible latency (one dispatch pins a generation for K
# batches), which BENCH_churn measures at K>1.


def serve_superbatch(state, ring: EventRing, hdr: jnp.ndarray,
                     now: jnp.ndarray, batch_id0: jnp.ndarray,
                     trace_sample: int = 1024,
                     valid: jnp.ndarray = None,
                     proxy_ports: jnp.ndarray = None,
                     audit: bool = False):
    """K wide batches in one dispatch: ``hdr`` [K, bucket, N_COLS],
    ``valid`` [K, bucket] (REQUIRED — the empty-step masking above
    depends on it), batch ids ``batch_id0 + k`` per step (the ring's
    13-bit field wraps them exactly like the host's seq mask).
    Returns (state, ring) after all K steps."""
    from ..datapath.verdict import datapath_step

    assert valid is not None, "superbatch dispatch requires valid masks"
    K = hdr.shape[0]

    def body(carry, xs):
        st, rg = carry
        hdr_k, valid_k, k = xs
        out, st = datapath_step(st, hdr_k, now, valid=valid_k,
                                audit=audit)
        rg = ring_append(rg, out, batch_id0 + k,
                         trace_sample=trace_sample, valid=valid_k,
                         proxy_ports=proxy_ports)
        return (st, rg), None

    xs = (hdr, valid, jnp.arange(K, dtype=jnp.uint32))
    (state, ring), _ = jax.lax.scan(body, (state, ring), xs)
    return state, ring


serve_superbatch_jit = jax.jit(serve_superbatch, donate_argnums=(0, 1),
                               static_argnames=("trace_sample",
                                                "audit"))


def serve_superbatch_packed(state, ring: EventRing,
                            packed: jnp.ndarray, now: jnp.ndarray,
                            batch_id0: jnp.ndarray,
                            eps: jnp.ndarray, dirns: jnp.ndarray,
                            trace_sample: int = 1024,
                            valid: jnp.ndarray = None,
                            proxy_ports: jnp.ndarray = None,
                            audit: bool = False):
    """K packed batches in one dispatch: ``packed`` [K, bucket, 4]
    (16 B/packet on the h2d link, 4x fewer bytes AND one copy for K
    batches), ``eps``/``dirns`` [K] per-step stream-metadata scalars,
    ``valid`` [K, bucket].  On-device unpack + datapath + ring append
    per scan step; same empty-step semantics as
    :func:`serve_superbatch`."""
    from ..datapath.verdict import datapath_step_packed

    assert valid is not None, "superbatch dispatch requires valid masks"
    K = packed.shape[0]

    def body(carry, xs):
        st, rg = carry
        hdr_k, valid_k, ep_k, dirn_k, k = xs
        out, st = datapath_step_packed(st, hdr_k, now, ep_k, dirn_k,
                                       valid=valid_k, audit=audit)
        rg = ring_append(rg, out, batch_id0 + k,
                         trace_sample=trace_sample, valid=valid_k,
                         proxy_ports=proxy_ports)
        return (st, rg), None

    xs = (packed, valid, eps, dirns, jnp.arange(K, dtype=jnp.uint32))
    (state, ring), _ = jax.lax.scan(body, (state, ring), xs)
    return state, ring


serve_superbatch_packed_jit = jax.jit(serve_superbatch_packed,
                                      donate_argnums=(0, 1),
                                      static_argnames=("trace_sample",
                                                       "audit"))


# -- occupancy-bounded drain (the d2h diet) ---------------------------
# The fetched window's byte count should scale with the EVENTS the
# window appended, not the ring's capacity: `swap` already blocks on
# the 8-byte cursor, so the host knows the occupancy before a single
# buffer byte moves.  A device-side gather pulls just the occupied
# slots (wrap-aware: slot of the i-th surviving event is
# (total - kept + i) & mask, which is the identity prefix [0, total)
# until the ring laps) into a contiguous buffer bucketed to a
# power-of-two RUNG ladder — each rung is ONE compiled executable
# (registered with TPULoader.compile_log like every other serving
# shape), and the d2h copy ships rung*8 bytes instead of capacity*8.
GATHER_MIN_RUNG = 64
# (n_shards, rung, capacity) keys ring_gather has compiled in this
# process (its jit cache is process-wide too): a swap about to compile
# a new one calls its ``on_cold`` hook first
_WARM_GATHERS: set = set()


def _gather_rung(kept: int, cap: int) -> int:
    """Smallest ladder rung holding ``kept`` rows (power of two,
    floored at GATHER_MIN_RUNG, capped at the ring capacity)."""
    r = min(GATHER_MIN_RUNG, cap)
    while r < kept:
        r <<= 1
    return min(r, cap)


@partial(jax.jit, static_argnames=("rung", "cap"))
def ring_gather(buf: jnp.ndarray, starts: jnp.ndarray, rung: int,
                cap: int) -> jnp.ndarray:
    """Gather each shard's occupied window slots, in append order,
    into a contiguous [n_shards * rung, RING_WORDS] buffer.

    ``buf`` is [n_shards * cap, RING_WORDS] (n_shards=1 for the
    single-chip ring), ``starts`` [n_shards] uint32 — each shard's
    oldest surviving slot ((total - kept) & mask; 0 until the ring
    laps).  Slots past a shard's occupancy are EMPTY on a fresh-per-
    window ring, so the host's empty-slot filter drops them exactly
    like the full-copy path.  One executable per (rung, shard count):
    ``starts`` is traced, the rung is static."""
    n_shards = starts.shape[0]
    offs = jnp.arange(rung, dtype=jnp.uint32)[None, :]
    idx = (starts[:, None] + offs) & jnp.uint32(cap - 1)
    idx = idx + (jnp.arange(n_shards, dtype=jnp.uint32)
                 * jnp.uint32(cap))[:, None]
    return buf[idx.reshape(-1).astype(jnp.int32)]


def _cursor_totals(cursor: np.ndarray) -> np.ndarray:
    """Host cursor ([2] or [S, 2] of u32 lo/hi words) -> int64 totals
    per shard ([S])."""
    c = np.asarray(cursor, dtype=np.uint64).reshape(-1, 2)
    return (c[:, 0] | (c[:, 1] << np.uint64(32))).astype(np.int64)


@dataclass
class RingWindow:
    """One drained window's in-flight handle: the device buffer whose
    host copy is already streaming, plus everything the EVENT-JOIN
    WORKER (serving/eventplane.py) needs to finish off the dispatch
    path — the synced host cursor, the occupancy/loss math done at
    swap time, and the originating drainer for counter accounting.

    Ownership: ``swap_window`` hands the window out and the drainer
    forgets it; exactly one thread (the worker, or a legacy
    ``collect()`` caller) calls :meth:`fetch` exactly once."""

    buf: Optional[object]  # device rows (None = empty window)
    cursor: np.ndarray  # host copy, [n_shards(|1), 2] u32
    capacity: int
    n_shards: int  # 0 = single-chip ring
    appended: int  # events appended across shards this window
    lost: int  # lap loss (appended - capacity when the host lagged)
    d2h_bytes: int  # bytes this window put on the d2h link
    gathered: bool  # buf is a rung gather, already in append order
    rung: int
    proxy_ports: Optional[np.ndarray]
    drainer: object
    t_swap: float = field(default_factory=time.monotonic)

    def fetch(self):
        # thread-affinity: event-worker, api, offline -- the blocking
        # d2h wait lives here; the drain thread only ever swaps
        """Complete the transfer and decode.  Returns
        ``(rows, shard_ids, appended, lost)``; ``shard_ids`` is None
        for a single-chip window.  Updates the originating drainer's
        windows/events/lost counters (single-writer: whoever owns the
        window)."""
        d = self.drainer
        if self.buf is None:
            rows = np.zeros((0, RING_COLS), dtype=np.uint32)
            shards = (np.zeros(0, dtype=np.int64)
                      if self.n_shards else None)
            if d is not None:
                d.windows += 1
            return rows, shards, 0, 0
        buf = np.asarray(self.buf)  # blocks until the copy lands
        self.buf = None
        totals = _cursor_totals(self.cursor)
        cap = self.capacity
        if self.n_shards:
            S = self.n_shards
            blk = buf.shape[0] // S
            parts: List[np.ndarray] = []
            sids: List[np.ndarray] = []
            for s in range(S):
                r, _total, _lost = _decode_fetched(
                    buf[s * blk:(s + 1) * blk], int(totals[s]), cap,
                    self.proxy_ports, gathered=self.gathered)
                parts.append(r)
                sids.append(np.full(len(r), s, dtype=np.int64))
            rows = (np.concatenate(parts) if parts else
                    np.zeros((0, RING_COLS), dtype=np.uint32))
            shards = (np.concatenate(sids) if sids else
                      np.zeros(0, dtype=np.int64))
        else:
            rows, _total, _lost = _decode_fetched(
                buf, int(totals[0]), cap, self.proxy_ports,
                gathered=self.gathered)
            shards = None
        if d is not None:
            d.windows += 1
            d.events += self.appended - self.lost
            d.lost += self.lost
        return rows, shards, self.appended, self.lost


def _start_window(ring: EventRing, capacity: int, n_shards: int,
                  proxy_ports, drainer, gather: bool,
                  compile_log, on_cold=None) -> RingWindow:
    # thread-affinity: drain, api, offline
    """The shared swap leg: sync the cursor (retires every queued
    dispatch — see AsyncRingDrainer.swap), do the occupancy math on
    host, start the async copy of either the rung gather or the full
    buffer, and wrap it all in a :class:`RingWindow`."""
    # hot-path-ok: the load-bearing 8-byte cursor sync — the synced
    # cursor is what makes the occupancy-bounded gather possible
    ring.cursor.block_until_ready()
    cur = np.array(np.asarray(ring.cursor), copy=True).reshape(-1, 2)
    totals = _cursor_totals(cur)
    appended = int(totals.sum())
    lost = int(np.maximum(totals - capacity, 0).sum())
    if appended == 0:
        return RingWindow(buf=None, cursor=cur, capacity=capacity,
                          n_shards=n_shards, appended=0, lost=0,
                          d2h_bytes=0, gathered=False, rung=0,
                          proxy_ports=proxy_ports, drainer=drainer)
    if gather:
        kept = np.minimum(totals, capacity)
        rung = _gather_rung(int(kept.max()), capacity)
        # oldest surviving slot per shard: 0 until the ring laps,
        # then the wrapped cursor (total & mask)
        starts = np.where(totals > capacity,
                          totals & (capacity - 1),
                          0).astype(np.uint32)
        key = (max(n_shards, 1), rung, capacity)
        if on_cold is not None and key not in _WARM_GATHERS:
            on_cold()
        size = getattr(ring_gather, "_cache_size", lambda: 0)
        before = size() if compile_log is not None else 0
        t0 = time.monotonic()
        buf = ring_gather(ring.buf, starts, rung, capacity)
        _WARM_GATHERS.add(key)
        if compile_log is not None:
            after = size()
            if after > before:
                compile_log.record_dispatch(
                    "gather", (max(n_shards, 1), rung), before, after,
                    time.monotonic() - t0, key_extra=(capacity,))
        buf.copy_to_host_async()
        return RingWindow(buf=buf, cursor=cur, capacity=capacity,
                          n_shards=n_shards, appended=appended,
                          lost=lost, d2h_bytes=buf.nbytes + cur.nbytes,
                          gathered=True, rung=rung,
                          proxy_ports=proxy_ports, drainer=drainer)
    ring.buf.copy_to_host_async()
    return RingWindow(buf=ring.buf, cursor=cur, capacity=capacity,
                      n_shards=n_shards, appended=appended, lost=lost,
                      d2h_bytes=ring.buf.nbytes + cur.nbytes,
                      gathered=False, rung=capacity,
                      proxy_ports=proxy_ports, drainer=drainer)


class AsyncRingDrainer:
    """Double-buffered drain: the host fetches window N-1 while the
    device steps window N.

    ``ring_drain`` blocks on a device->host copy that must first
    retire every dispatch queued since the previous fetch.  Double
    buffering hides that wait: at each
    window boundary ``swap(ring)`` starts an ASYNC copy of the
    just-filled ring and hands the serve loop a fresh one, and
    ``collect()`` completes the transfer that has been streaming in
    the background — by then the bytes are already on host.  This is
    also the production shape of the reference's perf-buffer consumer
    (the kernel keeps appending to live pages while userspace reads
    the pages it was handed).

    Because every window starts on a fresh ring, the fetched cursor
    IS the window's append count and per-window loss is
    ``max(0, appended - capacity)`` with no cross-window bookkeeping.
    """

    def __init__(self, capacity: int = 1 << 15,
                 proxy_ports: np.ndarray = None,
                 gather: bool = True, compile_log=None):
        self.capacity = capacity
        self.proxy_ports = proxy_ports
        # occupancy-bounded fetch (module comment at GATHER_MIN_RUNG):
        # d2h bytes scale with the window's events, not the capacity.
        # compile_log (TPULoader.compile_log) records the bucketed
        # gather's rung executables under the same one-executable-
        # per-(rung, mode) guard as the serve steps
        self.gather = bool(gather)
        self.compile_log = compile_log
        self._pending: Optional[RingWindow] = None
        self.windows = 0
        self.events = 0
        self.lost = 0

    def fresh(self) -> EventRing:
        return EventRing.create(self.capacity)

    def swap_window(self, ring: EventRing, on_cold=None
                    ) -> Tuple[RingWindow, EventRing]:
        # thread-affinity: drain, api, offline
        """Start the async fetch of ``ring`` and hand its window out
        as a :class:`RingWindow` (ownership transfers to the caller —
        the event-join worker's shape); returns the fresh ring for
        the next window alongside it.

        The block_until_ready on the CURSOR before the copy is
        load-bearing: the synced cursor IS the window's event count,
        so the occupancy-bounded gather's rung is known before a
        single buffer byte moves.  ``on_cold()`` runs just before a
        gather rung's first compile in this process."""
        from ..infra import faults

        faults.check(faults.SITE_RING_SWAP)
        window = _start_window(ring, self.capacity, 0,
                               self.proxy_ports, self, self.gather,
                               self.compile_log, on_cold)
        return window, self.fresh()

    def swap(self, ring: EventRing) -> EventRing:
        # thread-affinity: drain, api, offline
        """Legacy single-window double buffering: start the async
        fetch, retain the window internally for :meth:`collect`.  At
        most one fetch may be in flight."""
        assert self._pending is None, "previous window not collected"
        window, fresh = self.swap_window(ring)
        self._pending = window
        return fresh

    def collect(self) -> Tuple[np.ndarray, int, int]:
        # thread-affinity: event-worker, api, offline
        """Complete the in-flight fetch -> (rows, appended, lost) for
        that window (empty result when nothing is pending)."""
        from ..infra import faults

        faults.check(faults.SITE_RING_COLLECT)
        window = self._pending
        if window is None:
            return np.zeros((0, RING_COLS), dtype=np.uint32), 0, 0
        self._pending = None
        rows, _shards, appended, lost = window.fetch()
        return rows, appended, lost


def _unpack_rows(packed: np.ndarray,
                 proxy_ports: np.ndarray = None) -> np.ndarray:
    """Packed [m, RING_WORDS] device rows -> decoded [m, RING_COLS]
    (OUT_* columns + pkt_idx + batch), pure host numpy.
    ``proxy_ports`` (same table given to :func:`ring_append`) restores
    redirect ports from their 4-bit wire index."""
    from ..datapath.verdict import (OUT_CT, OUT_ID_ROW, OUT_PROXY,
                                    OUT_REASON, OUT_VERDICT)

    w0, w1 = packed[:, 0], packed[:, 1]
    rows = np.empty((len(packed), RING_COLS), dtype=np.uint32)
    rows[:, OUT_VERDICT] = w0 & 0x7
    rows[:, OUT_EVENT] = (w0 >> 3) & 0x3
    rows[:, OUT_REASON] = (w0 >> 5) & 0xF
    rows[:, OUT_CT] = (w0 >> 9) & 0x7
    pidx = (w0 >> 12) & 0xF
    if proxy_ports is None:
        rows[:, OUT_PROXY] = 0
    else:
        # pad to the full 4-bit index space: a drain given a SHORTER
        # table than append used (listener removed between windows)
        # must degrade stale rows to port 0, not crash the drain
        table = np.zeros(MAX_PROXY_PORTS + 1, dtype=np.uint32)
        pp = np.asarray(proxy_ports, dtype=np.uint32)
        table[1:1 + len(pp)] = pp
        rows[:, OUT_PROXY] = table[pidx]
    rows[:, OUT_ID_ROW] = w0 >> 16
    rows[:, COL_PKT_IDX] = w1 & 0x7FFFF
    rows[:, COL_BATCH] = w1 >> 19
    return rows


def _decode_fetched(buf: np.ndarray, total: int, cap: int,
                    proxy_ports: np.ndarray = None,
                    gathered: bool = False
                    ) -> Tuple[np.ndarray, int, int]:
    # thread-affinity: event-worker, api, cli, offline
    """Decode ONE ring's fetched window given its 64-bit append total:
    wrap/lost math, empty-slot filter, wire unpack.  The single
    definition of the drain rules — :func:`ring_drain` (one ring),
    :func:`sharded_ring_drain` (per-chip rings), and
    :meth:`RingWindow.fetch` (the async event plane) all land here,
    so a future wire-format change (e.g. widening the 4-bit reason
    field) lands in one place.

    ``gathered=True`` means ``buf`` is a :func:`ring_gather` output:
    already rotated into append order on device (its length is the
    rung, not the capacity), so only the prefix/empty filter
    applies."""
    lost = max(0, total - cap)
    if gathered:
        rows = buf[:min(total, cap, buf.shape[0])]
    elif total <= cap:
        rows = buf[:total]
    else:
        head = total & (cap - 1)
        rows = np.concatenate([buf[head:], buf[:head]])
    # empty slots carry event bits 0b11 (no EV_* code is 3)
    rows = rows[((rows[:, 0] >> 3) & 0x3) != 0x3]
    return _unpack_rows(rows, proxy_ports), total, lost


def _drain_window(buf: np.ndarray, cursor: np.ndarray,
                  proxy_ports: np.ndarray = None
                  ) -> Tuple[np.ndarray, int, int]:
    """Legacy full-copy decode: cursor words -> total, then
    :func:`_decode_fetched` over the whole fetched buffer."""
    total = int(_cursor_totals(cursor)[0])
    return _decode_fetched(buf, total, buf.shape[0], proxy_ports)


def sharded_ring_drain(buf: np.ndarray, cursor: np.ndarray,
                       proxy_ports: np.ndarray = None
                       ) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """Host decode of a SHARDED ring window (per-chip private rings
    drained round-robin, shard 0 first).

    ``buf`` is the fetched [n_shards * cap, RING_WORDS] buffer (shard
    s owns rows [s*cap, (s+1)*cap)), ``cursor`` the [n_shards, 2]
    per-shard cursors.  Returns ``(rows, shard_ids, appended, lost)``
    — ``rows`` decoded like :func:`ring_drain` with shard-LOCAL packet
    indices, ``shard_ids`` aligned per row so the caller can map a row
    back to its per-shard header block (global row = shard * block +
    pkt_idx; the header join knows each batch's block)."""
    n_shards = cursor.shape[0]
    cap = buf.shape[0] // n_shards
    parts: List[np.ndarray] = []
    shard_ids: List[np.ndarray] = []
    appended = lost = 0
    for s in range(n_shards):
        rows, total, lost_s = _drain_window(
            buf[s * cap:(s + 1) * cap], cursor[s], proxy_ports)
        parts.append(rows)
        shard_ids.append(np.full(len(rows), s, dtype=np.int64))
        appended += total
        lost += lost_s
    return (np.concatenate(parts) if parts
            else np.zeros((0, RING_COLS), dtype=np.uint32),
            np.concatenate(shard_ids) if shard_ids
            else np.zeros(0, dtype=np.int64),
            appended, lost)


class ShardedAsyncRingDrainer:
    """The :class:`AsyncRingDrainer` shape for per-chip rings: one
    device-sharded (buf, cursor) pair holds every chip's private ring;
    ``swap`` starts the async fetch of the just-filled window and
    hands back a fresh one, ``collect`` completes it and decodes the
    shards round-robin.  Loss accounting is per shard per window
    (every window starts on fresh rings), summed."""

    def __init__(self, capacity: int, n_shards: int,
                 fresh_fn, proxy_ports: np.ndarray = None,
                 gather: bool = True, compile_log=None):
        # fresh_fn: () -> device EventRing with buf [S*cap, RING_WORDS]
        # sharded on axis 0 and cursor [S, 2] sharded (parallel.mesh
        # builds it — placement needs the mesh, which lives there)
        self.capacity = capacity
        self.n_shards = n_shards
        self.proxy_ports = proxy_ports
        self._fresh_fn = fresh_fn
        self.gather = bool(gather)
        self.compile_log = compile_log
        self._pending: Optional[RingWindow] = None
        self.windows = 0
        self.events = 0
        self.lost = 0

    def fresh(self):
        return self._fresh_fn()

    def swap_window(self, ring, on_cold=None
                    ) -> Tuple[RingWindow, object]:
        # thread-affinity: drain, api, offline
        """Same cursor-first sync discipline as the single-chip
        drainer (see AsyncRingDrainer.swap_window): block on the
        small cursor, then the (gathered) buffer bytes stream in the
        background.  The gather rung is COMMON across shards (the max
        occupancy, bucketed) so the fetched layout stays one block
        per shard."""
        from ..infra import faults

        faults.check(faults.SITE_RING_SWAP)
        window = _start_window(ring, self.capacity, self.n_shards,
                               self.proxy_ports, self, self.gather,
                               self.compile_log, on_cold)
        return window, self.fresh()

    def swap(self, ring):
        # thread-affinity: drain, api, offline
        assert self._pending is None, "previous window not collected"
        window, fresh = self.swap_window(ring)
        self._pending = window
        return fresh

    def collect(self) -> Tuple[np.ndarray, np.ndarray, int, int]:
        # thread-affinity: event-worker, api, offline
        from ..infra import faults

        faults.check(faults.SITE_RING_COLLECT)
        window = self._pending
        if window is None:
            return (np.zeros((0, RING_COLS), dtype=np.uint32),
                    np.zeros(0, dtype=np.int64), 0, 0)
        self._pending = None
        rows, shards, appended, lost = window.fetch()
        return rows, shards, appended, lost


def ring_drain(ring: EventRing,
               proxy_ports: np.ndarray = None
               ) -> Tuple[np.ndarray, int, int]:
    """Fetch + decode the ring on host.

    Returns (rows [m, RING_COLS] in append order, total_appended,
    n_overwritten).  The single host fetch happens HERE, at the
    monitor's cadence — never in the datapath hot loop."""
    return _drain_window(np.asarray(ring.buf), np.asarray(ring.cursor),
                         proxy_ports)
