"""Mesh construction, flow routing, and the sharded datapath step.

Reference mapping: cilium scales per-packet work across CPUs (per-CPU
eBPF execution, RSS steering flows to CPUs) and across nodes (one
agent per node, identities replicated via kvstore).  Here:

- ``make_mesh``: 1-D device mesh over the ``data`` axis (chips).
- ``flow_shard_ids``: symmetric (direction-invariant) flow hash so
  both directions of a flow land on the same chip — the RSS analogue.
- ``route_by_flow``: host-side packet steering into equal-size
  per-shard blocks (padding masked via ``valid``).
- ``make_sharded_step``: ``shard_map``-wrapped ``datapath_step`` —
  policy/ipcache tensors replicated, conntrack sharded (each chip owns
  a private CT shard), batch sharded; drop/metric counters are
  ``psum``-ed so every replica carries the global totals, the way
  every cilium agent sees the cluster-wide identity state.

Multi-host: the same mesh spans hosts under ``jax.distributed`` — XLA
runs the psums over ICI/DCN; no application code changes (the
ClusterMesh analogue).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.packets import (
    COL_DPORT,
    COL_DST_IP0,
    COL_PROTO,
    COL_SPORT,
    COL_SRC_IP0,
    N_COLS,
)
from ..datapath.conntrack import CTTable
from ..datapath.verdict import DatapathState, datapath_step


def make_mesh(n_devices: Optional[int] = None, axis: str = "data") -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (all by default).
    Raises when fewer exist: a smaller mesh would run a different
    deployment under the requested name."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"make_mesh({n_devices}) needs {n_devices} devices; "
                f"{len(devs)} {devs[0].platform} device(s) present")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def _flow_hash_mix(src: np.ndarray, dst: np.ndarray,
                   sport: np.ndarray, dport: np.ndarray,
                   proto: np.ndarray, n_shards: int) -> np.ndarray:
    """The ONE symmetric flow-hash definition (uint64 inputs).

    Commutative combines of src/dst words and ports, so forward and
    reply orientations hash identically — shared by the header path
    (:func:`flow_shard_ids`) and the CT-snapshot path
    (:func:`ct_rows_slot_ids`): a CT row MUST land on the same slot
    as the packets that created it, or scale-out migration
    (cluster/scale.py) would ship the wrong entries."""
    h = np.zeros(len(proto), dtype=np.uint64)
    for w in range(4):
        h = h * 31 + (src[:, w] + dst[:, w])
        h ^= (src[:, w] ^ dst[:, w]) * np.uint64(0x9E3779B97F4A7C15)
    h += (sport + dport) * np.uint64(0x85EBCA6B)
    h ^= (sport ^ dport) * np.uint64(0xC2B2AE35)
    h += proto
    h ^= h >> 33
    h *= np.uint64(0xFF51AFD7ED558CCD)
    h ^= h >> 33
    return (h % np.uint64(n_shards)).astype(np.int64)


def flow_shard_ids(data: np.ndarray, n_shards: int) -> np.ndarray:
    """Symmetric flow hash -> shard id per packet (host numpy).

    Direction-invariant: uses commutative combines of src/dst words and
    ports so a flow's forward and reply packets hash identically."""
    from ..core.packets import normalize_ports

    d = data.astype(np.uint64)
    src = d[:, COL_SRC_IP0:COL_SRC_IP0 + 4]
    dst = d[:, COL_DST_IP0:COL_DST_IP0 + 4]
    # same tuple normalization as ct_keys_from_headers, or a flow's
    # packets would land on a shard that doesn't own its CT entry
    sport, dport = normalize_ports(np, d[:, COL_PROTO], d[:, COL_SPORT],
                                   d[:, COL_DPORT])
    return _flow_hash_mix(src, dst, sport, dport, d[:, COL_PROTO],
                          n_shards)


def ct_rows_slot_ids(rows: np.ndarray, n_shards: int) -> np.ndarray:
    """Dense CT snapshot rows ([n, ROW_WORDS], conntrack layout) ->
    the same flow slot :func:`flow_shard_ids` assigns the flow's
    packets.

    The CT key already carries NORMALIZED ports (word 8 =
    sport << 16 | dport after ``normalize_ports``) and the proto in
    word 9's low byte, and the hash mix is commutative in both the
    address pair and the port pair — so hashing straight from the
    key words reproduces the header-side slot regardless of which
    direction created the entry.  This is scale-out migration's
    selector: exactly the moved slots' entries ship to the new
    owner."""
    d = np.asarray(rows).astype(np.uint64)
    if d.ndim != 2 or d.shape[1] < 10:
        raise ValueError(
            f"want dense CT rows [n, ROW_WORDS], got {d.shape}")
    src = d[:, 0:4]
    dst = d[:, 4:8]
    ports = d[:, 8]
    sport = ports >> np.uint64(16)
    dport = ports & np.uint64(0xFFFF)
    proto = d[:, 9] & np.uint64(0xFF)
    return _flow_hash_mix(src, dst, sport, dport, proto, n_shards)


def route_by_flow(data: np.ndarray, n_shards: int,
                  block: Optional[int] = None,
                  out: Optional[Tuple[np.ndarray, np.ndarray,
                                      np.ndarray]] = None
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Steer packets into equal-size per-shard blocks (host side).

    Returns (routed [n_shards*block, N_COLS], valid [...] bool,
    orig_idx [...] int64 — original row index, -1 on padding,
    n_overflow — packets dropped because their shard's block was full).
    The RSS analogue: the device-side pipeline shards this batch
    contiguously; an overflow is an RSS queue overflow and MUST be
    accounted (feed ``n_overflow`` to :func:`add_route_overflow` so it
    lands in the metricsmap like CT map-pressure drops do).

    ``block`` (per-shard rows) should be FIXED by the caller across
    batches — a data-dependent shape would retrace the jitted sharded
    step every batch.  Default: 2x the fair share, rounded to a power
    of two.

    ``out`` is an optional preallocated ``(routed, valid, orig)``
    triple (e.g. serving-arena slots) with shapes
    ``[n_shards*block, N_COLS] u32 / [n_shards*block] bool / int64``
    — the serving hot path reuses buffers instead of allocating per
    batch; contents are fully overwritten."""
    ids = flow_shard_ids(data, n_shards)
    if block is None:
        fair = max(-(-len(data) // n_shards), 1)
        block = 1
        while block < 2 * fair:
            block *= 2
    # Vectorized steering (this sits in the ingest hot path — the r02
    # per-shard Python loop cost n_shards full-array passes): one
    # stable argsort groups packets by shard; a packet's slot is
    # shard*block + its rank within the shard, ranks >= block are the
    # RSS-queue-overflow drops.
    n = len(data)
    order = np.argsort(ids, kind="stable")
    sorted_ids = ids[order]
    counts = np.bincount(ids, minlength=n_shards)
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    rank = np.arange(n, dtype=np.int64) - starts[sorted_ids]
    keep = rank < block
    n_overflow = int(n - keep.sum())
    dest = sorted_ids[keep] * block + rank[keep]
    src_rows = order[keep]
    if out is None:
        routed = np.zeros((n_shards * block, N_COLS), dtype=np.uint32)
        valid = np.zeros(n_shards * block, dtype=bool)
        orig = np.full(n_shards * block, -1, dtype=np.int64)
    else:
        routed, valid, orig = out
        assert routed.shape[0] == valid.shape[0] == orig.shape[0] \
            == n_shards * block, "out buffers must match the routed shape"
        routed[:] = 0
        valid[:] = False
        orig[:] = -1
    routed[dest] = data[src_rows]
    valid[dest] = True
    orig[dest] = src_rows
    return routed, valid, orig, n_overflow


def add_host_drops(state: DatapathState, reason: int,
                   n: int) -> DatapathState:
    """Account host-side drops in the device metricsmap (ingress
    column) so the loss is visible to operators exactly like CT
    map-pressure drops.  Used for every drop class that never reaches
    the device: flow-router overflow (REASON_ROUTE_OVERFLOW), and the
    serving recovery plane's lost batches (REASON_DISPATCH_TIMEOUT /
    REASON_RECOVERY_DROP).  Sharding-preserving (.at on the
    replicated array)."""
    if n == 0:
        return state
    metrics = state.metrics.at[int(reason), 0].add(jnp.uint32(n))
    return DatapathState(policy=state.policy, ipcache=state.ipcache,
                         ct=state.ct, metrics=metrics)


def add_route_overflow(state: DatapathState, n: int) -> DatapathState:
    """RSS-queue-overflow accounting: see :func:`add_host_drops`."""
    from ..datapath.verdict import REASON_ROUTE_OVERFLOW

    return add_host_drops(state, REASON_ROUTE_OVERFLOW, n)


def shard_state(state: DatapathState, mesh: Mesh,
                axis: str = "data") -> DatapathState:
    """Place device state per the sharded-step layout: CT table sharded
    over chips, everything else replicated."""
    repl = NamedSharding(mesh, P())
    # P(axis), not P(axis, None): the spellings place identically but
    # the compile cache keys on them — see make_sharded_ring
    ct_sh = NamedSharding(mesh, P(axis))
    fp_sh = NamedSharding(mesh, P(axis))

    def put(x, sharding):
        return jax.device_put(x, sharding)

    return DatapathState(
        policy=jax.tree.map(lambda x: put(x, repl), state.policy),
        ipcache=jax.tree.map(lambda x: put(x, repl), state.ipcache),
        ct=CTTable(table=put(state.ct.table, ct_sh),
                   fp=put(state.ct.fp, fp_sh),
                   dropped=put(state.ct.dropped, repl)),
        metrics=put(state.metrics, repl),
    )


def make_sharded_ring(mesh: Mesh, capacity: int, axis: str = "data"):
    """Per-chip private event rings as ONE device-sharded EventRing:
    ``buf`` [n_shards * capacity, RING_WORDS] sharded on axis 0 (shard
    s owns its contiguous block), ``cursor`` [n_shards, 2] sharded.
    Inside the sharded serve step each chip sees exactly a single-chip
    ring and appends locally — no cross-chip traffic on the monitor
    plane, the per-CPU perf-ring layout."""
    from ..monitor.ring import RING_WORDS, EventRing, _EMPTY

    assert capacity & (capacity - 1) == 0, "capacity must be 2^k"
    n_shards = mesh.devices.size
    # P(axis), NOT P(axis, None): jit normalizes output specs by
    # trimming trailing Nones, and the two spell the SAME placement —
    # but the compilation cache keys on the spelling, so a fresh ring
    # written P(axis, None) would recompile the serve step every
    # window swap (caught by the recompile-guard test)
    row_sh = NamedSharding(mesh, P(axis))
    buf = jax.device_put(
        jnp.full((n_shards * capacity, RING_WORDS), _EMPTY,
                 dtype=jnp.uint32), row_sh)
    cursor = jax.device_put(
        jnp.zeros((n_shards, 2), dtype=jnp.uint32), row_sh)
    return EventRing(buf=buf, cursor=cursor)


def make_sharded_serve_step(mesh: Mesh, axis: str = "data",
                            packed: bool = False,
                            trace_sample: int = 1024,
                            audit: bool = False) -> Callable:
    """Build the jitted multi-chip SERVING step: per shard, fused
    datapath + event-ring append (monitor/ring.py serve_step) with the
    CT private per chip, policy/ipcache replicated, counters psum-ed,
    and each chip appending to its own private ring block (see
    :func:`make_sharded_ring`).

    ``packed=True`` builds the 16 B/packet variant: ``hdr`` is the
    flow-routed packed tensor [n_shards*block, 4] and ``ep``/``dirn``
    ride as replicated scalars (stream metadata); the wide tensor is
    only ever materialized on device, per shard.

    step(state, ring, hdr, now, batch_id, valid, proxy_ports[, ep,
    dirn]) -> (state', ring') with hdr/valid sharded on the batch
    axis.  ``proxy_ports`` must be a device array (possibly length 0 —
    "no listeners"); ``trace_sample``/``audit`` are baked into the
    built step (they are per serving session, and the loader caches
    one step per configuration)."""
    from ..datapath.verdict import datapath_step, datapath_step_packed
    from ..monitor.ring import EventRing, ring_append

    state_specs = (P(), P(), P(axis, None), P(axis), P(), P())
    ring_specs = (P(axis, None), P(axis, None))
    meta_specs = ((P(), P()) if packed else ())

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=state_specs + ring_specs
        + (P(axis, None), P(), P(), P(axis), P()) + meta_specs,
        out_specs=(P(axis, None), P(axis), P(), P(),
                   P(axis, None), P(axis, None)),
    )
    def _step(policy, ipcache, ct_table, ct_fp, ct_dropped, metrics,
              rbuf, rcur, hdr, now, batch_id, valid, proxy_ports,
              *meta):
        state = DatapathState(
            policy=policy, ipcache=ipcache,
            ct=CTTable(table=ct_table, fp=ct_fp, dropped=ct_dropped),
            metrics=metrics)
        if packed:
            ep, dirn = meta
            out, ns = datapath_step_packed(state, hdr, now, ep, dirn,
                                           valid=valid, audit=audit)
        else:
            out, ns = datapath_step(state, hdr, now, valid=valid,
                                    audit=audit)
        ring = ring_append(EventRing(buf=rbuf, cursor=rcur[0]), out,
                           batch_id, trace_sample=trace_sample,
                           valid=valid, proxy_ports=proxy_ports)
        d_dropped = jax.lax.psum(ns.ct.dropped - ct_dropped, axis)
        d_metrics = jax.lax.psum(ns.metrics - metrics, axis)
        return (ns.ct.table, ns.ct.fp, ct_dropped + d_dropped,
                metrics + d_metrics, ring.buf, ring.cursor[None])

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(state: DatapathState, ring, hdr: jnp.ndarray,
             now: jnp.ndarray, batch_id: jnp.ndarray,
             valid: jnp.ndarray, proxy_ports: jnp.ndarray,
             ep=None, dirn=None):
        meta = (ep, dirn) if packed else ()
        table, fp, dropped, metrics, rbuf, rcur = _step(
            state.policy, state.ipcache, state.ct.table, state.ct.fp,
            state.ct.dropped, state.metrics, ring.buf, ring.cursor,
            hdr, now, batch_id, valid, proxy_ports, *meta)
        return (DatapathState(
            policy=state.policy, ipcache=state.ipcache,
            ct=CTTable(table=table, fp=fp, dropped=dropped),
            metrics=metrics),
            EventRing(buf=rbuf, cursor=rcur))

    return step


def make_sharded_step(mesh: Mesh, axis: str = "data") -> Callable:
    """Build the jitted multi-chip datapath step.

    step(state, hdr, now, valid) -> (out, state') with hdr/out sharded
    on the batch axis, CT sharded, policy/ipcache replicated."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(axis, None), P(axis), P(), P(),
                  P(axis, None), P(), P(axis)),
        out_specs=(P(axis, None), P(axis, None), P(axis), P(), P()),
    )
    def _step(policy, ipcache, ct_table, ct_fp, ct_dropped, metrics,
              hdr, now, valid):
        state = DatapathState(
            policy=policy, ipcache=ipcache,
            ct=CTTable(table=ct_table, fp=ct_fp, dropped=ct_dropped),
            metrics=metrics)
        out, ns = datapath_step(state, hdr, now, valid=valid)
        # counters are replicated state: accumulate the global delta so
        # every replica agrees (the kvstore-replication analogue)
        d_dropped = jax.lax.psum(ns.ct.dropped - ct_dropped, axis)
        d_metrics = jax.lax.psum(ns.metrics - metrics, axis)
        return (out, ns.ct.table, ns.ct.fp, ct_dropped + d_dropped,
                metrics + d_metrics)

    @partial(jax.jit, donate_argnums=0)
    def step(state: DatapathState, hdr: jnp.ndarray, now: jnp.ndarray,
             valid: jnp.ndarray) -> Tuple[jnp.ndarray, DatapathState]:
        out, table, fp, dropped, metrics = _step(
            state.policy, state.ipcache, state.ct.table, state.ct.fp,
            state.ct.dropped, state.metrics, hdr, now, valid)
        return out, DatapathState(
            policy=state.policy, ipcache=state.ipcache,
            ct=CTTable(table=table, fp=fp, dropped=dropped),
            metrics=metrics)

    return step
