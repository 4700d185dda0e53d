#!/usr/bin/env python
"""Headline benchmark: policy verdicts/sec on one chip.

BASELINE.md north-star: >= 10M policy verdicts/sec on one TPU v5e chip
over the 10k-identity L3/L4 policy set, <= 1% divergence vs the oracle.

Two phases, one JSON line:

1. **device** — the fused pipeline (ipcache LPM -> conntrack -> policy
   -> ct-create -> events) replaying pre-staged device batches: the
   kernel-rate ceiling (headline metric, matches BASELINE's
   verdicts/s/chip definition).
2. **end_to_end** — the honest number: raw ethernet frames in host
   memory -> native C++ parse -> header tensor -> device_put -> fused
   pipeline -> device event ring (compacted drops/verdicts/sampled
   traces, monitor/ring.py) -> single host drain.  Non-replayed
   traffic (every batch distinct), advancing clock.

   The event-ring architecture mirrors the reference (the kernel
   streams *events* through the perf ring and counts the rest in the
   metricsmap; it does not copy every packet to userspace), so the
   hot loop is fetch-free.

Output: ONE JSON line {"metric", "value", "unit", "vs_baseline",
"end_to_end": {...}} — extra keys carry the e2e numbers + bottleneck
split.
"""

import json
import time

import numpy as np

# 262144 packets/batch: large enough that per-dispatch host cost is
# amortized over the batch (BASELINE config #3's batch)
BATCH = 1 << 18


def _pow2_cap(n_events: int) -> int:
    """Smallest power-of-two ring capacity holding ``n_events``
    (EventRing.create asserts 2^k)."""
    return 1 << max(0, int(n_events) - 1).bit_length()
BASELINE_PPS = 10_000_000.0  # north-star target


def paired_legs(baseline_fn, candidate_fn, reps: int = 3) -> dict:
    """The bench "machine weather" convention promoted into tooling
    (ISSUE 11 satellite): run ``baseline_fn``/``candidate_fn``
    INTERLEAVED rep-by-rep with the pair order ALTERNATING per rep
    (whichever leg runs second in a pair reads a few percent faster
    on this box — thermal/cache settling — so a fixed order
    masquerades as a real difference), and report the PER-PAIR ratios
    and their spread alongside the best absolute legs.  A ratio of
    two legs from the SAME pair survives weather a best-vs-best
    ratio does not: a throttle window slows both legs together.

    Each fn returns ``pps`` (float) or ``(pps, extra)``; ``extra``
    of the best rep per side rides the result.  Returns::

        {"baseline_pps", "candidate_pps",          # best-of-reps
         "pairs": [candidate/baseline per rep],    # the honest view
         "ratio_best", "ratio_median", "spread",
         "baseline_extra", "candidate_extra"}
    """
    base_best = cand_best = 0.0
    base_extra = cand_extra = None
    pairs = []
    for rep in range(reps):
        legs = [("b", baseline_fn), ("c", candidate_fn)]
        if rep % 2:
            legs.reverse()
        res = {}
        for name, fn in legs:
            out = fn()
            res[name] = out if isinstance(out, tuple) else (out, None)
        b, be = res["b"]
        c, ce = res["c"]
        pairs.append(c / b if b else None)
        if b > base_best:
            base_best, base_extra = b, be
        if c > cand_best:
            cand_best, cand_extra = c, ce
    ratios = sorted(r for r in pairs if r is not None)
    return {
        "baseline_pps": round(base_best),
        "candidate_pps": round(cand_best),
        "pairs": [None if r is None else round(r, 4) for r in pairs],
        "ratio_best": round(ratios[-1], 4) if ratios else None,
        "ratio_median": (round(ratios[len(ratios) // 2], 4)
                         if ratios else None),
        "spread": (round(ratios[-1] - ratios[0], 4)
                   if ratios else None),
        "baseline_extra": base_extra,
        "candidate_extra": cand_extra,
    }


def bench_device(world, jnp, datapath_step_jit, iters=10):
    from cilium_tpu.datapath.conntrack import ST_FREE, V_STATE

    from cilium_tpu.testing.fixtures import bench_traffic

    rng = np.random.default_rng(0)
    pool = [jnp.asarray(bench_traffic(world, BATCH, rng))
            for _ in range(4)]
    state = world.state
    now = 1_000
    t_warm = time.perf_counter()
    for b in pool:  # warmup: compile + seed steady-state CT
        out, state = datapath_step_jit(state, b, jnp.uint32(now))
    out.block_until_ready()
    warm_dt = time.perf_counter() - t_warm
    # 7 repetitions, MEDIAN as the headline + full envelope: a
    # single sample can misread a faster kernel as a regression
    n_reps = 7
    reps = []
    for _rep in range(n_reps):
        t0 = time.perf_counter()
        for i in range(iters):
            now += 1
            out, state = datapath_step_jit(state, pool[i % 4],
                                           jnp.uint32(now))
        out.block_until_ready()
        reps.append(time.perf_counter() - t0)
    dt = sorted(reps)[n_reps // 2]  # median of 7
    # occupancy counted on device: one scalar crosses to the host at
    # the end of the phase, not the table
    occupied = jnp.sum(state.ct.table[:, V_STATE] != ST_FREE)
    detail = {
        "ct_capacity": int(state.ct.capacity),
        "ct_occupied_dev": occupied,  # resolved at print time
        "batch_size": BATCH,
        "iters": iters,
        "warmup_ms": round(warm_dt * 1e3, 1),
        "step_ms": round(dt / iters * 1e3, 3),
        "rep_pps": sorted(round(BATCH * iters / r) for r in reps),
        "roofline": _roofline(dt / iters),
        "note": ("median of 7 reps; device rate depends on CT "
                 "capacity + occupancy (probe-gather locality)"),
    }
    return BATCH * iters / dt, state, now, detail


def _roofline(step_s: float) -> dict:
    """Modeled HBM traffic of the fused step (bytes/packet, upper
    bound on unique gather/scatter traffic) -> achieved GB/s.  r03
    verdict item: the CT probe was ~2176 B/pkt (two full [16, 17]
    windows) + a 16-step insert loop (~1728 B/pkt); the r04
    fingerprint diet (conntrack.py _probe_fp) cuts both."""
    from cilium_tpu.datapath.conntrack import (N_CAND, N_CAND_INS,
                                               N_PROBE, ROW_WORDS)

    b = {
        "hdr_read": 16 * 4,
        "ct_fp_windows": 2 * N_PROBE * 4,  # fwd+rev fingerprint gathers
        "ct_candidate_rows": 2 * N_CAND * ROW_WORDS * 4,
        "ct_insert_gathers": N_CAND_INS * (ROW_WORDS + 10) * 4,
        "ct_insert_scatter": ROW_WORDS * 4 + 4,  # one winner row + fp
        "ct_refresh_rmw": 32,
        "policy_gathers": 5 * 4,  # ep/proto/class/verdict/ct_proxy
        "lpm_gathers": 3 * 4,
        "out_write": 24,
        "metrics_scatter": 8,
    }
    per_pkt = sum(b.values())
    old_per_pkt = (16 * 4 + 2 * N_PROBE * ROW_WORDS * 4
                   + N_PROBE * (ROW_WORDS + 10) * 4 + ROW_WORDS * 4
                   + 32 + 20 + 12 + 24 + 8)
    return {
        "modeled_bytes_per_pkt": per_pkt,
        "breakdown": b,
        "r03_kernel_bytes_per_pkt": old_per_pkt,
        "traffic_ratio": round(old_per_pkt / per_pkt, 2),
        "modeled_bytes_per_step": per_pkt * BATCH,
        "achieved_gb_per_s": round(per_pkt * BATCH / step_s / 1e9, 1),
        "note": ("upper bound: counts every gather/scatter as unique "
                 "HBM traffic; v5e-class HBM is ~819 GB/s"),
    }


def bench_end_to_end(world, state, now0, jax, jnp, datapath_step_jit,
                     iters=16, sustain_iters=24):
    """Host frames -> device verdicts + event ring; one drain at end.

    The ingest path is the PACKED pipeline (core/packets.py PACKED_*):
    native C++ parses raw frames straight into reused 16 B/packet
    transfer buffers (page-registration-cache friendly), the device
    unpacks inside the fused serve step (datapath + ring compaction,
    one dispatch per batch).  Packed rows carry a quarter of the wide
    64 B/packet format's h2d bytes."""
    from cilium_tpu import native
    from cilium_tpu.core.ingest import frames_from_batch
    from cilium_tpu.monitor.ring import (EventRing, ring_drain,
                                         serve_step_packed_jit)
    from cilium_tpu.testing.fixtures import steady_flow_pool, steady_traffic

    rng = np.random.default_rng(1)
    # bounded flow pool: replaying it once establishes the steady state
    # (95% established / 5% new / 2% scan-drops thereafter)
    pool = steady_flow_pool(world, 2 * BATCH, rng)
    # distinct traffic every iteration — nothing replays
    n_bufs = max(iters, sustain_iters)
    frame_bufs = [frames_from_batch(steady_traffic(pool, BATCH, rng))
                  for _ in range(n_bufs)]
    wire_bytes = sum(len(b) for b in frame_bufs[:iters])

    # rotating packed transfer buffers: reuse keeps host pages warm and
    # registered with the transfer runtime
    out_pool = [np.empty((BATCH + 64, 4), dtype=np.uint32)
                for _ in range(4)]

    # parse-stage rate alone (for the bottleneck split); warm first so
    # the one-time g++ compile/dlopen of the native lib isn't timed
    use_native = native.available()

    def parse_packed(buf, i):
        if use_native:
            rows, _, _ = native.parse_frames_packed(buf, out_pool[i % 4])
        else:
            rows, _, _ = native.parse_frames_packed_py(buf,
                                                       out_pool[i % 4])
        return rows

    parse_packed(frame_bufs[0], 0)
    t0 = time.perf_counter()
    for i, buf in enumerate(frame_bufs[:8]):
        rows0 = parse_packed(buf, i)
    parse_dt = time.perf_counter() - t0
    parse_pps = 8 * BATCH / parse_dt

    # ring sized FROM the run length (~7.5k compacted events/batch:
    # 5% new-flow verdicts + 2% drops + sampled traces; bound by
    # BATCH/16) so the zero-loss claim holds for any iters/
    # sustain_iters a caller passes; both the timed and sustained runs
    # (plus one warmup append) land in the ring before the drain
    n_appends = iters + n_bufs + 1
    cap = _pow2_cap(n_appends * (BATCH // 16))
    ring = EventRing.create(cap)
    # warmup: establish the pool's flows in CT + compile the e2e shapes
    # — NO host fetch (see module doc)
    for chunk in pool.reshape(2, BATCH, -1):
        out, state = datapath_step_jit(state, jnp.asarray(chunk),
                                       jnp.uint32(now0))
    zero = jnp.uint32(0)
    state, ring = serve_step_packed_jit(
        state, ring, jax.device_put(rows0), jnp.uint32(now0), zero,
        zero, zero)
    ring.cursor.block_until_ready()

    def run(bufs, base):
        t0 = time.perf_counter()
        nonlocal state, ring
        for i, buf in enumerate(bufs):
            rows = parse_packed(buf, i)  # host: native C++, reused buf
            dev = jax.device_put(rows)  # h2d (async, 16 B/packet)
            state, ring = serve_step_packed_jit(
                state, ring, dev, jnp.uint32(base + i), jnp.uint32(i),
                zero, zero)
        ring.cursor.block_until_ready()
        return time.perf_counter() - t0

    dt = run(frame_bufs[:iters], now0 + 1)
    # sustained: a longer run past any transfer-buffer burst window
    dt_sustained = run(frame_bufs, now0 + 1 + iters)

    # a scalar fetch first waits out every queued dispatch, so the
    # drain below times the fetch + decode alone
    t0 = time.perf_counter()
    _ = np.asarray(state.metrics)
    sync_dt = time.perf_counter() - t0

    # the monitor's drain: fetch + decode the ring, outside the hot loop
    t0 = time.perf_counter()
    events, total, lost = ring_drain(ring)
    drain_dt = time.perf_counter() - t0

    return {
        "verdicts_per_sec": round(BATCH * iters / dt),
        "vs_target_10M": round(BATCH * iters / dt / BASELINE_PPS, 3),
        "sustained_pps": round(BATCH * len(frame_bufs) / dt_sustained),
        "sustained_batches": len(frame_bufs),
        "wire_gbps": round(wire_bytes * 8 / dt / 1e9, 2),
        "parse_stage_pps": round(parse_pps),
        "h2d_bytes_per_pkt": 16,
        "native_ingest": use_native,
        "batches": iters,
        "batch_size": BATCH,
        "events_streamed": int(total),
        "events_lost": int(lost),
        "first_fetch_sync_ms": round(sync_dt * 1e3, 1),
        "ring_drain_ms": round(drain_dt * 1e3, 1),
        "ring_drain_events_per_sec": round(int(total) / drain_dt)
        if drain_dt > 0 else None,
    }, state


def bench_end_to_end_wide(world, state, now0, jax, jnp, iters=12):
    """The WIDE path end-to-end: 64 B/packet header rows carrying the
    semantics the packed 16 B format declares out of scope — IPv6
    flows (TCAM LPM, 128-bit CT keys) and ICMP-error RELATED rows
    (embedded-tuple conntrack association).  r03 verdict: the
    v6/RELATED-correct path had NO perf claim; this block is it."""
    from cilium_tpu.core.ingest import parse_frames, wide_frames_from_batch
    from cilium_tpu.core.packets import COL_FAMILY, COL_FLAGS, FLAG_RELATED
    from cilium_tpu.monitor.ring import (EventRing, ring_drain,
                                         serve_step_jit)
    from cilium_tpu.testing.fixtures import wide_flow_pool, wide_traffic

    rng = np.random.default_rng(4)
    pool = wide_flow_pool(world, BATCH, rng)
    batches = [wide_traffic(pool, BATCH, rng) for _ in range(iters)]
    frame_bufs = [wide_frames_from_batch(b) for b in batches]
    wire_bytes = sum(len(b) for b in frame_bufs)
    frac_v6 = float(np.mean([np.mean(b[:, COL_FAMILY] == 6)
                             for b in batches]))
    frac_rel = float(np.mean([np.mean((b[:, COL_FLAGS] & FLAG_RELATED)
                                      != 0) for b in batches]))

    # rotating WIDE transfer buffers: same page-registration-cache
    # reuse as the packed path
    out_pool = [np.empty((BATCH + 64, 16), dtype=np.uint32)
                for _ in range(4)]

    # parse-stage rate alone (mixed v4/v6/ICMP-error frames)
    parse_frames(frame_bufs[0], out=out_pool[0])
    t0 = time.perf_counter()
    for i, buf in enumerate(frame_bufs[:4]):
        rows0 = parse_frames(buf, out=out_pool[i % 4])
    parse_pps = 4 * BATCH / (time.perf_counter() - t0)

    cap = _pow2_cap((iters + 2) * (BATCH // 8))
    # warmup: establish the dual-stack pool + compile the wide shapes
    # (throwaway ring: the pool replay is one solid batch of NEW-flow
    # verdict events that would swamp the measured ring)
    ring = EventRing.create(cap)
    state, ring = serve_step_jit(state, ring, jnp.asarray(pool),
                                 jnp.uint32(now0), jnp.uint32(0))
    state, ring = serve_step_jit(state, ring,
                                 jax.device_put(rows0),
                                 jnp.uint32(now0), jnp.uint32(0))
    ring.cursor.block_until_ready()
    ring = EventRing.create(cap)

    t0 = time.perf_counter()
    for i, buf in enumerate(frame_bufs):
        rows = parse_frames(buf, out=out_pool[i % 4])  # 64 B/pkt rows
        dev = jax.device_put(rows)
        state, ring = serve_step_jit(state, ring, dev,
                                     jnp.uint32(now0 + 1 + i),
                                     jnp.uint32(i))
    ring.cursor.block_until_ready()
    dt = time.perf_counter() - t0

    # a scalar fetch waits out the queued dispatches, so drain_ms
    # reports the fetch + decode (see bench_end_to_end)
    t0 = time.perf_counter()
    _ = np.asarray(state.metrics)
    sync_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    events, total, lost = ring_drain(ring)
    drain_dt = time.perf_counter() - t0
    return {
        "verdicts_per_sec": round(BATCH * iters / dt),
        "vs_target_10M": round(BATCH * iters / dt / BASELINE_PPS, 3),
        "phase_sync_ms": round(sync_dt * 1e3, 1),
        "h2d_bytes_per_pkt": 64,
        "frac_v6": round(frac_v6, 4),
        "frac_related": round(frac_rel, 4),
        "parse_stage_pps": round(parse_pps),
        "wire_gbps": round(wire_bytes * 8 / dt / 1e9, 2),
        "batches": iters,
        "events_streamed": int(total),
        "events_lost": int(lost),
        "ring_drain_ms": round(drain_dt * 1e3, 1),
    }, state


def bench_ring_steady_state(world, state, now0, jax, jnp, batches=24,
                            drain_every=4, ring_cap=None,
                            fresh_frac=32):
    """Sustained monitor-plane cadence with OVERLAPPED drains: the
    host fetches window N-1 (AsyncRingDrainer, monitor/ring.py) while
    the device steps window N — the production double-buffered drain
    loop.
    Loss accounting stays per window: every window starts on a fresh
    ring, so its fetched cursor is its append count and loss is
    ``max(0, appended - capacity)``.

    Traffic is generated ON DEVICE from a pre-staged flow pool (one
    gather + sport churn per batch, fused into the serve step): this
    phase measures the MONITOR plane — verdict + ring append +
    concurrent drain — and host->device ingest is the e2e phases' job;
    1/``fresh_frac`` of each batch gets rotating source ports, so CT
    sees a steady NEW-flow churn and the ring a production event mix.
    """
    from functools import partial

    from cilium_tpu.core.packets import COL_SPORT
    from cilium_tpu.datapath.verdict import datapath_step
    from cilium_tpu.monitor.ring import (AsyncRingDrainer, ring_append,
                                         serve_step_jit)
    from cilium_tpu.testing.fixtures import steady_flow_pool

    if ring_cap is None:
        # a drain window carries ~5% of its packets as events (3% NEW
        # verdicts at fresh_frac=32 + 2% scan drops + sampled traces);
        # the ring sizes at 6.25% of the window — headroom without
        # paying double the drain bandwidth for padding
        ring_cap = _pow2_cap(drain_every * (BATCH // 16))
    rng = np.random.default_rng(5)
    pool = jnp.asarray(steady_flow_pool(world, 2 * BATCH, rng))
    fresh_n = BATCH // fresh_frac

    @partial(jax.jit, donate_argnums=(0, 1),
             static_argnames=("trace_sample",))
    def serve_gen_step(st, ring, pool, i, now, trace_sample=1024):
        # batch i = a rotating window of the pool (established flows)
        # + a slice of never-seen source ports (NEW churn)
        idx = (i * jnp.uint32(40503) + jnp.arange(BATCH,
                                                  dtype=jnp.uint32)
               ) % jnp.uint32(pool.shape[0])
        hdr = pool[idx.astype(jnp.int32)]
        fresh_sport = (jnp.uint32(33000)
                       + (i * jnp.uint32(fresh_n)
                          + jnp.arange(fresh_n, dtype=jnp.uint32))
                       % jnp.uint32(30000))
        hdr = hdr.at[:fresh_n, COL_SPORT].set(fresh_sport)
        out, st = datapath_step(st, hdr, now)
        ring = ring_append(ring, out, i, trace_sample=trace_sample)
        return st, ring

    zero = jnp.uint32(0)
    drainer = AsyncRingDrainer(ring_cap)
    # establish the POOL's flows first (throwaway ring): the steady
    # state this phase measures is 95% established traffic — without
    # this, the first windows are solid NEW-verdict floods and the
    # "loss" is a warmup artifact, not a drain-cadence property
    ring = drainer.fresh()
    state, ring = serve_step_jit(state, ring, pool,
                                 jnp.uint32(now0), zero)
    state, ring = serve_gen_step(state, ring, pool, zero,
                                 jnp.uint32(now0))
    ring.cursor.block_until_ready()
    # wait out the warmup dispatches off the clock
    t0 = time.perf_counter()
    _ = np.asarray(state.metrics)
    sync_ms = round((time.perf_counter() - t0) * 1e3, 1)
    # raw d2h bandwidth with NO queued dispatches (we just synced):
    # the denominator that shows whether the drain transfer is
    # link-optimal or leaving bandwidth on the table
    t0 = time.perf_counter()
    _ = np.asarray(ring.buf)
    raw_dt = time.perf_counter() - t0
    raw_mbps = ring_cap * 4 * 2 / raw_dt / 1e6
    ring = drainer.fresh()

    collect_times = []
    stall_times = []
    t_run = time.perf_counter()
    for i in range(batches):
        state, ring = serve_gen_step(state, ring, pool,
                                     jnp.uint32(1 + i),
                                     jnp.uint32(now0 + 1 + i))
        if (i + 1) % drain_every == 0:
            # collect window N-1 (already streamed to host while this
            # window was stepping), then hand the filled ring to the
            # async fetch and keep serving on a fresh one
            t0 = time.perf_counter()
            drainer.collect()
            collect_times.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            ring = drainer.swap(ring)
            stall_times.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    drainer.collect()  # the last in-flight window
    collect_times.append(time.perf_counter() - t0)
    dt = time.perf_counter() - t_run
    drained_mb = drainer.windows * ring_cap * 8 / 1e6
    med_collect = sorted(collect_times)[len(collect_times) // 2]
    med_stall = sorted(stall_times)[len(stall_times) // 2]
    # the steady-state cost per window is the transfer (collect)
    # overlapped with the window's steps, plus the swap stall
    window_pkts = drain_every * BATCH
    ring_bytes = ring_cap * 8
    return {
        "sustained_pps_with_drains": round(BATCH * batches / dt),
        # what the drain costs per packet on the wire: 8 B/event x
        # ~5% event mix = ~0.4 B/pkt
        "drain_bytes_per_pkt": round(ring_bytes / window_pkts, 3),
        "raw_d2h_mbps": round(raw_mbps, 2),
        # collect (transfer + decode, overlapped with compute) vs a
        # BLOCKING raw fetch of the same bytes: > 1 means the
        # double-buffered path beats a synchronous fetch outright;
        # it is NOT a link-utilization fraction (the numerator
        # includes decode, the raw fetch includes per-transfer
        # latency)
        "collect_vs_raw_fetch_ratio": round(
            (ring_bytes / max(med_collect, 1e-6) / 1e6) / raw_mbps, 3),
        "projected_pps_at_1GBps_link": round(
            window_pkts / (ring_bytes / 1e9)),
        "batches": batches,
        "drain_every": drain_every,
        "ring_capacity": ring_cap,
        "windows_drained": int(drainer.windows),
        "events_drained": int(drainer.events),
        "window_lost": int(drainer.lost),
        "fresh_flow_frac": round(1 / fresh_frac, 3),
        "drained_mb": round(drained_mb, 1),
        "drain_transfer_ms_median": round(med_collect * 1e3, 1),
        "swap_stall_ms_median": round(med_stall * 1e3, 1),
        "pre_phase_sync_ms": sync_ms,
        "note": ("double-buffered drain: collect(window N-1) + async "
                 "swap while window N steps; per-window loss "
                 "accounting on a bounded ring (8 B/event packed "
                 "wire format); traffic generated on device from a "
                 "pre-staged pool — ingest is the e2e phases' "
                 "measurement.  sustained_pps includes the swap "
                 "stall at each window (swap_stall_ms_median)"),
    }, state


def bench_l7(batch: int = 4096, iters: int = 24, n_exact: int = 192,
             n_regex: int = 16) -> dict:
    """Eval config #4 (wrk2-style): HTTP request verdicts through the
    L7 proxy — featurize + device match tensors + access records, the
    full per-request path.  The reference config drives Envoy+proxylib
    at 10k RPS; `vs_wrk2_10k` scores against that rate.

    r03 verdict: 5 rules + 63% denies exercised mostly the cheap deny
    path.  Now: ``n_exact`` literal rules (device tensors) +
    ``n_regex`` regex rules (host fallback), and the request mix
    reports how often the per-request Python fallback actually runs —
    the real bound for non-admitted traffic."""
    from cilium_tpu.policy.api import L7Rules
    from cilium_tpu.proxy import L7Proxy

    rules = [{"method": ("GET", "POST", "PUT", "DELETE")[i % 4],
              "path": f"/api/v{i % 3}/resource{i}"}
             for i in range(n_exact)]
    rules += [{"method": "GET", "path": f"/static/{i}/.*"}
              for i in range(n_regex)]
    l7 = L7Rules.from_dict({"http": rules})
    proxy = L7Proxy()
    proxy.update([type("P", (), {"redirects": [(10000, "bench", l7)]})()])
    rng = np.random.default_rng(3)
    reqs = []
    for _ in range(batch):
        r = rng.random()
        if r < 0.70:  # admitted by a device-tensor literal rule
            i = int(rng.integers(0, n_exact))
            reqs.append({"method": ("GET", "POST", "PUT", "DELETE")[i % 4],
                         "path": f"/api/v{i % 3}/resource{i}",
                         "host": "db.svc"})
        elif r < 0.85:  # admitted only by a regex rule (host fallback)
            i = int(rng.integers(0, n_regex))
            reqs.append({"method": "GET", "path": f"/static/{i}/app.js",
                         "host": "db.svc"})
        else:  # denied (still pays the fallback scan before the 403)
            reqs.append({"method": "DELETE", "path": "/etc/passwd",
                         "host": "db.svc"})
    proxy.handle_http(10000, reqs)  # warm/compile
    proxy.requests_total = proxy.requests_denied = 0
    proxy.host_fallback_checked = proxy.host_fallback_allowed = 0
    t0 = time.perf_counter()
    for _ in range(iters):
        proxy.handle_http(10000, reqs)
    dt = time.perf_counter() - t0
    rps = batch * iters / dt
    return {
        "requests_per_sec": round(rps),
        "vs_wrk2_10k": round(rps / 10_000.0, 1),
        "n_rules": n_exact + n_regex,
        "n_regex_rules": n_regex,
        "denied_frac": round(proxy.requests_denied
                             / proxy.requests_total, 3),
        "host_fallback_frac": round(proxy.host_fallback_checked
                                    / proxy.requests_total, 3),
        "host_fallback_hit_frac": round(proxy.host_fallback_allowed
                                        / max(proxy.host_fallback_checked,
                                              1), 3),
        "batch": batch,
    }


def bench_l7_redirect(batch=1024, iters=6, reps=3) -> dict:
    """The ``l7_redirect`` rung (ISSUE 16): paired-leg redirect
    overhead through LIVE serving.  Baseline leg serves SYN traffic
    against a plain L4 allow on port 80; the candidate leg serves the
    IDENTICAL traffic shape against the same policy WITH an HTTP rule
    — every row then verdicts REDIRECT, emits its verdict event, and
    detours through the L7 worker pool (parse + per-rule verdict),
    and the candidate's wall clock includes waiting for the pool to
    drain what the leg submitted.  The paired ratio is the honest
    cost of making REDIRECT a real serving outcome; both legs ride
    :func:`paired_legs` so machine weather cancels per pair."""
    import ipaddress

    from cilium_tpu.agent import Daemon, DaemonConfig
    from cilium_tpu.core.packets import (COL_DPORT, COL_DST_IP3,
                                         COL_EP, COL_FAMILY, COL_FLAGS,
                                         COL_LEN, COL_PROTO, COL_SPORT,
                                         COL_SRC_IP3, N_COLS, TCP_SYN)

    def build(with_l7):
        d = Daemon(DaemonConfig(backend="tpu", ct_capacity=1 << 16,
                                flow_ring_capacity=1 << 13,
                                serving_bucket_ladder=(batch,),
                                serving_queue_depth=1 << 14))
        d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        tp = {"ports": [{"port": "80", "protocol": "TCP"}]}
        if with_l7:
            tp["rules"] = {"http": [{"method": "GET"}]}
        d.policy_import([{
            "endpointSelector": {"matchLabels": {"app": "db"}},
            "ingress": [{
                "fromEndpoints": [{"matchLabels": {"app": "web"}}],
                "toPorts": [tp]}],
        }])
        d.start_serving(ring_capacity=1 << 13, trace_sample=0,
                        drain_every=1)
        return d, db.id

    src = int(ipaddress.IPv4Address("10.0.1.1"))
    dst = int(ipaddress.IPv4Address("10.0.2.1"))
    counters = {"base": 0, "redir": 0}

    def rows_for(n, key, ep):
        rows = np.zeros((n, N_COLS), dtype=np.uint32)
        rows[:, COL_SRC_IP3] = src
        rows[:, COL_DST_IP3] = dst
        start = counters[key]
        counters[key] += n
        # fresh sport per row: every packet is a NEW flow, so each
        # redirect verdict emits its event and detours the pool —
        # the exact path whose overhead this rung defends
        rows[:, COL_SPORT] = 1024 + (start + np.arange(n)) % 60000
        rows[:, COL_DPORT] = 80
        rows[:, COL_PROTO] = 6
        rows[:, COL_FLAGS] = TCP_SYN
        rows[:, COL_LEN] = 64
        rows[:, COL_FAMILY] = 4
        rows[:, COL_EP] = ep
        return rows

    d_base, ep_b = build(False)
    d_red, ep_r = build(True)
    try:
        # warm both executables (same bucket shape, but the first
        # dispatch of each daemon pays compile)
        d_base.serve_batch(rows_for(batch, "base", ep_b))
        d_red.serve_batch(rows_for(batch, "redir", ep_r))

        def leg(d, ep, key):
            def run():
                t0 = time.perf_counter()
                for _ in range(iters):
                    d.serve_batch(rows_for(batch, key, ep))
                # the candidate pays its detour in full: wall time
                # includes the pool draining this leg's tasks (the
                # baseline's plane never sees a row — no-op)
                plane = d._l7plane
                if plane is not None:
                    deadline = time.monotonic() + 30.0
                    while plane.pool.pending \
                            and time.monotonic() < deadline:
                        time.sleep(0.0005)
                return batch * iters / (time.perf_counter() - t0)
            return run

        out = paired_legs(leg(d_base, ep_b, "base"),
                          leg(d_red, ep_r, "redir"), reps=reps)
        st = d_red.stop_serving()
        d_base.stop_serving()
        out["l7"] = st.get("l7")
        out["batch"] = batch
        out["packets_per_leg"] = batch * iters
        return out
    finally:
        d_base.shutdown()
        d_red.shutdown()


def _run_l7_phase() -> None:
    """--l7: the L7 proxy-plane phase standalone (one JSON line).
    Also writes BENCH_l7.json next to this file — schema-checked by
    the CTA012 machinery (importable ``check_bench`` in
    ``cilium_tpu.analysis.proxy_lint``)."""
    import os

    from cilium_tpu.proxy import registry as l7registry

    redirect = bench_l7_redirect()
    out = {
        "schema": "bench-l7-v1",
        # paired-leg redirect overhead: candidate (redirect + pool
        # drain) over baseline (plain L4 allow), same traffic shape
        "redirect_overhead": redirect,
        # per-plugin parse+verdict percentiles recorded by the
        # candidate leg's workers through the registry seam
        "parse_latency_by_plugin": l7registry.latency_snapshot(),
        # the offline proxy microbench rides along (eval config #4)
        "offline_http": bench_l7(),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_l7.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out))


def bench_socket_lb(n_services=512, iters=9) -> dict:
    """Socket-LB delta (SURVEY §2a bpf_sock row): per-packet LB cost
    on ESTABLISHED traffic, flow-cached probe (service/socklb.py) vs
    the per-packet [N, S] frontend compare + Maglev (lb_stage)."""
    import jax
    import jax.numpy as jnp

    from cilium_tpu.core.packets import (COL_DPORT, COL_DST_IP3,
                                         COL_FAMILY, COL_PROTO,
                                         COL_SPORT, COL_SRC_IP3,
                                         N_COLS)
    from cilium_tpu.service import ServiceManager
    from cilium_tpu.service.socklb import SockLBTable, socklb_stage_jit

    m = ServiceManager()
    for i in range(n_services):
        vip = f"172.16.{i // 256}.{i % 256}"
        m.upsert(f"svc{i}", f"{vip}:80",
                 [f"10.1.{i // 256}.{i % 256}:8080",
                  f"10.2.{i // 256}.{i % 256}:8080"])
    t = m.tensors()
    rng = np.random.default_rng(11)
    hdr = np.zeros((BATCH, N_COLS), dtype=np.uint32)
    hdr[:, COL_FAMILY] = 4
    hdr[:, COL_SRC_IP3] = rng.integers(1, 2**31, BATCH)
    svc_rows = rng.random(BATCH) < 0.5
    vip_ips = np.asarray(t.svc_ip)
    hdr[:, COL_DST_IP3] = np.where(
        svc_rows, rng.choice(vip_ips, BATCH),
        rng.integers(1, 2**31, BATCH))
    hdr[:, COL_DPORT] = np.where(svc_rows, 80,
                                 rng.integers(1, 65535, BATCH))
    hdr[:, COL_SPORT] = rng.integers(1024, 65535, BATCH)
    hdr[:, COL_PROTO] = 6
    jhdr = jnp.asarray(hdr)
    now = jnp.uint32(100)

    # LOOP stage iterations inside ONE dispatch (lax.fori_loop): a
    # per-dispatch timing of a sub-ms stage would measure dispatch
    # overhead.  One dispatch of LOOP iterations is the compute-only
    # comparison.
    LOOP = 32
    from functools import partial

    from cilium_tpu.service import lb_stage
    from cilium_tpu.service.socklb import CONNECT_CAP, socklb_stage

    # `t` rides as an ARGUMENT: closing over it would inline the
    # Maglev table as an HLO constant, one program per table
    @jax.jit
    def brute_loop(t, hdr0):
        # thread hdr through so iterations cannot be hoisted (the
        # stage is pure); post-rewrite rows still pay the same [N, S]
        # compare, which is the cost being measured
        def body(_i, h):
            h2, _hits, _nobe = lb_stage(t, h)
            return h2
        return jax.lax.fori_loop(0, LOOP, body, hdr0)

    @partial(jax.jit, donate_argnums=0)
    def cached_loop(tbl, t, hdr0):
        # fold the rewritten header + hit mask into a carried scalar:
        # without a live use, XLA dead-code-eliminates the DNAT
        # rewrite selects/scatters from the cached path while the
        # brute loop (which threads h) pays them — an unfair compare
        def body(_i, carry):
            tb, acc = carry
            h2, hits, _nobe, tb2 = socklb_stage(tb, t, hdr0, now)
            return tb2, (acc + h2[:, COL_DST_IP3].sum()
                         + h2[:, COL_DPORT].sum()
                         + hits.sum().astype(jnp.uint32))
        return jax.lax.fori_loop(0, LOOP, body,
                                 (tbl, jnp.uint32(0)))

    def median_time(fn, reps=iters):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append((time.perf_counter() - t0) / LOOP)
        return sorted(ts)[len(ts) // 2]

    jax.block_until_ready(brute_loop(t, jhdr))  # compile
    dt_compare = median_time(lambda: brute_loop(t, jhdr))

    tbl = SockLBTable.create(1 << 20)
    box = [tbl]
    _, _, _, box[0] = socklb_stage_jit(box[0], t, jhdr, now)  # compile
    # warm the flow cache in connect-buffer-sized slices: a single
    # full-batch step has BATCH >> CONNECT_CAP misses and takes the
    # resolve-only fallback (nothing caches) — production flows
    # arrive gradually, which the sliced warmup models
    for i in range(0, BATCH, CONNECT_CAP):
        _h, hit, _nb, box[0] = socklb_stage_jit(
            box[0], t, jhdr[i:i + CONNECT_CAP], now)
    _h, hit, _nb, box[0] = socklb_stage_jit(box[0], t, jhdr, now)
    jax.block_until_ready(hit)  # cache now holds every flow

    box[0], _acc = cached_loop(box[0], t, jhdr)  # compile
    jax.block_until_ready(box[0].fp)

    def cached_step():
        box[0], acc = cached_loop(box[0], t, jhdr)
        return acc

    dt_cached = median_time(cached_step)
    return {
        "n_services": n_services,
        "batch": BATCH,
        "looped_iterations": LOOP,
        "per_packet_compare_pps": round(BATCH / dt_compare),
        "flow_cached_pps": round(BATCH / dt_cached),
        "note": ("established-path LB: connect-time resolution cached "
                 "per flow (bpf_sock analogue) vs per-packet [N,S] "
                 "frontend compare + Maglev.  The cached path is O(1) "
                 "in the service count (probe window + candidate "
                 "gathers); the compare is O(S) per packet — run with "
                 "several n_services to see the flat-vs-linear split. "
                 "The semantic contract is affinity either way: "
                 "cached flows keep their backend across backend-set "
                 "changes."),
    }


def bench_socket_lb_scaling(counts=(512, 4096)) -> dict:
    """Socket-LB at several service counts: the flow cache's flat
    cost vs the per-packet compare's O(S) growth (the design claim a
    single-point speedup number cannot carry)."""
    points = [bench_socket_lb(n_services=s, iters=5) for s in counts]
    return {
        "points": [{k: p[k] for k in ("n_services",
                                      "per_packet_compare_pps",
                                      "flow_cached_pps")}
                   for p in points],
        "note": points[-1]["note"],
    }


def bench_encryption(mb: int = 8, iters: int = 9) -> dict:
    """Transparent-encryption throughput (host-side, no TPU): seal +
    open of batch-sized buffers through the native ChaCha20-Poly1305
    (native/crypto.cpp).  The unit of encryption is the BATCH (one
    AEAD per batch, DIVERGENCES #24), so GiB/s here bounds the
    node-to-node encrypted plane; at 16 B/packet packed frames,
    1 GiB/s ~ 67M packets/s.  Without the native library (no g++)
    the pure-Python fallback is orders of magnitude slower, so the
    buffer shrinks to keep the phase bounded."""
    from cilium_tpu.encryption import EncryptedChannel, NodeKeypair
    from cilium_tpu.native import crypto

    if not crypto.available():
        mb, iters = 1, 3  # python-fallback path: keep it bounded

    a, b = NodeKeypair(), NodeKeypair()
    ca = EncryptedChannel(a, b.public)
    cb = EncryptedChannel(b, a.public)
    buf = bytes(np.random.default_rng(5).bytes(mb << 20))
    ts_seal, ts_open = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        frame = ca.seal(buf)
        ts_seal.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        out = cb.open(frame)
        ts_open.append(time.perf_counter() - t0)
        assert out == buf
    seal_gbps = (mb / (1 << 10)) / sorted(ts_seal)[len(ts_seal) // 2]
    open_gbps = (mb / (1 << 10)) / sorted(ts_open)[len(ts_open) // 2]
    return {
        "native": crypto.available(),
        "buffer_mb": mb,
        "seal_gb_per_s": round(seal_gbps, 3),
        "open_gb_per_s": round(open_gbps, 3),
        "packed_pps_bound": round(min(seal_gbps, open_gbps)
                                  * (1 << 30) / 16),
    }


def _run_socklb_phase() -> None:
    """--socklb: the socket-LB scaling phase standalone (one JSON
    line)."""
    print(json.dumps(bench_socket_lb_scaling()))


def bench_serving(offline_batches=16, paced_seconds=2.0) -> dict:
    """Serving front-end phase: sustained verdicts/sec under Poisson
    arrivals through the admission queue + adaptive batcher
    (cilium_tpu/serving) vs the OFFLINE serve_batch ceiling (perfect
    pre-assembled full buckets) — the first entry in the BENCH
    trajectory.  Deliberately bounded and CPU-runnable
    (JAX_PLATFORMS=cpu): the number it defends is the front end's
    OVERHEAD RATIO (serving_vs_offline), which is platform-relative;
    absolute pps is whatever the backend does.

    The ingress side runs the PACKED 16 B/packet h2d path (PR 2
    tentpole): BENCH_serving.json records the packed-vs-wide batch
    split and measured h2d bytes/packet alongside the ratio.  Both
    sides are measured 3x INTERLEAVED and compared best-of-3 —
    single-shot CPU wall timings swing +-15%, and the ratio must
    measure the front end, not scheduling weather.

    Since PR 5 the overload legs run with EVENT DECODE ENABLED: the
    headline ``sustained_pps`` at the production-default
    ``trace_sample=1024`` (PR 4 measured with events disabled
    outright), plus a dedicated DECODE-UNDER-LOAD leg
    (``sustained_pps_decode``, ``trace_sample=1``: every admitted
    packet appends a ring event, every event is
    fetched/decoded/joined/emitted on the async event plane's
    worker).  ``d2h_bytes_per_event`` + ``event_join_lag_us`` come
    from that leg's best rep, and ``d2h_scaling`` contrasts the
    occupancy-bounded gather against the legacy full-capacity copy
    at LOW occupancy, where the diet matters."""
    import ipaddress

    import jax

    from cilium_tpu.agent import Daemon, DaemonConfig
    from cilium_tpu.core.packets import (COL_DPORT, COL_DST_IP3,
                                         COL_EP, COL_FAMILY, COL_FLAGS,
                                         COL_LEN, COL_PROTO, COL_SPORT,
                                         COL_SRC_IP3, N_COLS, TCP_ACK)

    LADDER = (512, 2048, 8192)
    # superbatch_k=8 (ISSUE 11): the overload legs run the K-batch
    # fused dispatch as the production default — the drain loop takes
    # what is queued, so batches-per-dispatch floats with queue depth
    # and the dedicated bench_superbatch pair pins it at K
    d = Daemon(DaemonConfig(backend="tpu", ct_capacity=1 << 16,
                            flow_ring_capacity=1 << 14,
                            serving_queue_depth=1 << 15,
                            serving_bucket_ladder=LADDER,
                            serving_max_wait_us=2000.0,
                            serving_superbatch_k=8))
    d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
    db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
    d.policy_import([{
        "endpointSelector": {"matchLabels": {"app": "db"}},
        "ingress": [{"fromEndpoints": [{"matchLabels": {"app": "web"}}],
                     "toPorts": [{"ports": [{"port": "5432",
                                             "protocol": "TCP"}]}]}],
    }])
    rng = np.random.default_rng(7)
    B = LADDER[-1]
    src = int(ipaddress.IPv4Address("10.0.1.1"))
    dst = int(ipaddress.IPv4Address("10.0.2.1"))
    # bounded flow universe: after warmup the mix is established
    # traffic (trace_sample=0 keeps it off the event ring)
    sports = (1024 + rng.permutation(50000)[:4096]).astype(np.uint32)

    def batch(n):
        rows = np.zeros((n, N_COLS), dtype=np.uint32)
        rows[:, COL_SRC_IP3] = src
        rows[:, COL_DST_IP3] = dst
        rows[:, COL_SPORT] = rng.choice(sports, n)
        rows[:, COL_DPORT] = 5432
        rows[:, COL_PROTO] = 6
        rows[:, COL_FLAGS] = TCP_ACK
        rows[:, COL_LEN] = 512
        rows[:, COL_FAMILY] = 4
        rows[:, COL_EP] = db.id
        return rows

    from cilium_tpu.core.packets import pack_eligibility, pack_rows

    # ---- warm every compiled shape once (shared by all reps):
    # wide + packed ladders at trace_sample=0 (the offline ceiling)
    # AND trace_sample=1 (the decode-under-load ingress side) —
    # trace_sample is a static arg, so each value is its own
    # executable and an unwarmed one would bill XLA compile time to
    # the first timed rep
    for ts in (0, 1024, 1):
        # the ts>0 sessions mirror the overload legs' 2^16 ring —
        # the gather executables key on (rung, shards, capacity)
        d.start_serving(ring_capacity=(1 << 16) if ts else (1 << 15),
                        trace_sample=ts, packed=True)
        for b in LADDER:
            d.serve_batch(batch(b), valid=np.ones(b, dtype=bool))
            w = batch(b)
            ok, ep, dirn = pack_eligibility(w)
            assert ok, "bench traffic must be packed-eligible"
            d.serve_batch(pack_rows(w), valid=np.ones(b, dtype=bool),
                          packed_meta=(ep, dirn))
        if ts:
            # fill one whole drain window at full occupancy so the
            # top ring-gather rung (the one the timed overload legs
            # hit) compiles here, not inside a timed rep
            w = batch(B)
            ok, ep, dirn = pack_eligibility(w)
            pw = pack_rows(w)
            for _ in range(4):
                d.serve_batch(pw.copy(),
                              valid=np.ones(B, dtype=bool),
                              packed_meta=(ep, dirn))
            # superbatch executables (the K-batch scan, ISSUE 11):
            # the overload legs run superbatch_k=8, and WHICH K rungs
            # a leg hits depends on live queue depth — warm every
            # rung here so none pays its XLA compile in a timed rep
            from cilium_tpu.serving.batcher import SuperBatch

            for K in (2, 4, 8):
                sb = SuperBatch(
                    hdr=np.stack([pw] * K),
                    valid=np.ones((K, B), dtype=bool),
                    bucket=B, arrivals=[], packed=True,
                    eps=np.full(K, ep, np.uint32),
                    dirns=np.full(K, dirn, np.uint32))
                d.serve_superbatch(sb)
        d.stop_serving()

    valid = np.ones(B, dtype=bool)
    chunks = [batch(max(int(rng.poisson(4096.0)), 1))
              for _ in range(32)]
    target = offline_batches * B

    def rep_offline() -> float:
        """Offline ceiling: perfect pre-assembled full WIDE buckets."""
        d.start_serving(trace_sample=0)
        t0 = time.perf_counter()
        for _ in range(offline_batches):
            d.serve_batch(batch(B), valid=valid)
        dt = time.perf_counter() - t0
        d.stop_serving()
        return offline_batches * B / dt

    def rep_overload(span_sample=0, trace_sample=1024, agg=True):
        """Overload: Poisson chunks offered until the target volume
        is ADMITTED, backing off only when the queue is full —
        offered load exceeds capacity, so sheds are expected and
        counted.  The ingress runtime ships eligible buckets packed
        (16 B/packet h2d) with event decode ENABLED
        (``trace_sample=1024`` is the production default;
        ``trace_sample=1`` is the decode-under-load leg — every
        admitted packet appends a ring event; either way the async
        event plane fetches the occupancy-bounded gather and
        decodes/joins/emits on its worker, off the dispatch path).
        ``span_sample`` arms the obs span tracer (the trace-overhead
        leg); 0 keeps the production default (tracer None, one
        is-None branch on the hot path).  ``agg`` toggles the FLOW
        ANALYTICS plane (windowed per-identity aggregation + top-K
        sketches on the event-join worker, PR 6): True is the
        production default and the headline legs run with it; the
        dedicated agg-vs-no-agg pair at ``trace_sample=1`` isolates
        its dispatch-path cost (agg_overhead_ratio — the aggregation
        itself runs off-path, so the ratio defends ~1.0)."""
        # 2^16 ring: a full drain window (drain_every=4 x 8192-row
        # buckets at trace_sample=1) is half the capacity, so the
        # bench measures the gather diet, never lap loss
        d.analytics.enabled = bool(agg)
        d.start_serving(ring_capacity=1 << 16,
                        trace_sample=trace_sample,
                        ingress=True, packed=True,
                        span_sample=span_sample or None)
        admitted = offered = i = 0
        t0 = time.perf_counter()
        try:
            while admitted < target:
                c = chunks[i % len(chunks)]
                i += 1
                got = d.submit(c)
                offered += len(c)
                admitted += got
                if got < len(c):
                    time.sleep(0.0005)  # queue full: backpressure
            stats = d.stop_serving()  # drains everything admitted
        finally:
            d.analytics.enabled = True  # the production default
        dt = time.perf_counter() - t0
        fe = stats["front-end"]
        return fe["verdicts"] / dt, fe, offered, stats["event-plane"]

    # ---- best-of-3 INTERLEAVED: rep k runs offline then overload
    # back to back, so both sides sample the same machine weather.
    # fe/offered come from the SAME rep as the reported max pps —
    # mixed-provenance telemetry would mislead anyone correlating
    # the ratio with the shed/queue-wait numbers
    offline_pps = sustained_pps = decode_pps = traced_pps = 0.0
    noagg_pps = aggdec_pps = 0.0
    agg_pairs = []  # per-rep (noagg, agg) adjacent-leg ratios
    fe = offered = fe_traced = ev = dec_ev = agg_stats = None
    # untimed ingress warm leg: the very first overload leg of a run
    # pays residual warmth (first partial-bucket shapes, thread/alloc
    # steady state) that a timed pair member must not absorb
    rep_overload(agg=False)
    for k in range(3):
        offline_pps = max(offline_pps, rep_offline())
        # the PR 6 agg pair: the HEADLINE leg runs at production
        # defaults (trace_sample=1024, flow analytics ENABLED —
        # windowed counters, both top-K sketches, and the spike
        # detector see every decoded event AND every shed drop
        # batch, on the event-join worker), its baseline is the
        # identical overload with the analytics plane OFF.  The
        # ratio between the two is the dispatch-path cost of
        # aggregation (defended ~1.0: the drain thread only pays the
        # O(1) monitor-consumer reference park; worker-side CPU is
        # duty-cycle capped by flow_agg_max_duty).  The pair
        # ALTERNATES order per rep — measured on this box, whichever
        # leg runs second in a pair reads a few percent faster
        # (thermal/cache settling), so a fixed order masquerades as
        # aggregation cost; alternation cancels it in the median
        def agg_leg():
            nonlocal sustained_pps, fe, offered, ev, agg_stats
            s0 = d.analytics.stats()
            pps, rep_fe, rep_offered, rep_ev = rep_overload()
            if pps > sustained_pps:
                sustained_pps, fe, offered, ev = (pps, rep_fe,
                                                  rep_offered,
                                                  rep_ev)
                # THIS leg's analytics activity (counters are
                # daemon-lifetime cumulative — a raw snapshot would
                # conflate every earlier agg-enabled leg)
                s1 = d.analytics.stats()
                agg_stats = {k: (s1[k] - s0[k]
                                 if type(s1[k]) is int
                                 and type(s0.get(k)) is int
                                 else s1[k])
                             for k in s1}
            return pps

        def noagg_leg():
            nonlocal noagg_pps
            pps_na, _, _, _ = rep_overload(agg=False)
            noagg_pps = max(noagg_pps, pps_na)
            return pps_na

        if k % 2 == 0:
            pps, pps_na = agg_leg(), noagg_leg()
        else:
            pps_na, pps = noagg_leg(), agg_leg()
        agg_pairs.append(pps_na / pps)
        # the PR 5 decode-under-load leg: every packet an event —
        # the event plane's worker decodes ~all of the admitted
        # volume while the drain thread keeps dispatching (agg off:
        # PR 5 semantics)
        pps_dec, _, _, rep_dec_ev = rep_overload(trace_sample=1,
                                                 agg=False)
        if pps_dec > decode_pps:
            decode_pps, dec_ev = pps_dec, rep_dec_ev
        # the stress contrast: per-packet events AND aggregation —
        # the worst case the duty governor exists for, reported as
        # a secondary honesty number (not the acceptance ratio)
        pps_ad, _, _, _ = rep_overload(trace_sample=1)
        aggdec_pps = max(aggdec_pps, pps_ad)
        # the obs satellite's guard leg: the SAME overload rep with
        # 1-in-64 span tracing armed, interleaved so both legs see
        # the same machine weather.  trace_overhead_ratio ~ 1.0
        # documents the sampled cost; the DISABLED cost is the
        # default path above (tracer None) and is what the pre/post
        # bench comparison defends
        pps_tr, rep_fe_tr, _, _ = rep_overload(span_sample=64)
        if pps_tr > traced_pps:
            traced_pps, fe_traced = pps_tr, rep_fe_tr

    # ---- paced: Poisson arrivals at ~50% of the offline rate — the
    # latency-percentile run (at overload, queue wait just measures
    # queue depth).  Analytics OFF: this leg's percentiles are the
    # PR 5 decode-latency trajectory (trace_sample=1 is already a
    # stress shape, not the production default) — the aggregation
    # cost has its own dedicated pair above
    d.analytics.enabled = False
    d.start_serving(ring_capacity=1 << 16, trace_sample=1,
                    ingress=True, packed=True)
    rate = max(offline_pps * 0.5, 1.0)
    t_end = time.perf_counter() + paced_seconds
    i = 0
    while time.perf_counter() < t_end:
        c = chunks[i % len(chunks)]
        i += 1
        d.submit(c)
        time.sleep(float(rng.exponential(len(c) / rate)))
    paced_out = d.stop_serving()
    d.analytics.enabled = True
    paced = paced_out["front-end"]
    paced_ev = paced_out["event-plane"]

    # ---- d2h scaling contrast: the same LOW-occupancy window (one
    # 512-row bucket per drain tick on the 2^16 ring) fetched via the
    # occupancy-bounded gather vs the legacy full-capacity copy —
    # the bytes-per-event gap IS the tentpole's d2h diet
    scaling = {"ring_capacity": 1 << 16}
    for label, g in (("gather", True), ("fullcopy", False)):
        d.start_serving(ring_capacity=1 << 16, drain_every=1,
                        trace_sample=1, packed=True, event_gather=g)
        b = LADDER[0]
        for _ in range(4):
            d.serve_batch(batch(b), valid=np.ones(b, dtype=bool))
        sc = d.stop_serving()["event-plane"]
        scaling[f"{label}_bytes_per_event"] = sc["d2h-bytes-per-event"]
    d.shutdown()

    return {
        "offline_pps": round(offline_pps),
        "sustained_pps": round(sustained_pps),
        "serving_vs_offline": round(sustained_pps / offline_pps, 3),
        "offered": offered,
        "admitted": fe["admitted"],
        "shed": fe["shed"],
        "shed_drop_events": fe["shed-events"],
        "batch_shapes": fe["batch-shapes"],
        "pad_efficiency": fe["pad-efficiency"],
        # the h2d link scoreboard (PR 2 tentpole): bytes/packet on
        # the wire and how many batches shipped packed vs wide
        "h2d_bytes_per_packet": fe["h2d"]["bytes-per-packet"],
        "packed_batches": fe["h2d"]["packed-batches"],
        "wide_batches": fe["h2d"]["wide-batches"],
        # the superbatch scoreboard of the HEADLINE leg (ISSUE 11):
        # overload legs run superbatch_k=8, so batches-per-dispatch
        # floats with live queue depth; the dedicated "superbatch"
        # section (bench_superbatch) is the pinned-K acceptance pair
        "superbatch_k": 8,
        "dispatches": fe["dispatch"]["dispatches"],
        "batches_per_dispatch":
            fe["dispatch"]["batches-per-dispatch"],
        # the d2h link scoreboard (PR 5 tentpole): event decode is ON
        # in every overload/paced leg (sustained_pps at the
        # production-default trace_sample=1024; sustained_pps_decode
        # with EVERY packet an event), the fetch is the
        # occupancy-bounded gather, and decode/join/emit run on the
        # event-join worker off the dispatch path
        "event_decode": "enabled (trace_sample=1024 headline; "
                        "decode leg trace_sample=1)",
        # decode ratio keeps its PR 5 meaning (events-per-packet vs
        # events-sampled, both with analytics OFF): the denominator
        # is the no-agg production-default leg, not the analytics-
        # enabled headline
        "sustained_pps_decode": round(decode_pps),
        "decode_overhead_ratio": round(decode_pps / noagg_pps, 4)
        if noagg_pps else None,
        # the flow analytics scoreboard (PR 6 tentpole): the
        # HEADLINE runs at production defaults with aggregation ON;
        # sustained_pps_noagg is the identical overload with it OFF,
        # so agg_overhead_ratio = noagg/agg defends <= 1.05 (the
        # dispatch path only pays the O(1) reference park; worker
        # CPU is duty-capped by flow_agg_max_duty).  The *_aggdecode
        # pair is the per-packet-event stress contrast (every packet
        # decoded AND aggregated) — the governor's worst case,
        # reported for honesty, not the acceptance gate
        "flow_agg": "headline at production defaults WITH "
                    "aggregation; sustained_pps_noagg = same leg "
                    "with analytics off",
        "sustained_pps_noagg": round(noagg_pps),
        "agg_overhead_ratio": round(sorted(agg_pairs)[1], 4)
        if len(agg_pairs) == 3 else None,
        "agg_overhead_ratio_pairs": [round(r, 4) for r in agg_pairs],
        "sustained_pps_aggdecode": round(aggdec_pps),
        "aggdecode_vs_decode_ratio": round(decode_pps / aggdec_pps, 4)
        if aggdec_pps else None,
        "flow_agg_stats": agg_stats,
        "d2h_bytes_per_event": dec_ev["d2h-bytes-per-event"],
        "event_join_lag_us": dec_ev["join-lag-us"],
        "event_windows": {"joined": dec_ev["windows-joined"],
                          "dropped": dec_ev["windows-dropped"],
                          "ring-lost": dec_ev["ring-lost"],
                          "events-joined": dec_ev["events-joined"]},
        "paced_d2h_bytes_per_event": paced_ev["d2h-bytes-per-event"],
        "d2h_scaling": scaling,
        "bucket_ladder": list(LADDER),
        "max_wait_us": 2000.0,
        "overload_queue_wait_us": fe["queue-wait-us"],
        "paced_latency_us": paced["latency-us"],
        "paced_queue_wait_us": paced["queue-wait-us"],
        "paced_pad_efficiency": paced["pad-efficiency"],
        # obs plane: sustained pps with 1-in-64 span tracing armed
        # (best-of-3, interleaved with the untraced leg) and the
        # resulting overhead ratio; span counts prove the traces
        # actually flowed
        "sustained_pps_traced": round(traced_pps),
        "trace_overhead_ratio": round(traced_pps / sustained_pps, 4)
        if sustained_pps else None,
        "trace_spans_completed": (fe_traced or {}).get(
            "trace", {}).get("completed"),
        "platform": jax.default_backend(),
        "note": ("serving front end (admission queue + power-of-two "
                 "bucket batcher + drain loop, PACKED 16 B/packet "
                 "h2d, EVENT DECODE enabled on the async event "
                 "plane: headline at the production-default "
                 "trace_sample=1024, decode-under-load leg at "
                 "trace_sample=1) vs offline pre-assembled wide "
                 "buckets at trace_sample=0; serving_vs_offline is "
                 "the front end's overhead ratio, best-of-3 "
                 "interleaved; sheds are counted monitor DROP "
                 "events (REASON_INGRESS_OVERFLOW); d2h_scaling "
                 "contrasts the occupancy-bounded gather with the "
                 "legacy full-capacity copy at low ring occupancy; "
                 "agg_overhead_ratio is the median of order-"
                 "alternated adjacent-leg pairs (production-default "
                 "overload, analytics on vs off; aggregation runs "
                 "on the event-join worker, duty-capped, so the "
                 "ratio defends the dispatch path staying "
                 "untouched).  CAVEAT: every single-run ratio here "
                 "(trace_overhead_ratio, the agg pairs, "
                 "serving_vs_offline) divides two wall-clock "
                 "measurements on a shared CPU box whose weather "
                 "swings far beyond the documented +-15%; judge "
                 "ratios across runs (the agg pairs field exposes "
                 "the per-rep spread for exactly this reason), "
                 "never from one leg"),
    }


def bench_superbatch(reps: int = 3, bucket: int = 512,
                     k: int = 16, n_buckets: int = 192) -> dict:
    """The ISSUE 11 acceptance pair: sustained drain throughput with
    K-batch superbatch dispatch vs the K=1 leg of the SAME
    interleaved run (``paired_legs``), at one shared bucket ladder.

    Measurement shape: the queue is pre-filled with the whole leg's
    volume in large doorbell chunks and the drain loop consumes it
    flat out — the purest view of per-dispatch cost, with zero
    producer interference and batches-per-dispatch pinned at the
    configured K.  The bucket is deliberately SMALL (512): on the
    CPU backend the datapath math runs orders of magnitude slower
    than on a TPU while the Python per-dispatch cost is identical,
    so the dispatch-bound regime a real TPU sits in at EVERY bucket
    is reproduced on CPU at the small rung (at 8192 the CPU "device"
    math dominates and the same pair reads ~1.25x — recorded as
    ``ratio_top_bucket`` for honesty)."""
    import ipaddress

    from cilium_tpu.agent import Daemon, DaemonConfig
    from cilium_tpu.core.packets import (COL_DPORT, COL_DST_IP3,
                                         COL_EP, COL_FAMILY,
                                         COL_FLAGS, COL_LEN,
                                         COL_PROTO, COL_SPORT,
                                         COL_SRC_IP3, N_COLS,
                                         TCP_ACK)

    def build(B, depth_buckets):
        d = Daemon(DaemonConfig(
            backend="tpu", ct_capacity=1 << 16,
            flow_ring_capacity=1 << 14,
            serving_queue_depth=depth_buckets * B,
            serving_bucket_ladder=(B,),
            serving_max_wait_us=2000.0))
        d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        d.policy_import([{
            "endpointSelector": {"matchLabels": {"app": "db"}},
            "ingress": [{"fromEndpoints": [
                {"matchLabels": {"app": "web"}}],
                "toPorts": [{"ports": [{"port": "5432",
                                        "protocol": "TCP"}]}]}],
        }])
        rng = np.random.default_rng(31)
        src = int(ipaddress.IPv4Address("10.0.1.1"))
        dst = int(ipaddress.IPv4Address("10.0.2.1"))
        sports = (1024
                  + rng.permutation(50000)[:4096]).astype(np.uint32)

        def batch(n):
            rows = np.zeros((n, N_COLS), dtype=np.uint32)
            rows[:, COL_SRC_IP3] = src
            rows[:, COL_DST_IP3] = dst
            rows[:, COL_SPORT] = rng.choice(sports, n)
            rows[:, COL_DPORT] = 5432
            rows[:, COL_PROTO] = 6
            rows[:, COL_FLAGS] = TCP_ACK
            rows[:, COL_LEN] = 512
            rows[:, COL_FAMILY] = 4
            rows[:, COL_EP] = db.id
            return rows

        # big doorbell chunks: the fill must outrun the drain so the
        # queue actually holds K ready buckets
        chunk = max(4096, B)
        filler = [batch(chunk)
                  for _ in range(depth_buckets * B // chunk)]
        return d, filler

    def leg_fn(d, filler, kk):
        total = sum(len(c) for c in filler)

        def leg():
            d.start_serving(ring_capacity=1 << 16,
                            trace_sample=1024, ingress=True,
                            packed=True, superbatch_k=kk)
            rt = d._serving["runtime"]
            t0 = time.perf_counter()
            for c in filler:
                d.submit(c)
            deadline = t0 + 120.0
            while (rt.stats.verdicts < total
                   and time.perf_counter() < deadline):
                time.sleep(0.001)
            dt = time.perf_counter() - t0
            fe = d.stop_serving()["front-end"]
            ft = fe["fault-tolerance"]
            exact = fe["submitted"] == (fe["verdicts"] + fe["shed"]
                                        + ft["recovery-dropped"])
            return fe["verdicts"] / dt, {
                "batches_per_dispatch":
                    fe["dispatch"]["batches-per-dispatch"],
                "superbatches": fe["dispatch"]["superbatches"],
                "ledger_exact": exact,
            }

        return leg

    # -- the acceptance pair at the dispatch-bound rung --------------
    d, filler = build(bucket, n_buckets)
    base, cand = leg_fn(d, filler, 1), leg_fn(d, filler, k)
    base()
    cand()  # warm both executables outside the timed pairs
    pair = paired_legs(base, cand, reps=reps)
    comp = d.loader.compile_log.summary()
    d.shutdown()

    # -- the honesty contrast at the big rung: CPU "device" math
    # dominates there, so the same pair reads much lower ------------
    d2, filler2 = build(8192, 24)
    base2, cand2 = leg_fn(d2, filler2, 1), leg_fn(d2, filler2, 8)
    base2()
    cand2()
    top = paired_legs(base2, cand2, reps=1)
    d2.shutdown()

    ce, be = pair["candidate_extra"], pair["baseline_extra"]
    return {
        "bucket_ladder": [bucket],
        "k": k,
        "sustained_pps": pair["candidate_pps"],
        "sustained_pps_k1": pair["baseline_pps"],
        "ratio_pairs": pair["pairs"],
        "ratio_best": pair["ratio_best"],
        "ratio_median": pair["ratio_median"],
        "spread": pair["spread"],
        "batches_per_dispatch": ce["batches_per_dispatch"],
        "superbatches": ce["superbatches"],
        "ledger_exact": bool(ce["ledger_exact"]
                             and be["ledger_exact"]),
        "compile_violations": comp["violations"],
        "ratio_top_bucket": top["ratio_best"],
        "top_bucket_pps": {"k1": top["baseline_pps"],
                           "k8": top["candidate_pps"]},
        "note": ("pre-filled-queue drain legs, K=%d vs K=1 "
                 "interleaved per pair (paired_legs); bucket %d is "
                 "the dispatch-bound rung on CPU — the honest proxy "
                 "for TPU behavior at every bucket, where device "
                 "math is microseconds and Python dispatch is the "
                 "ceiling; ratio_top_bucket shows the same pair at "
                 "8192 where the CPU datapath math dominates"
                 % (k, bucket)),
    }


def bench_recovery() -> dict:
    """--recovery: fault-tolerance latency phase (ISSUE 3).  Measures,
    best-of-3 INTERLEAVED (CPU wall timings swing +-15%, so each rep
    runs all three scenarios back to back and the minimum is
    reported):

    - ``restart_recovery_ms``: injected dispatch death -> first
      healthy dispatch of the restarted drain loop;
    - ``hang_detect_ms``: injected dispatch hang -> watchdog restart
      recorded (the detection latency the deadline knob governs);
    - ``demotion_ms``: injected packed-path fault streak -> first
      successful dispatch on the demoted (wide) rung;
    - ``promotion_ms``: cooldown start -> first batch after
      re-promotion to the packed rung.

    CPU-bounded and deterministic (seeded injector); each scenario
    uses a FRESH daemon so compile warmup is inside the rep and
    excluded from the measured windows (warm batches run first)."""
    import ipaddress

    import jax

    from cilium_tpu.agent import Daemon, DaemonConfig
    from cilium_tpu.core.packets import (COL_DPORT, COL_DST_IP3,
                                         COL_EP, COL_FAMILY,
                                         COL_FLAGS, COL_LEN,
                                         COL_PROTO, COL_SPORT,
                                         COL_SRC_IP3, N_COLS, TCP_ACK)

    src = int(ipaddress.IPv4Address("10.0.1.1"))
    dst = int(ipaddress.IPv4Address("10.0.2.1"))

    def batch(n, ep_id):
        rows = np.zeros((n, N_COLS), dtype=np.uint32)
        rows[:, COL_SRC_IP3] = src
        rows[:, COL_DST_IP3] = dst
        rows[:, COL_SPORT] = (20000 + np.arange(n)) % 60000
        rows[:, COL_DPORT] = 5432
        rows[:, COL_PROTO] = 6
        rows[:, COL_FLAGS] = TCP_ACK
        rows[:, COL_LEN] = 512
        rows[:, COL_FAMILY] = 4
        rows[:, COL_EP] = ep_id
        return rows

    def fresh(spec, **over):
        cfg = dict(backend="tpu", ct_capacity=1 << 14,
                   flow_ring_capacity=1 << 13,
                   serving_queue_depth=4096,
                   serving_bucket_ladder=(512,),
                   serving_max_wait_us=500.0,
                   serving_dispatch_deadline_ms=100.0,
                   serving_restart_budget=8,
                   serving_restart_backoff_ms=1.0,
                   serving_demote_threshold=1,
                   serving_promote_after=2,
                   serving_promote_cooldown_s=0.05,
                   fault_injection=spec, fault_seed=7)
        cfg.update(over)
        d = Daemon(DaemonConfig(**cfg))
        d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        d.policy_import([{
            "endpointSelector": {"matchLabels": {"app": "db"}},
            "ingress": [{"fromEndpoints": [
                {"matchLabels": {"app": "web"}}],
                "toPorts": [{"ports": [{"port": "5432",
                                        "protocol": "TCP"}]}]}],
        }])
        return d, db

    def pump_until(rt, pred, tmax=30.0):
        t0 = time.perf_counter()
        while not pred():
            if time.perf_counter() - t0 > tmax:
                raise TimeoutError("recovery bench stalled")
            time.sleep(0.001)
        return time.perf_counter()

    def rep_restart() -> float:
        """dispatch death -> first healthy post-restart dispatch."""
        d, db = fresh("serving.dispatch=1x1@1")
        d.start_serving(trace_sample=0, ingress=True)
        rt = d._serving["runtime"]
        rows = batch(512, db.id)
        d.submit(rows)  # warm (compile outside the window)
        pump_until(rt, lambda: rt.stats.verdicts >= 512)
        t0 = time.perf_counter()
        d.submit(rows)  # dies
        d.submit(rows)  # dispatches after the restart
        t1 = pump_until(rt, lambda: rt.stats.verdicts >= 1024)
        d.stop_serving()
        d.shutdown()
        return (t1 - t0) * 1e3

    def rep_hang_detect() -> float:
        """hang start -> watchdog restart recorded (deadline 100ms)."""
        d, db = fresh("serving.dispatch=1x1@1~3")
        d.start_serving(trace_sample=0, ingress=True)
        rt = d._serving["runtime"]
        rows = batch(512, db.id)
        d.submit(rows)
        pump_until(rt, lambda: rt.stats.verdicts >= 512)
        t0 = time.perf_counter()
        d.submit(rows)  # hangs
        t1 = pump_until(rt, lambda: rt.stats.restarts >= 1,
                        tmax=10.0)
        d.stop_serving()
        d.shutdown()
        return (t1 - t0) * 1e3

    def rep_ladder() -> tuple:
        """(demotion_ms, promotion_ms): packed fault -> first wide
        dispatch; cooldown -> first post-promotion batch."""
        d, db = fresh("loader.serve_packed=1x1@1",
                      serving_dispatch_deadline_ms=5000.0)
        d.start_serving(trace_sample=0, ingress=True, packed=True)
        rt = d._serving["runtime"]
        rows = batch(512, db.id)
        d.submit(rows)  # warm the packed rung
        pump_until(rt, lambda: rt.stats.verdicts >= 512)
        t0 = time.perf_counter()
        d.submit(rows)  # faults -> demote (threshold 1) -> retried
        t1 = pump_until(rt, lambda: rt.stats.verdicts >= 1024)
        demote_ms = (t1 - t0) * 1e3
        lad = d._serving["ladder"]
        assert lad.rung == "wide", "bench expected a demotion"
        t2 = time.perf_counter()
        n = 2
        while lad.rung != "single":  # healthy batches + cooldown
            d.submit(rows)
            n += 1
            pump_until(rt, lambda: rt.stats.verdicts >= n * 512)
            time.sleep(0.02)
        t3 = time.perf_counter()
        d.stop_serving()
        d.shutdown()
        return demote_ms, (t3 - t2) * 1e3

    restart_ms = hang_ms = demote_ms = promote_ms = float("inf")
    for _ in range(3):  # best-of-3 interleaved
        restart_ms = min(restart_ms, rep_restart())
        hang_ms = min(hang_ms, rep_hang_detect())
        dm, pm = rep_ladder()
        demote_ms = min(demote_ms, dm)
        promote_ms = min(promote_ms, pm)

    import jax as _jax

    return {
        "restart_recovery_ms": round(restart_ms, 2),
        "hang_detect_ms": round(hang_ms, 2),
        "dispatch_deadline_ms": 100.0,
        "demotion_ms": round(demote_ms, 2),
        "promotion_ms": round(promote_ms, 2),
        "promote_cooldown_ms": 50.0,
        "restart_backoff_ms": 1.0,
        "platform": _jax.default_backend(),
        "note": ("fault injected -> first healthy dispatch, best-of-3"
                 " interleaved; hang_detect is watchdog-deadline"
                 " governed (deadline 100ms), demotion includes the"
                 " demoted rung's first-dispatch compile,"
                 " promotion includes the configured 50ms cooldown"),
    }


def _run_recovery_phase() -> None:
    """--recovery: the fault-tolerance latency phase standalone (one
    JSON line).  Also writes BENCH_recovery.json next to this file;
    runs bounded under JAX_PLATFORMS=cpu."""
    import os

    out = bench_recovery()
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_recovery.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out))


def _run_serving_phase() -> None:
    """--serving: the serving front-end phase standalone (one JSON
    line).  Also writes BENCH_serving.json next to this file — the
    artifact that seeds the BENCH trajectory; runs bounded under
    JAX_PLATFORMS=cpu."""
    import os

    out = bench_serving()
    # the ISSUE 11 acceptance pair: K-batch superbatch dispatch vs
    # the K=1 leg of the same interleaved run (paired_legs), plus a
    # top-level ratio mirror for the trajectory reader
    out["superbatch"] = bench_superbatch()
    out["superbatch_ratio"] = out["superbatch"]["ratio_best"]
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out))


def _hist_pct_delta(before, after, p, max_us_hint=None):
    """Percentile over the DELTA of two log2-µs histogram snapshots —
    lets a leg report its own p99 out of a cumulative histogram
    without a reset API.  Delegates to LatencyHistogram.percentile on
    a throwaway instance so the bucket convention and interpolation
    can never drift from the registry/CLI numbers."""
    from cilium_tpu.serving.stats import LatencyHistogram

    h = LatencyHistogram()
    h.buckets = [b - a for a, b in zip(before, after)]
    h.count = sum(h.buckets)
    if h.count <= 0:
        return None
    h.max_us = (float(max_us_hint) if max_us_hint
                else float("inf"))
    v = h.percentile(p)
    return round(v, 3) if v is not None else None


def bench_churn(target_packets=81920, reps=3, churn_hz=200.0) -> dict:
    """--churn: live policy/identity churn under serving (ISSUE 10)
    -> BENCH_churn.json.

    Two legs per rep, INTERLEAVED (rep k runs no-churn then churn
    back to back so both sample the same machine weather; best-of-3
    per leg):

    - NO-CHURN OVERLOAD: the PR 1-style sustained leg on the packed
      path at one bucket rung — the baseline ``sustained_pps``.
    - CHURN OVERLOAD: the same loop while the seeded
      ``identity_churn`` scenario (testing/workloads.py) mints and
      withdraws label-selected peer identities at ``churn_hz`` from
      the driver thread — every op is a patch_identity +
      patch_ipcache publish pair against the live tables.

    Reported: ``sustained_pps_churn`` vs ``sustained_pps`` (the
    churn tax), ``update_visible_p50/p99_us`` (mutation entry ->
    published generation, measured per op by the driver),
    ``swap_stall_p99_us`` (dispatch-lock hold per publish flip, from
    the churn legs' delta of the loader's cumulative histogram), the
    generation/swap totals, ``ledger_exact`` (every leg's
    ``submitted == verdicts + shed + recovery_dropped``), and
    ``compile_violations`` — the one-executable guard must stay at
    zero through churn (identity churn never retraces the serving
    executables; that IS the delta-compile story)."""
    import ipaddress

    from cilium_tpu.agent import Daemon, DaemonConfig
    from cilium_tpu.core.packets import (COL_DPORT, COL_DST_IP3,
                                         COL_EP, COL_FAMILY,
                                         COL_FLAGS, COL_LEN,
                                         COL_PROTO, COL_SPORT,
                                         COL_SRC_IP3, N_COLS,
                                         TCP_ACK)
    from cilium_tpu.testing.workloads import make_scenario

    BUCKET = 2048
    d = Daemon(DaemonConfig(
        backend="tpu", ct_capacity=1 << 16,
        flow_ring_capacity=1 << 14,
        serving_queue_depth=1 << 15,
        serving_bucket_ladder=(BUCKET,),
        serving_max_wait_us=2000.0))
    d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
    db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
    d.policy_import([{
        "endpointSelector": {"matchLabels": {"app": "db"}},
        "ingress": [
            {"fromEndpoints": [{"matchLabels": {"app": "web"}}],
             "toPorts": [{"ports": [{"port": "5432",
                                     "protocol": "TCP"}]}]},
            {"fromEndpoints": [{"matchLabels": {"churn": "yes"}}],
             "toPorts": [{"ports": [{"port": "5432",
                                     "protocol": "TCP"}]}]},
        ],
    }])
    d.start()
    sc = make_scenario("identity_churn", seed=23, n_slots=16,
                       zipf_a=1.3, rate_hz=churn_hz)
    rng = np.random.default_rng(23)
    src = int(ipaddress.IPv4Address("10.0.1.1"))
    dst = int(ipaddress.IPv4Address("10.0.2.1"))
    sports = (1024 + rng.permutation(50000)[:4096]).astype(np.uint32)

    def batch(n):
        rows = np.zeros((n, N_COLS), dtype=np.uint32)
        rows[:, COL_SRC_IP3] = src
        rows[:, COL_DST_IP3] = dst
        rows[:, COL_SPORT] = rng.choice(sports, n)
        rows[:, COL_DPORT] = 5432
        rows[:, COL_PROTO] = 6
        rows[:, COL_FLAGS] = TCP_ACK
        rows[:, COL_LEN] = 512
        rows[:, COL_FAMILY] = 4
        rows[:, COL_EP] = db.id
        return rows

    chunks = [batch(max(int(rng.poisson(1024.0)), 1))
              for _ in range(32)]

    # warm: the serving executables at this rung AND the patch
    # publish ops (first .at[].set per shape pays a tiny compile)
    d.start_serving(ring_capacity=1 << 14, trace_sample=0,
                    packed=True, ingress=True)
    d.submit(batch(BUCKET))
    live = {}
    ops_warm = iter(sc.iter_ops())
    for _ in range(4):
        sc.apply(d, next(ops_warm), live)
    t0 = time.perf_counter()
    while (d._serving["runtime"].stats.verdicts < BUCKET
           and time.perf_counter() - t0 < 120):
        time.sleep(0.005)
    d.stop_serving()
    # superbatch executables for the K>1 churn legs (ISSUE 11): warm
    # each K rung's packed scan so no timed leg pays an XLA compile
    from cilium_tpu.core.packets import pack_eligibility, pack_rows
    from cilium_tpu.serving.batcher import SuperBatch

    w = batch(BUCKET)
    ok, ep, dirn = pack_eligibility(w)
    pw = pack_rows(w)
    d.start_serving(ring_capacity=1 << 14, trace_sample=0,
                    packed=True)
    for K in (2, 4, 8):
        d.serve_superbatch(SuperBatch(
            hdr=np.stack([pw] * K),
            valid=np.ones((K, BUCKET), dtype=bool),
            bucket=BUCKET, arrivals=[], packed=True,
            eps=np.full(K, ep, np.uint32),
            dirns=np.full(K, dirn, np.uint32)))
    d.stop_serving()
    # warmup identities must not leak into the measured legs' worlds
    sc.drain(d, live)

    def overload_leg(churn: bool, superbatch_k: int = 1):
        q = None
        d.start_serving(ring_capacity=1 << 14, trace_sample=0,
                        packed=True, ingress=True,
                        superbatch_k=superbatch_k)
        q = d._serving["runtime"].queue
        ops = iter(sc.iter_ops())
        leg_live = {}
        op_lat = []
        next_op = time.perf_counter()
        submitted = 0
        t0 = time.perf_counter()
        while submitted < target_packets:
            for c in chunks:
                if submitted >= target_packets:
                    break
                submitted += d.submit(c.copy())
                if q.pending > (1 << 15) // 2:
                    while q.pending > (1 << 15) // 4:
                        if churn and time.perf_counter() >= next_op:
                            break
                        time.sleep(0.001)
                if churn and time.perf_counter() >= next_op:
                    next_op += sc.interval_s
                    t1 = time.perf_counter()
                    sc.apply(d, next(ops), leg_live)
                    op_lat.append((time.perf_counter() - t1) * 1e6)
        fe = d.stop_serving()["front-end"]
        dt = time.perf_counter() - t0
        ft = fe["fault-tolerance"]
        exact = fe["submitted"] == (fe["verdicts"] + fe["shed"]
                                    + ft["recovery-dropped"])
        # drain the leg's surviving identities so legs are
        # independent worlds
        sc.drain(d, leg_live)
        return fe["verdicts"] / dt, {
            "op_lat": op_lat, "exact": exact,
            "bpd": fe["dispatch"]["batches-per-dispatch"]}

    # paired-leg harness (ISSUE 11 satellite): each pair runs
    # no-churn/churn back to back with alternating order, ratios are
    # per-pair — weather slows both legs of a pair together.  Two
    # pairs: the K=1 trajectory leg and the K=8 superbatch leg, the
    # latter recording update-visible latency at superbatch
    # granularity (one dispatch pins a generation for K batches)
    lat_by_k = {1: [], 8: []}
    state = {"exact": True, "ops": 0}

    def make_leg(churn: bool, k: int):
        def fn():
            pps, extra = overload_leg(churn, superbatch_k=k)
            state["exact"] = state["exact"] and extra["exact"]
            if churn:
                lat_by_k[k].extend(extra["op_lat"])
                state["ops"] += len(extra["op_lat"])
            return pps, extra
        return fn

    stall_before = list(d.loader.tables.swap_stall.buckets)
    pair_k1 = paired_legs(make_leg(False, 1), make_leg(True, 1),
                          reps=reps)
    pair_k8 = paired_legs(make_leg(False, 8), make_leg(True, 8),
                          reps=reps)
    stall_after = list(d.loader.tables.swap_stall.buckets)
    stall_p99 = _hist_pct_delta(
        stall_before, stall_after, 0.99,
        max_us_hint=d.loader.tables.swap_stall.max_us)
    ts = d.loader.table_stats()
    comp = d.loader.compile_log.summary()
    d.shutdown()
    lat1 = (np.asarray(lat_by_k[1]) if lat_by_k[1]
            else np.zeros(1))
    lat8 = (np.asarray(lat_by_k[8]) if lat_by_k[8]
            else np.zeros(1))
    return {
        "schema": "bench-churn-v1",
        "best_of": reps,
        "sustained_pps": pair_k1["baseline_pps"],
        "sustained_pps_churn": pair_k1["candidate_pps"],
        # per-pair median, not best/best: the paired harness's
        # whole point (pairs + spread recorded alongside)
        "churn_ratio": pair_k1["ratio_median"],
        "churn_ratio_pairs": pair_k1["pairs"],
        "churn_ratio_spread": pair_k1["spread"],
        "churn_ops": state["ops"],
        "churn_rate_hz": churn_hz,
        "update_visible_p50_us": round(
            float(np.percentile(lat1, 50)), 1),
        "update_visible_p99_us": round(
            float(np.percentile(lat1, 99)), 1),
        # the K=8 superbatch legs (ISSUE 11): generation pinning at
        # superbatch granularity — one dispatch pins one table
        # generation for K batches, so update-visible latency is the
        # number to watch as K grows
        "superbatch_k": 8,
        "sustained_pps_k8": pair_k8["baseline_pps"],
        "sustained_pps_churn_k8": pair_k8["candidate_pps"],
        "churn_ratio_k8": pair_k8["ratio_median"],
        "churn_ratio_k8_pairs": pair_k8["pairs"],
        "batches_per_dispatch_k8":
            (pair_k8["candidate_extra"] or {}).get("bpd"),
        "update_visible_p50_us_k8": round(
            float(np.percentile(lat8, 50)), 1),
        "update_visible_p99_us_k8": round(
            float(np.percentile(lat8, 99)), 1),
        "swap_stall_p99_us": stall_p99,
        "swaps": ts["swaps"],
        "generation": ts["generation"],
        "delta_attaches": ts["delta-attaches"],
        "patches": ts["patches"],
        "ledger_exact": state["exact"],
        "compile_violations": comp["violations"],
        "note": ("churn legs mint/withdraw label-selected peer "
                 "identities (2 publish flips per op) from the "
                 "driver thread during the packed overload leg; "
                 "update-visible latency measured per op by the "
                 "driver, swap stall from the loader histogram's "
                 "leg delta; paired-leg harness: ratios are per-pair "
                 "medians over %d order-alternated no-churn/churn "
                 "pairs (pairs + spread recorded), at K=1 and at "
                 "superbatch K=8" % reps),
    }


def _run_churn_phase() -> None:
    """--churn: the live-churn phase standalone (one JSON line).
    Also writes BENCH_churn.json next to this file; schema-checked
    by the CTA009 bench machinery."""
    import os

    out = bench_churn()
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_churn.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out))


def bench_scenarios(seed: int = 31) -> dict:
    """--scenarios: the adversarial scenario engine (ISSUE 12) ->
    BENCH_scenarios.json.

    Every registered scenario (``testing/workloads.SCENARIOS``) runs
    once through the shared :func:`run_scenario` driver against a
    fresh daemon built from the scenario's own ``daemon_overrides``
    (the pressure shape it declares — a 1k-entry CT map for
    ``syn_flood``, a 256-port SNAT pool for ``nat_exhaustion``) and
    is judged against its DECLARED pass criteria.  Per-scenario
    sustained pps, shed fraction, pressure counters, and pass/fail
    land in the artifact; ``all_passed`` is the regression gate.
    Schema-checked by the CTA010 machinery (importable
    ``check_bench`` in ``cilium_tpu.analysis.scenario_lint``).

    CPU-bounded numbers (the standing caveat): pps here defends the
    DRIVER's honesty (ledger exact under each hostile shape), not
    device throughput — --serving/--churn own the speed story."""
    from cilium_tpu.testing.workloads import (SCENARIOS,
                                              make_scenario,
                                              run_scenario,
                                              scenario_daemon)

    results = {}
    for name in sorted(SCENARIOS):
        sc = make_scenario(name, seed=seed)
        if getattr(sc, "cluster_ops", False):
            # cluster-facade op streams (rotation_storm's epoch
            # bumps) have no plain-daemon leg — the soak gate's
            # encrypted cluster leg owns them (ISSUE 18)
            continue
        d = None
        try:
            # construction/start INSIDE the guard: one scenario's
            # bad daemon shape must not abort the whole sweep either
            d = scenario_daemon(sc, map_pressure_interval=0.25)
            d.start()
            r = run_scenario(d, sc)
            m = r["metrics"]
            results[name] = {
                "seed": r["seed"],
                "criteria": r["criteria"],
                "checks": r["checks"],
                "passed": r["passed"],
                "sustained_pps": m["sustained_pps"],
                "shed_frac": m["shed_frac"],
                "p99_us": m["p99_us"],
                "packets": m["submitted"],
                "ops_applied": m["ops_applied"],
                "ct_insert_drops": m["ct_insert_drops"],
                "nat_failures": m["nat_failures"],
                "drop_frac": m["drop_frac"],
                "pressure_state": d.pressure.stats()["state"],
                "pressure_episodes": d.pressure.stats()["episodes"],
            }
        except Exception as e:  # one hostile shape failing must not
            results[name] = {  # hide the rest of the sweep
                "seed": seed, "criteria": dict(sc.criteria),
                "checks": {}, "passed": False,
                "sustained_pps": 0.0, "shed_frac": None,
                "error": f"{type(e).__name__}: {e}"[:200],
            }
        finally:
            if d is not None:
                d.shutdown()
    return {
        "schema": "bench-scenarios-v1",
        "scenarios": results,
        "all_passed": all(r.get("passed") for r in results.values()),
        "note": ("each scenario runs the shared run_scenario driver "
                 "against a fresh daemon built from its own "
                 "daemon_overrides and is judged against its "
                 "DECLARED criteria; pps is CPU-bounded and defends "
                 "ledger exactness under hostile shapes, not device "
                 "throughput"),
    }


def _run_scenarios_phase() -> None:
    """--scenarios: the adversarial scenario phase standalone (one
    JSON line).  Also writes BENCH_scenarios.json next to this file;
    schema-checked by the CTA010 bench machinery."""
    import os

    out = bench_scenarios()
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_scenarios.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out))


def bench_cluster(target_packets=98304, reps=3) -> dict:
    """--cluster: the clustermesh serving tier phase (ISSUE 8 +
    ISSUE 13 + ISSUE 17) -> BENCH_cluster.json.

    Eight legs, CPU-bounded and deterministic:

    - SCALING-vs-NODES, PER MODE (``thread`` and ``process``):
      sustained verdicts/sec through the cluster front end at
      N = 1 / 2 / 3 replicas, measured with the ``paired_legs``
      harness (interleaved rep-by-rep, pair order ALTERNATING, the
      per-pair ratios + spread shipped alongside best absolutes) —
      scaling_nK is the PAIR-MEDIAN of nK/n1 ratios, not a
      best-vs-best.  Thread mode is the PR 8 shape (replicas share
      one GIL — the curve documents the contention penalty); process
      mode is one worker PROCESS per node forwarding over real
      sockets, the shape where N nodes buy N cores.  HONESTY FLOOR:
      ``host_cores`` records ``os.cpu_count()`` — a 1-core host
      cannot show N-core speedups in ANY mode (processes time-slice
      one core); on such hosts the process curve's claim is
      "adding nodes no longer makes the cluster SLOWER" (vs the
      thread curve's sub-1.0), and the linear-speedup claim needs a
      multi-core host.

    - FORWARD-PATH LATENCY: enqueue -> delivered percentiles from
      the router's histogram (queue wait + node submit / socket
      round trip), per mode, taken from the N=3 legs.

    - FAILOVER BLACKOUT (process mode — the PR 8 proof re-made
      against a real SIGKILL): a 3-worker cluster under sustained
      load; one worker is SIGKILLed and health-detected, the
      parent-retained CT snapshot replays onto the peer, the router
      re-pins, and the ledger closes exactly with the corpse's
      admitted-but-unresolved rows counted ``crash_dropped``.

    - LIVE SCALE-OUT (process mode): ``add_node()`` on the serving
      cluster — build/converge/warm off to the side, freeze +
      quiesce, slot re-pin + CT migration, resume; the pause window
      and survivor recompile count ship in the artifact.

    v3 legs (ISSUE 17 — the pipelined data channel):

    - PIPELINED THROUGHPUT (process mode, ONE node, small frames):
      window=1 (the PR 13 sync-ack protocol, byte-identical wire)
      vs window=8 (credit-windowed streaming with coalesced acks),
      through the ``paired_legs`` harness — ``pipelined_speedup``
      is the PAIR-MEDIAN of windowed/sync ratios.  Small frames on
      purpose: the channel is ACK-CADENCE-bound, the regime the
      window exists for (big frames amortize the RTT and hide it).
      Same ``host_cores`` honesty floor as the scaling curve: the
      overlap win needs parent and worker on separate cores — a
      1-core host shows only the ack-coalescing share of it.

    - FORWARD-LATENCY p50 AT LOW LOAD, sync vs pipelined: one small
      frame at a time, fully landed before the next — the window
      must not buy throughput by selling latency
      (``latency_p50_ratio`` is pipelined/sync; target <= 1.5x —
      what the worker's flush-on-drain ack exists for).
      Both sides measure the SAME enqueue->acked interval (the sync
      path's blocking submit and the windowed path's cumulative-ack
      retire record into one histogram).

    - SIGKILL MID-WINDOW (process mode): the corpse dies with the
      credit window OPEN — sent-but-unacked frames outstanding.
      The last cumulative ack is the final word; everything past it
      requeues to the failover peer or lands ``crash_dropped``, and
      the ledger closes EXACTLY (the property test's claim, re-made
      against a real process corpse under real load).

    - LIVE SCALE-IN (process mode): ``remove_node()`` on the
      serving cluster — freeze + quiesce (window drained), victim
      CT migrated out, slots re-pinned onto survivors, victim
      retired; the pause window and the ZERO survivor-recompile
      count ship in the artifact.

    v4 legs (ISSUE 18 — the encrypted data channel):

    - ENCRYPTED THROUGHPUT (process mode, ONE node, the shipped
      window): ``cluster_encrypt=False`` vs ``True`` through the
      ``paired_legs`` harness — ``encrypted_ratio`` is the
      PAIR-MEDIAN of encrypted/plaintext rates, the AEAD toll
      honestly measured on the same wire at the same window (one
      seal per frame + one open per ack on the parent, the mirror
      pair on the worker).

    - SEAL/OPEN LATENCY: per-op percentiles for one bucket-sized
      packed wire buffer through ``EncryptedChannel`` directly (no
      cluster in the loop) — the per-frame cost floor an operator
      pays for ``cluster_encrypt=True``.

    - SIGKILL MID-ROTATION (process mode, encrypted): the corpse
      dies CONCURRENT with a cluster-wide ``rotate_epoch`` under an
      open window.  Whatever interleaving lands (rotation acked
      then killed, killed mid-ack, killed before), the survivors
      carry the new epoch, every undecryptable/unacked frame's rows
      are counted (``crypto_dropped``/``crash_dropped``), and the
      ledger closes EXACTLY — the chaos gate's claim, re-made as a
      shipped artifact."""
    import ipaddress
    import os as _os
    import threading as _threading

    from cilium_tpu.agent import DaemonConfig
    from cilium_tpu.cluster import ClusterServing
    from cilium_tpu.core.packets import (COL_DPORT, COL_DST_IP3,
                                         COL_EP, COL_FAMILY,
                                         COL_FLAGS, COL_LEN,
                                         COL_PROTO, COL_SPORT,
                                         COL_SRC_IP3, N_COLS, TCP_ACK)

    BUCKET = 2048
    rng = np.random.default_rng(11)
    src = int(ipaddress.IPv4Address("10.0.1.1"))
    dst = int(ipaddress.IPv4Address("10.0.2.1"))
    sports = (1024 + rng.permutation(50000)[:4096]).astype(np.uint32)

    def cfg(**over):
        base = dict(backend="tpu", ct_capacity=1 << 14,
                    flow_ring_capacity=1 << 13,
                    serving_queue_depth=1 << 15,
                    serving_bucket_ladder=(BUCKET,),
                    serving_max_wait_us=1000.0,
                    serving_restart_backoff_ms=1.0,
                    cluster_forward_depth=1 << 15,
                    cluster_probe_interval_s=0.05,
                    cluster_death_threshold=2)
        base.update(over)
        return DaemonConfig(**base)

    def batch(n, db_id):
        rows = np.zeros((n, N_COLS), dtype=np.uint32)
        rows[:, COL_SRC_IP3] = src
        rows[:, COL_DST_IP3] = dst
        rows[:, COL_SPORT] = rng.choice(sports, n)
        rows[:, COL_DPORT] = 5432
        rows[:, COL_PROTO] = 6
        rows[:, COL_FLAGS] = TCP_ACK
        rows[:, COL_LEN] = 512
        rows[:, COL_FAMILY] = 4
        rows[:, COL_EP] = db_id
        return rows

    RULES = [{
        "endpointSelector": {"matchLabels": {"app": "db"}},
        "ingress": [{"fromEndpoints": [
            {"matchLabels": {"app": "web"}}],
            "toPorts": [{"ports": [{"port": "5432",
                                    "protocol": "TCP"}]}]}],
    }]

    def build(n_nodes, mode, **over):
        c = ClusterServing(nodes=n_nodes,
                           config=cfg(cluster_mode=mode, **over))
        c.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db = c.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        rev = c.policy_import(RULES)
        assert c.wait_policy(rev, timeout=30)
        c.start(trace_sample=0, packed=True, ring_capacity=1 << 15)
        return c, db

    fwd_latency = {}

    def leg(n_nodes, mode):
        """One scaling leg: an untimed settle wave (post-bring-up
        allocator/thread steady state), then offer chunks until
        target_packets are ADMITTED (backpressure-paced) and time to
        the last verdict LANDING — stop()/teardown cost (control
        RPCs, worker reaping) never bills the throughput number."""
        c, db = build(n_nodes, mode)
        try:
            chunks = [batch(BUCKET, db.id) for _ in range(8)]

            def accounted():
                return c.ledger()["per-node-accounted"]

            for i in range(4):  # settle wave, untimed
                c.submit(chunks[i])
            t0 = time.perf_counter()
            while accounted() < 4 * BUCKET:
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("settle wave stalled")
                time.sleep(0.002)
            base = accounted()
            admitted = i = 0
            t0 = time.perf_counter()
            while admitted < target_packets:
                got = c.submit(chunks[i % len(chunks)])
                admitted += got
                i += 1
                if got < BUCKET:
                    time.sleep(0.0005)  # router/queue full
            while accounted() - base < admitted:
                if time.perf_counter() - t0 > 300:
                    raise TimeoutError("scaling leg stalled")
                time.sleep(0.002)
            dt = time.perf_counter() - t0
            st = c.stop()
            assert st["ledger"]["exact"], st["ledger"]
            if n_nodes == 3:
                fwd_latency[mode] = (st["cluster"]["router"]
                                     or {}).get("forward-latency-us")
            return admitted / dt
        finally:
            c.shutdown()
            # settle: worker teardown (process reap, socket close)
            # must not bleed CPU into the next leg's timed window
            time.sleep(0.5)

    modes_out = {}
    ledger_ok = True
    for mode in ("thread", "process"):
        # untimed warm leg: executables + thread/alloc steady state
        # must not bill the first timed rep (process workers warm
        # their own caches inside bring-up, off the timed window)
        leg(1, mode)
        n1_best = 0.0
        curve = {}
        for n_nodes in (2, 3):
            pair = paired_legs(lambda m=mode: leg(1, m),
                               lambda m=mode, n=n_nodes: leg(n, m),
                               reps=reps)
            n1_best = max(n1_best, pair["baseline_pps"])
            curve[n_nodes] = pair
        modes_out[mode] = {
            "sustained_pps_n1": round(n1_best),
            "sustained_pps_n2": curve[2]["candidate_pps"],
            "sustained_pps_n3": curve[3]["candidate_pps"],
            "scaling_n2": curve[2]["ratio_median"],
            "scaling_n3": curve[3]["ratio_median"],
            "scaling_n2_pairs": curve[2]["pairs"],
            "scaling_n3_pairs": curve[3]["pairs"],
            "scaling_n2_spread": curve[2]["spread"],
            "scaling_n3_spread": curve[3]["spread"],
            "forward_latency_us": fwd_latency.get(mode),
        }

    def failover_rep() -> dict:
        """SIGKILL failover under load, process mode: the PR 8
        blackout/CT-replay numbers against a real process corpse."""
        c, db = build(3, "process")
        try:
            warm = batch(BUCKET, db.id)
            c.submit(warm)
            t0 = time.perf_counter()
            while c.ledger()["per-node-accounted"] < BUCKET:
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("cluster bench stalled")
                time.sleep(0.002)
            c.snapshot_now()  # parent-retained replica per worker
            c.node("node1").proc.kill()  # raw SIGKILL mid-serve
            while not c.membership.is_dead("node1"):
                c.submit(batch(BUCKET, db.id))
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("death never detected")
                time.sleep(0.002)
            while c.failovers_total() < 1:
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("failover never completed")
                time.sleep(0.002)
            rec = c.failover.snapshot()[0]
            # post-failover: the survivors keep serving
            c.submit(batch(BUCKET, db.id))
            st = c.stop()
            assert st["ledger"]["exact"], st["ledger"]
            return {
                "blackout_ms": rec["blackout-ms"],
                "detect_ms": rec["detect-ms"],
                "ct_entries": rec["ct-replayed-entries"],
                "failover_dropped":
                    st["ledger"]["failover-dropped"],
                "crash_dropped": st["ledger"]["crash-dropped"],
                "ledger_exact": st["ledger"]["exact"],
            }
        finally:
            c.shutdown()

    fo = [failover_rep() for _ in range(reps)]
    best = min(fo, key=lambda r: r["blackout_ms"])
    ledger_ok = ledger_ok and all(r["ledger_exact"] for r in fo)

    def scale_out_leg() -> dict:
        """add_node() on a live 2-worker cluster under established
        flows: the pause window + CT migration + survivor compile
        counts, ledger exact across the transition."""
        c, db = build(2, "process")
        try:
            c.submit(batch(BUCKET, db.id))
            t0 = time.perf_counter()
            while c.ledger()["per-node-accounted"] < BUCKET:
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("scale-out leg stalled")
                time.sleep(0.002)
            rec = c.add_node()
            c.submit(batch(BUCKET, db.id))
            st = c.stop()
            assert st["ledger"]["exact"], st["ledger"]
            return {
                "pause_ms": rec["pause-ms"],
                "build_ms": rec["build-ms"],
                "moved_slots": rec["moved-slots"],
                "ct_migrated_entries": rec["ct-migrated-entries"],
                "survivor_recompiles": rec["survivor-recompiles"],
                "ledger_exact": st["ledger"]["exact"],
            }
        finally:
            c.shutdown()

    so = scale_out_leg()
    ledger_ok = ledger_ok and so["ledger_exact"]

    # -- v3: the pipelined data channel (ISSUE 17) --------------------
    FRAME = 128       # small frames: the channel is ack-cadence-
    WAVE_FRAMES = 128  # bound, the regime the window exists for
    WAVES = 9
    WINDOW = cfg().cluster_forward_window  # the shipped default

    def window_leg(window: int, encrypt: bool = False) -> float:
        """Per-node forward throughput through ONE process-mode
        channel at the given credit window.  window=1 degenerates to
        the PR 13 sync-ack protocol (one frame in flight, one ack
        per frame, byte-identical wire) — the baseline side of the
        paired legs.  The timed interval per WAVE is push-from-idle
        to all-RETIRED (sync: the blocking submit returned = acked;
        windowed: the cumulative ack covered it) — the channel rate,
        with the worker's verdict pipeline draining UNTIMED between
        waves so the verdict executor's throughput does not cap both
        sides into a false tie.  Median-of-waves damps scheduler
        weather (this leg is switch-cost-sensitive on small hosts).
        HONESTY FLOOR: the overlap win (parent packs frame k+1 while
        the worker admits frame k) needs parent and worker on
        SEPARATE cores; a 1-core host time-slices them and the
        measured win shrinks to what ack-coalescing alone buys
        (fewer wakeups + 1/ack_every of the ack legs) — the >=2x
        claim needs ``host_cores`` >= 2, same convention as the
        scaling curve.  ``encrypt=True`` runs the identical leg
        with the channel sealed (the v4 paired comparison)."""
        c, db = build(1, "process", cluster_forward_window=window,
                      cluster_encrypt=encrypt)
        try:
            frames = [batch(FRAME, db.id) for _ in range(16)]
            wave_rows = WAVE_FRAMES * FRAME

            def accounted():
                return c.ledger()["per-node-accounted"]

            def fwd():
                # dirty read on purpose: a locked snapshot() in the
                # poll loop would stall the ack reader's retire path
                # and bill the contention to the thing measured
                return sum(c.router.forwarded)

            for i in range(8):  # settle wave, untimed
                c.submit(frames[i % len(frames)])
            t0 = time.perf_counter()
            while accounted() < 8 * FRAME:
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("window settle stalled")
                time.sleep(0.002)
            rates = []
            for w in range(WAVES):
                # drain: worker queue empty before the timed push
                t0 = time.perf_counter()
                while accounted() < 8 * FRAME + w * wave_rows:
                    if time.perf_counter() - t0 > 120:
                        raise TimeoutError("window drain stalled")
                    time.sleep(0.002)
                f0 = fwd()
                t0 = time.perf_counter()
                for i in range(WAVE_FRAMES):
                    got = c.submit(frames[i % len(frames)])
                    assert got == FRAME, "router backpressured"
                while fwd() - f0 < wave_rows:
                    if time.perf_counter() - t0 > 120:
                        raise TimeoutError("window wave stalled")
                    time.sleep(0.0005)
                rates.append(wave_rows / (time.perf_counter() - t0))
            st = c.stop()
            assert st["ledger"]["exact"], st["ledger"]
            rates.sort()
            return rates[len(rates) // 2]
        finally:
            c.shutdown()
            time.sleep(0.5)

    window_leg(WINDOW)  # untimed warm leg
    pipe = paired_legs(lambda: window_leg(1),
                       lambda: window_leg(WINDOW), reps=reps)

    def latency_leg(window: int) -> float:
        """Forward-latency p50 at LOW load: ONE small frame at a
        time, fully landed before the next, idle gaps in between —
        the regime where the worker's flush-on-drain acks each
        frame immediately (channel empty after the admit) and the
        window must not cost latency over the sync baseline."""
        c, db = build(1, "process", cluster_forward_window=window)
        try:
            def accounted():
                return c.ledger()["per-node-accounted"]

            done = 0
            for _ in range(192):
                c.submit(batch(64, db.id))
                done += 64
                t0 = time.perf_counter()
                while accounted() < done:
                    if time.perf_counter() - t0 > 60:
                        raise TimeoutError("latency leg stalled")
                    time.sleep(0.0005)
                time.sleep(0.002)  # low load: idle gap per frame
            st = c.stop()
            assert st["ledger"]["exact"], st["ledger"]
            lat = (st["cluster"]["router"]
                   or {})["forward-latency-us"]
            return float(lat["p50"])
        finally:
            c.shutdown()
            time.sleep(0.5)

    lat_sync = latency_leg(1)
    lat_pipe = latency_leg(WINDOW)

    def sigkill_mid_window_rep() -> dict:
        """SIGKILL a worker with the credit window OPEN — frames
        sent-but-unacked at the corpse.  The last cumulative ack is
        the final word; everything past it requeues to the failover
        peer or lands ``crash_dropped``, and the ledger closes
        EXACTLY — the property test's claim against a real corpse
        under real load."""
        c, db = build(2, "process")
        try:
            c.submit(batch(BUCKET, db.id))
            t0 = time.perf_counter()
            while c.ledger()["per-node-accounted"] < BUCKET:
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("mid-window warm stalled")
                time.sleep(0.002)
            c.snapshot_now()  # parent-retained replica per worker
            # open the window: a burst of small frames, then the
            # kill lands while they are in flight
            for _ in range(64):
                c.submit(batch(FRAME, db.id))
            win = (c.router.snapshot().get("window") or {})
            inflight_at_kill = win.get("inflight-frames", 0)
            c.node("node1").proc.kill()  # raw SIGKILL mid-window
            while not c.membership.is_dead("node1"):
                c.submit(batch(FRAME, db.id))
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("death never detected")
                time.sleep(0.002)
            while c.failovers_total() < 1:
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("failover never completed")
                time.sleep(0.002)
            c.submit(batch(BUCKET, db.id))  # survivor keeps serving
            st = c.stop()
            assert st["ledger"]["exact"], st["ledger"]
            return {
                "inflight_frames_at_kill": inflight_at_kill,
                "crash_dropped": st["ledger"]["crash-dropped"],
                "failover_dropped":
                    st["ledger"]["failover-dropped"],
                "ledger_exact": st["ledger"]["exact"],
            }
        finally:
            c.shutdown()

    skw = [sigkill_mid_window_rep() for _ in range(reps)]
    ledger_ok = ledger_ok and all(r["ledger_exact"] for r in skw)

    def scale_in_leg() -> dict:
        """remove_node() on a live 3-worker cluster under
        established flows: quiesce (window drained), victim CT
        migrated onto survivors, slots re-pinned, victim retired —
        with ZERO survivor recompiles and the ledger exact across
        the transition."""
        c, db = build(3, "process")
        try:
            c.submit(batch(BUCKET, db.id))
            t0 = time.perf_counter()
            while c.ledger()["per-node-accounted"] < BUCKET:
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("scale-in leg stalled")
                time.sleep(0.002)
            rec = c.remove_node()
            c.submit(batch(BUCKET, db.id))
            st = c.stop()
            assert st["ledger"]["exact"], st["ledger"]
            return {
                "pause_ms": rec["pause-ms"],
                "moved_slots": rec["moved-slots"],
                "ct_migrated_entries": rec["ct-migrated-entries"],
                "survivor_recompiles": rec["survivor-recompiles"],
                "ledger_exact": st["ledger"]["exact"],
            }
        finally:
            c.shutdown()

    si = scale_in_leg()
    ledger_ok = ledger_ok and si["ledger_exact"]

    # -- v4: the encrypted data channel (ISSUE 18) --------------------
    enc = paired_legs(lambda: window_leg(WINDOW, encrypt=False),
                      lambda: window_leg(WINDOW, encrypt=True),
                      reps=reps)

    def crypto_latency() -> tuple:
        """Per-op seal/open percentiles through the channel itself
        (no cluster in the loop): one bucket-sized packed wire
        buffer (BUCKET packets x 16 B), the unit the transport
        actually seals."""
        from cilium_tpu.encryption import (EncryptedChannel,
                                           NodeKeypair)

        a, b = NodeKeypair(), NodeKeypair()
        tx = EncryptedChannel(a, b.public)
        rx = EncryptedChannel(b, a.public)
        payload = np.ascontiguousarray(
            batch(BUCKET, 1)[:, :4]).tobytes()
        seal_ns, open_ns = [], []
        t_end = time.perf_counter() + 2.0  # time-boxed: the pure-
        # python fallback must not stall the phase
        for _ in range(512):
            t0 = time.perf_counter_ns()
            frame = tx.seal(payload)
            t1 = time.perf_counter_ns()
            rx.open(frame)
            t2 = time.perf_counter_ns()
            seal_ns.append(t1 - t0)
            open_ns.append(t2 - t1)
            if time.perf_counter() > t_end and len(seal_ns) >= 32:
                break

        def pct(v):
            v = sorted(v)
            return {"p50": round(v[len(v) // 2] / 1e3, 2),
                    "p90": round(v[(len(v) * 9) // 10] / 1e3, 2),
                    "p99": round(v[(len(v) * 99) // 100] / 1e3, 2),
                    "n": len(v),
                    "payload_bytes": len(payload)}

        return pct(seal_ns), pct(open_ns)

    seal_lat, open_lat = crypto_latency()

    def sigkill_mid_rotation_rep() -> dict:
        """SIGKILL one worker CONCURRENT with rotate_epoch on an
        encrypted 2-worker cluster with the window open: survivors
        carry the new epoch, the corpse's debt is counted, ledger
        exact (the chaos gate's claim as a shipped number)."""
        c, db = build(2, "process", cluster_encrypt=True)
        try:
            c.submit(batch(BUCKET, db.id))
            t0 = time.perf_counter()
            while c.ledger()["per-node-accounted"] < BUCKET:
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("mid-rotation warm stalled")
                time.sleep(0.002)
            c.snapshot_now()  # parent-retained replica per worker
            for _ in range(64):  # open the window
                c.submit(batch(FRAME, db.id))
            killer = _threading.Thread(
                target=lambda: (time.sleep(0.002),
                                c.node("node1").proc.kill()))
            killer.start()
            rot = c.rotate_epoch()  # races the kill: any
            # interleaving must land counted, never hung
            killer.join()
            while not c.membership.is_dead("node1"):
                c.submit(batch(FRAME, db.id))
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("death never detected")
                time.sleep(0.002)
            while c.failovers_total() < 1:
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("failover never completed")
                time.sleep(0.002)
            c.submit(batch(BUCKET, db.id))  # survivor at new epoch
            st = c.stop()
            assert st["ledger"]["exact"], st["ledger"]
            return {
                "epoch": rot["epoch"],
                "rotation_acked": rot["acked"],
                "rotation_failed": [f["node"]
                                    for f in rot.get("failed", ())],
                "crash_dropped": st["ledger"]["crash-dropped"],
                "crypto_dropped": st["ledger"]["crypto-dropped"],
                "failover_dropped":
                    st["ledger"]["failover-dropped"],
                "ledger_exact": st["ledger"]["exact"],
            }
        finally:
            c.shutdown()

    skr = [sigkill_mid_rotation_rep() for _ in range(reps)]
    ledger_ok = ledger_ok and all(r["ledger_exact"] for r in skr)

    proc = modes_out["process"]
    return {
        "schema": "bench-cluster-v4",
        "best_of": reps,
        "host_cores": _os.cpu_count(),
        "mode": "process",  # the headline curve below
        "sustained_pps_n1": proc["sustained_pps_n1"],
        "sustained_pps_n2": proc["sustained_pps_n2"],
        "sustained_pps_n3": proc["sustained_pps_n3"],
        "scaling_n2": proc["scaling_n2"],
        "scaling_n3": proc["scaling_n3"],
        "modes": modes_out,
        "forward_latency_us": fwd_latency.get("process"),
        "failover_blackout_ms": best["blackout_ms"],
        "failover_detect_ms": best["detect_ms"],
        "failover_ct_entries": best["ct_entries"],
        "failover_dropped": best["failover_dropped"],
        "failover_crash_dropped": best["crash_dropped"],
        "failover_mode": "process",
        "failover_reps": fo,
        "scale_out": so,
        # -- v3: the pipelined data channel (ISSUE 17) ----------------
        "forward_window": WINDOW,
        "pipelined_speedup": pipe["ratio_median"],
        "pipelined_speedup_pairs": pipe["pairs"],
        "pipelined_speedup_spread": pipe["spread"],
        "latency_p50_sync_us": lat_sync,
        "latency_p50_pipelined_us": lat_pipe,
        "latency_p50_ratio": (round(lat_pipe / lat_sync, 4)
                              if lat_sync else None),
        # headline rep: the one killed with the MOST frames in
        # flight — the deepest mid-window corpse the run produced
        "sigkill_mid_window": max(
            skw, key=lambda r: r["inflight_frames_at_kill"]),
        "sigkill_mid_window_reps": skw,
        "scale_in": si,
        # -- v4: the encrypted data channel (ISSUE 18) ----------------
        "encrypted_pps": enc["candidate_pps"],
        "plaintext_pps": enc["baseline_pps"],
        "encrypted_ratio": enc["ratio_median"],
        "encrypted_ratio_pairs": enc["pairs"],
        "encrypted_ratio_spread": enc["spread"],
        "seal_latency_us": seal_lat,
        "open_latency_us": open_lat,
        # headline rep: the one whose rotation saw a FAILED node —
        # the deepest kill/rotate interleaving the run produced
        "sigkill_mid_rotation": max(
            skr, key=lambda r: (len(r["rotation_failed"]),
                                r["crypto_dropped"])),
        "sigkill_mid_rotation_reps": skr,
        "ledger_exact": ledger_ok,
    }


def _run_cluster_phase() -> None:
    """--cluster: the clustermesh serving tier phase standalone (one
    JSON line).  Also writes BENCH_cluster.json next to this file —
    schema-checked by CTA008 (scripts/check_cluster_ledger.py);
    bounded under JAX_PLATFORMS=cpu."""
    import os

    out = bench_cluster()
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_cluster.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out))


def bench_obs(target_packets=1 << 20, reps=3) -> dict:
    """--obs: the cluster observability relay phase (ISSUE 14) ->
    BENCH_obs.json.

    One question, answered the paired-leg way: what does the relay
    COST?  Two legs on an identical 2-worker process cluster under
    the same backpressure-paced load, interleaved order-alternating
    (``paired_legs``):

    - OFF: ``cluster_obs_interval_s=0`` (no scrape loop),
      ``cluster_trace_sample=0`` (no trace context on the wire);
    - ON: a 0.25 s scrape cadence (every tick pulls each worker's
      registry exposition + flow tail + top-K + tracer + incidents
      over the control channel) AND 1-in-64 forwarded chunks carrying
      cross-process trace context.

    ``scrape_overhead_ratio`` is the PAIR-MEDIAN of on/off — the
    acceptance floor is >= 0.95.  What makes it hold structurally
    (not by luck) is the relay's scrape DUTY GOVERNOR
    (``obs/relay.SCRAPE_DUTY``, 2%): a worker answering
    ``obs_scrape`` spends its own core rendering the registry
    (including a device metricsmap fetch that waits out queued
    dispatches) / draining analytics / materializing the flow tail —
    ~0.2-0.4 s per sweep on this saturated 1-core box, and the RTT
    percentiles shipped here ARE that cost.  The loop therefore
    treats ``interval_s`` as a cadence CEILING and stretches its
    delay to keep sweep time under the duty fraction — the
    flow-analytics ``max_duty`` idiom one level up.  The timed
    window is sized to several seconds so it reads the governed
    steady state, not a single worst-case sweep: ungoverned 0.25 s
    cadence measured 0.72-0.77 on this box (that experiment is why
    the governor exists), governed runs clear the floor.

    v2 (ISSUE 19) adds two more numbers:

    - ``sampler_overhead_ratio``: a second paired-leg pair, single
      daemon under the same ingress overload, the SLO plane's
      sampler (history rings + burn evaluation, the `slo-sampler`
      thread) armed at an aggressive 0.25 s cadence vs off.  The
      same duty governor (``slo_max_duty``) defends this ratio: a
      tick's cost stretches the next delay, so sampling never
      claims more than the duty fraction of wall clock.
    - ``burn_detect_s``: detection latency of the shipped
      multi-window config for a seeded admission-shed burst, on a
      FAKE 10 s-tick timeline (deterministic — it characterizes the
      window math, not machine weather): fake seconds from the
      burst to the serving-availability SLO's page verdict."""
    import ipaddress

    from cilium_tpu.agent import DaemonConfig
    from cilium_tpu.cluster import ClusterServing
    from cilium_tpu.core.packets import (COL_DPORT, COL_DST_IP3,
                                         COL_EP, COL_FAMILY,
                                         COL_FLAGS, COL_LEN,
                                         COL_PROTO, COL_SPORT,
                                         COL_SRC_IP3, N_COLS, TCP_ACK)

    BUCKET = 2048
    rng = np.random.default_rng(14)
    src = int(ipaddress.IPv4Address("10.0.1.1"))
    dst = int(ipaddress.IPv4Address("10.0.2.1"))
    sports = (1024 + rng.permutation(50000)[:4096]).astype(np.uint32)

    def cfg(obs: bool):
        return DaemonConfig(
            backend="tpu", ct_capacity=1 << 14,
            flow_ring_capacity=1 << 13,
            serving_queue_depth=1 << 15,
            serving_bucket_ladder=(BUCKET,),
            serving_max_wait_us=1000.0,
            serving_restart_backoff_ms=1.0,
            cluster_forward_depth=1 << 15,
            cluster_probe_interval_s=0.25,
            cluster_death_threshold=2,
            cluster_mode="process",
            cluster_obs_interval_s=0.25 if obs else 0.0,
            cluster_trace_sample=64 if obs else 0)

    def batch(n, db_id):
        rows = np.zeros((n, N_COLS), dtype=np.uint32)
        rows[:, COL_SRC_IP3] = src
        rows[:, COL_DST_IP3] = dst
        rows[:, COL_SPORT] = rng.choice(sports, n)
        rows[:, COL_DPORT] = 5432
        rows[:, COL_PROTO] = 6
        rows[:, COL_FLAGS] = TCP_ACK
        rows[:, COL_LEN] = 512
        rows[:, COL_FAMILY] = 4
        rows[:, COL_EP] = db_id
        return rows

    RULES = [{
        "endpointSelector": {"matchLabels": {"app": "db"}},
        "ingress": [{"fromEndpoints": [
            {"matchLabels": {"app": "web"}}],
            "toPorts": [{"ports": [{"port": "5432",
                                    "protocol": "TCP"}]}]}],
    }]
    extras = {"ledger_exact": True}

    def leg(obs: bool):
        c = ClusterServing(nodes=2, config=cfg(obs))
        try:
            c.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
            db = c.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
            rev = c.policy_import(RULES)
            assert c.wait_policy(rev, timeout=30)
            c.start(trace_sample=0, packed=True,
                    ring_capacity=1 << 15)
            chunks = [batch(BUCKET, db.id) for _ in range(8)]

            def accounted():
                return c.ledger()["per-node-accounted"]

            for i in range(4):  # settle wave, untimed
                c.submit(chunks[i])
            t0 = time.perf_counter()
            while accounted() < 4 * BUCKET:
                if time.perf_counter() - t0 > 120:
                    raise TimeoutError("obs settle wave stalled")
                time.sleep(0.002)
            base = accounted()
            admitted = i = 0
            t0 = time.perf_counter()
            while admitted < target_packets:
                got = c.submit(chunks[i % len(chunks)])
                admitted += got
                i += 1
                if got < BUCKET:
                    time.sleep(0.0005)
            while accounted() - base < admitted:
                if time.perf_counter() - t0 > 300:
                    raise TimeoutError("obs leg stalled")
                time.sleep(0.002)
            dt = time.perf_counter() - t0
            st = c.stop()
            extras["ledger_exact"] = (extras["ledger_exact"]
                                      and st["ledger"]["exact"])
            if obs:
                ob = st.get("obs") or {}
                extras["obs"] = {
                    "scrapes": ob.get("scrapes"),
                    "scrape_errors": ob.get("scrape-errors"),
                    "rtt_us": ob.get("rtt-us"),
                    "spans": (ob.get("spans") or {}),
                }
            return admitted / dt
        finally:
            c.shutdown()
            time.sleep(0.5)

    leg(False)  # untimed warm leg (executable/thread steady state)
    pair = paired_legs(lambda: leg(False), lambda: leg(True),
                       reps=reps)

    # ---- sampler tax (ISSUE 19): ONE daemon, same ingress overload
    # loop, the history+SLO sampler armed at a 0.25 s cadence vs
    # stopped — paired order-alternating like the relay legs.  One
    # daemon (not one per leg) so both legs share executables and
    # thread steady state; only the `slo-sampler` thread differs.
    from cilium_tpu.agent import Daemon

    s_target = max(target_packets // 4, 64 * BUCKET)
    d = Daemon(DaemonConfig(
        backend="tpu", ct_capacity=1 << 14,
        flow_ring_capacity=1 << 13,
        serving_queue_depth=1 << 15,
        serving_bucket_ladder=(BUCKET,),
        serving_max_wait_us=1000.0,
        history_interval=0.25))
    try:
        d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db_l = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        d.policy_import(RULES)
        # warm occupancy sample (the daemon.start idiom): the armed
        # sampler reads the occupancy gauges, and their executable
        # must compile before any timed serving window
        d.pressure.sample()
        chunks = [batch(BUCKET, db_l.id) for _ in range(8)]

        def sampler_leg(armed: bool):
            if armed:
                d.slo.start()
            else:
                d.slo.stop()
            d.start_serving(ring_capacity=1 << 15, trace_sample=0,
                            ingress=True, packed=True)
            admitted = i = 0
            t0 = time.perf_counter()
            while admitted < s_target:
                got = d.submit(chunks[i % len(chunks)])
                admitted += got
                i += 1
                if got < BUCKET:
                    time.sleep(0.0005)  # queue full: backpressure
            stats = d.stop_serving()  # drains everything admitted
            dt = time.perf_counter() - t0
            return stats["front-end"]["verdicts"] / dt

        sampler_leg(False)  # untimed warm (compiles + steady state)
        spair = paired_legs(lambda: sampler_leg(False),
                            lambda: sampler_leg(True), reps=reps)
        sampler_ticks = d.slo.ticks
    finally:
        d.slo.stop()
        d.shutdown()

    burn_detect_s = _obs_burn_detect(batch, RULES, BUCKET)
    ob = extras.get("obs") or {}
    return {
        "schema": "bench-obs-v2",
        "best_of": reps,
        "sustained_pps_noobs": pair["baseline_pps"],
        "sustained_pps_obs": pair["candidate_pps"],
        "scrape_overhead_ratio": pair["ratio_median"],
        "scrape_overhead_pairs": pair["pairs"],
        "scrape_overhead_spread": pair["spread"],
        "scrape_rtt_us": ob.get("rtt_us"),
        "scrapes_total": ob.get("scrapes"),
        "scrape_errors": ob.get("scrape_errors"),
        "stitched_spans": (ob.get("spans") or {}).get("committed"),
        "spans_dropped": (ob.get("spans") or {}).get("dropped"),
        "ledger_exact": extras["ledger_exact"],
        "sampler_overhead_ratio": spair["ratio_median"],
        "sampler_overhead_pairs": spair["pairs"],
        "sampler_overhead_spread": spair["spread"],
        "sampler_pps_off": spair["baseline_pps"],
        "sampler_pps_armed": spair["candidate_pps"],
        "sampler_ticks": sampler_ticks,
        "burn_detect_s": burn_detect_s,
    }


def _obs_burn_detect(batch, rules, bucket) -> float:
    """``burn_detect_s``: fake seconds from a seeded admission-shed
    burst to the serving-availability SLO's page verdict, at the
    shipped multi-window config on a 10 s tick cadence.

    Deterministic by construction: the engine's clocks are
    injectable, so the timeline is fake (the number characterizes
    the burn-rate window math, not this box), while the COUNTERS are
    real — a healthy baseline covers the slow window, then a burst
    overflows the admission queue and the real shed ledger (exact,
    flushed by the drain thread) is what burns the budget."""
    from cilium_tpu.agent import Daemon, DaemonConfig

    d = Daemon(DaemonConfig(
        backend="tpu", ct_capacity=1 << 14,
        flow_ring_capacity=1 << 13,
        serving_queue_depth=1 << 15,
        serving_bucket_ladder=(bucket,),
        serving_max_wait_us=1000.0,
        history_interval=0.0))  # no sampler thread: tick() driven
    try:
        d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        d.policy_import(rules)
        d.pressure.sample()  # occupancy executable, pre-session
        d.start_serving(ring_capacity=1 << 15, trace_sample=0,
                        ingress=True, packed=True)
        step, t, w0 = 10.0, 0.0, 1.7e9
        # healthy baseline covering the slow window: 256-row chunks
        # can never overflow the 2^15 queue even undrained, so the
        # burst below is the FIRST shed the ledger ever sees
        rows = batch(256, db.id)
        for _ in range(int(d.config.slo_slow_window / step) + 1):
            d.submit(rows.copy())
            d.slo.tick(now=t, wall=w0 + t)
            t += step
        ev = d.slo.last["evals"]["serving-availability"]
        assert ev["state"] == "ok", ev
        t_burst = t
        burst = [batch(bucket, db.id) for _ in range(8)]
        shed = 0
        for i in range(64):
            shed += bucket - d.submit(burst[i % len(burst)].copy())
        assert shed > 0, "burst never overflowed admission"
        # the exact shed ledger flushes on drain activity — wait for
        # the registry (what the sampler reads) to surface all of it
        t0 = time.perf_counter()
        while (d.registry.sample(("cilium_serving_shed_total",))
               .get("cilium_serving_shed_total", 0)) < shed:
            if time.perf_counter() - t0 > 120:
                raise TimeoutError("shed ledger never surfaced")
            time.sleep(0.002)
        detect = None
        for _ in range(60):
            t += step
            out = d.slo.tick(now=t, wall=w0 + t)
            if (out["evals"]["serving-availability"]["state"]
                    == "page"):
                detect = t - t_burst
                break
        assert detect is not None, "seeded burst never paged"
        d.stop_serving()
        return detect
    finally:
        d.shutdown()


def _run_obs_phase() -> None:
    """--obs: the cluster observability relay phase standalone (one
    JSON line).  Also writes BENCH_obs.json next to this file —
    schema-checked by CTA014 (analysis/slo_lint.check_bench);
    bounded under JAX_PLATFORMS=cpu."""
    import os

    out = bench_obs()
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_obs.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out))


def _last_json_line(cmd, timeout: int) -> dict:
    """Run ``cmd`` in a fresh process and parse the JSON object on the
    last line of its output.  A non-zero exit, a timeout or an
    unparsable line becomes ``{"error": ...}``, which fails the
    bench (see :func:`main`)."""
    import subprocess

    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"error": f"timed out after {timeout}s"}
    if proc.returncode != 0:
        return {"error": f"exit {proc.returncode}: "
                         f"{proc.stderr.strip()[-300:]}"}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError) as e:
        return {"error": f"no JSON result line: {e}"[:300]}


def bench_anomaly() -> dict:
    """BASELINE eval config #5 (``cilium_tpu.ml.evaluate``) in its own
    process."""
    import sys

    return _last_json_line([sys.executable, "-m", "cilium_tpu.ml.evaluate"],
                           timeout=1800)


def _phase_subprocess(flag: str, timeout: int = 1800) -> dict:
    """Run one bench phase in its own process.  The phases run one
    after another and this parent never touches JAX, so each phase is
    the one process that holds the chip while it runs."""
    import sys

    return _last_json_line([sys.executable, __file__, flag], timeout)


def _run_device_phase() -> None:
    """--device: the fused-pipeline headline phase standalone (one
    JSON line).  Ends with the process's single d2h fetch (occupancy
    scalar), which pays the whole phase's queued-dispatch toll —
    bounded here instead of compounding into the e2e phase."""
    import jax.numpy as jnp

    from cilium_tpu.datapath import datapath_step_jit
    from cilium_tpu.testing.fixtures import build_world

    world = build_world(n_identities=10_000, ct_capacity=1 << 21,
                        n_v6=256)
    dev_pps, state, _now, detail = bench_device(world, jnp,
                                                datapath_step_jit)
    detail["ct_occupied"] = int(np.asarray(detail.pop("ct_occupied_dev")))
    print(json.dumps({"pps": round(dev_pps), "detail": detail}))


def _run_e2e_phase() -> None:
    """--e2e: the packed ingest end-to-end phase standalone (one JSON
    line).  Fresh process = fresh CT (its pool warmup establishes the
    steady state); r04 ran it after the device phase in-process, so
    its CT carried ~1M background entries — the fresh-process number
    has slightly lighter probe pressure (noted in the output)."""
    import jax
    import jax.numpy as jnp

    from cilium_tpu.datapath import datapath_step_jit
    from cilium_tpu.testing.fixtures import build_world

    world = build_world(n_identities=10_000, ct_capacity=1 << 21,
                        n_v6=256)
    out, _state = bench_end_to_end(world, world.state, 1_001, jax,
                                   jnp, datapath_step_jit)
    out["fresh_process"] = True
    print(json.dumps(out))


def _run_wide_phase() -> None:
    """--wide: the wide-path phase standalone (one JSON line)."""
    import jax
    import jax.numpy as jnp

    from cilium_tpu.testing.fixtures import build_world

    world = build_world(n_identities=10_000, ct_capacity=1 << 21,
                        n_v6=256)
    out, _state = bench_end_to_end_wide(world, world.state, 1_000,
                                        jax, jnp)
    print(json.dumps(out))


def _run_ring_phase() -> None:
    """--ring: the steady-drain phase standalone (one JSON line)."""
    import jax
    import jax.numpy as jnp

    from cilium_tpu.testing.fixtures import build_world

    world = build_world(n_identities=10_000, ct_capacity=1 << 21)
    out, _state = bench_ring_steady_state(world, world.state, 1_000,
                                          jax, jnp)
    print(json.dumps(out))


PHASES = {
    "device": lambda: _phase_subprocess("--device", timeout=2100),
    "end_to_end": lambda: _phase_subprocess("--e2e", timeout=2100),
    "end_to_end_wide": lambda: _phase_subprocess("--wide"),
    "ring_steady_state": lambda: _phase_subprocess("--ring"),
    "socket_lb": lambda: _phase_subprocess("--socklb"),
    "serving": lambda: _phase_subprocess("--serving"),
    "recovery": lambda: _phase_subprocess("--recovery"),
    "cluster": lambda: _phase_subprocess("--cluster"),
    "obs": lambda: _phase_subprocess("--obs"),
    "churn": lambda: _phase_subprocess("--churn"),
    "scenarios": lambda: _phase_subprocess("--scenarios"),
    "l7": lambda: _phase_subprocess("--l7"),
    "encryption": bench_encryption,
    "anomaly": bench_anomaly,
}


def main(phases=None) -> int:
    """Run every phase, print one JSON line, and return the exit code:
    non-zero when any phase returned an error."""
    results = {name: fn() for name, fn in (phases or PHASES).items()}
    failed = sorted(name for name, r in results.items()
                    if isinstance(r, dict) and "error" in r)
    device = results.get("device", {})
    dev_pps = device.get("pps", 0) or 0
    anomaly = results.get("anomaly", {})
    print(json.dumps({
        "metric": "policy_verdicts_per_sec_per_chip",
        "value": round(dev_pps),
        "unit": "verdicts/s",
        "vs_baseline": round(dev_pps / BASELINE_PPS, 3),
        "device_detail": device.get("detail", device),
        **{k: v for k, v in results.items() if k != "device"},
        "anomaly_auc": anomaly.get("value"),
        "failed_phases": failed,
    }))
    return 1 if failed else 0


if __name__ == "__main__":
    import sys

    from cilium_tpu.infra.jaxcache import enable_compile_cache

    enable_compile_cache()
    if "--device" in sys.argv:
        _run_device_phase()
    elif "--e2e" in sys.argv:
        _run_e2e_phase()
    elif "--wide" in sys.argv:
        _run_wide_phase()
    elif "--ring" in sys.argv:
        _run_ring_phase()
    elif "--socklb" in sys.argv:
        _run_socklb_phase()
    elif "--serving" in sys.argv:
        _run_serving_phase()
    elif "--recovery" in sys.argv:
        _run_recovery_phase()
    elif "--cluster" in sys.argv:
        _run_cluster_phase()
    elif "--obs" in sys.argv:
        _run_obs_phase()
    elif "--churn" in sys.argv:
        _run_churn_phase()
    elif "--scenarios" in sys.argv:
        _run_scenarios_phase()
    elif "--l7" in sys.argv:
        _run_l7_phase()
    else:
        sys.exit(main())
