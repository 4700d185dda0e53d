"""Observability: sampled per-packet trace spans (cilium_tpu/obs).

Covers the PR-4 tentpole acceptance properties:

- DETERMINISM: same seed + same packet stream => the identical
  sampled-trace set (the replayable-chaos property, applied to
  tracing);
- CORRECTNESS: seven stage timestamps monotonic (PR 5 split the old
  ``device`` stamp into ``dispatch-ret`` + true window-join
  ``device``), the six stage intervals telescope to the recorded
  end-to-end latency (sum <= e2e, within 10%);
- ZERO OVERHEAD OFF: sampling disabled leaves no tracer object in
  the pipeline — the hot path pays one ``is not None`` branch;
- NO SILENT LOSS: spans whose packet dies mid-pipeline (drop-oldest
  eviction, contained dispatch failures, recovery sweeps) are
  counted dropped, never stuck incomplete;
- the chaos e2e: a demotion-crossing trace is retrievable with its
  ``demoted`` annotation, and the compile-event log holds the
  one-executable-per-(rung, mode) invariant across the ladder walk.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from cilium_tpu.agent import Daemon, DaemonConfig
from cilium_tpu.core import TCP_SYN, make_batch
from cilium_tpu.obs import SpanTracer
from cilium_tpu.obs.trace import (PROFILE_SPANS, SPAN_STAGES,
                                  validate_obs_config)
from cilium_tpu.serving import DispatchFailedError, ServingRuntime

pytestmark = pytest.mark.obs

COLS = 16


def _chunks(rng, n_chunks=12, lo=20, hi=120):
    sizes = rng.integers(lo, hi, size=n_chunks)
    return [np.full((s, COLS), i, dtype=np.uint32)
            for i, s in enumerate(sizes)]


def _run_stream(chunks, sample, seed=0, dispatch=None):
    """One runtime session over ``chunks``; returns the tracer."""
    tracer = SpanTracer(sample, seed=seed, capacity=1024)
    if dispatch is None:
        def dispatch(hdr, valid, n_valid, **kw):
            return {"h2d_bytes": hdr.nbytes, "mode": "wide",
                    "batch_id": 3}
    rt = ServingRuntime(dispatch, queue_depth=1 << 14,
                        bucket_ladder=(64, 256),
                        max_wait_us=300.0, expected_cols=COLS,
                        tracer=tracer)
    rt.start()
    for c in chunks:
        rt.submit(c)
        time.sleep(0.002)
    rt.stop()
    return rt, tracer


class TestSamplingDeterminism:
    def test_same_seed_same_stream_identical_sampled_set(self):
        rng = np.random.default_rng(11)
        chunks = _chunks(rng)
        _, tr_a = _run_stream(chunks, sample=7, seed=3)
        _, tr_b = _run_stream(chunks, sample=7, seed=3)
        seqs_a = sorted(t["seq"] for t in tr_a.snapshot(1024)["traces"])
        seqs_b = sorted(t["seq"] for t in tr_b.snapshot(1024)["traces"])
        assert seqs_a and seqs_a == seqs_b
        # and the set is exactly the arithmetic progression over the
        # admitted sequence: (seq + seed) % sample == 0
        total = sum(len(c) for c in chunks)
        assert seqs_a == [s for s in range(total) if (s + 3) % 7 == 0]

    def test_seed_shifts_the_sampled_set(self):
        rng = np.random.default_rng(12)
        chunks = _chunks(rng)
        _, tr_a = _run_stream(chunks, sample=7, seed=0)
        _, tr_b = _run_stream(chunks, sample=7, seed=1)
        a = {t["seq"] for t in tr_a.snapshot(1024)["traces"]}
        b = {t["seq"] for t in tr_b.snapshot(1024)["traces"]}
        assert a and b and a.isdisjoint(b)

    def test_spans_monotonic_and_stage_sum_telescopes(self):
        rng = np.random.default_rng(13)
        _, tracer = _run_stream(_chunks(rng), sample=5)
        traces = tracer.snapshot(1024)["traces"]
        assert traces
        for t in traces:
            ts = t["timestamps"]
            assert len(ts) == len(SPAN_STAGES) == 7
            assert all(ts[i + 1] >= ts[i] for i in range(6)), t
            assert t["monotonic"]
            stage_sum = sum(t["stages-us"].values())
            # the intervals telescope: their sum IS the end-to-end
            # latency (well within the 10% acceptance bound)
            assert stage_sum <= t["e2e-us"] + 1e-3
            assert abs(stage_sum - t["e2e-us"]) <= 0.1 * t["e2e-us"] \
                + 1e-3
        # no span leaked: every started span completed or was counted
        st = tracer.stats()
        assert st["started"] == st["completed"] + st["dropped"]
        assert st["dropped"] == 0

    def test_disabled_sampling_is_structurally_free(self):
        """sample=0 => NO tracer object anywhere in the pipeline:
        the hot path's entire cost is `queue.tracer is None` (the
        bench guard measures the residue; this pins the structure)."""
        def dispatch(hdr, valid, n_valid, **kw):
            return None

        rt = ServingRuntime(dispatch, queue_depth=4096,
                            bucket_ladder=(64,), max_wait_us=300.0,
                            expected_cols=COLS)
        rt.start()
        rt.submit(np.zeros((64, COLS), dtype=np.uint32))
        time.sleep(0.05)
        snap = rt.stop()
        assert rt.queue.tracer is None
        assert rt._tracer is None
        assert "trace" not in snap
        assert rt._prev_spans == ()

    def test_drop_oldest_eviction_counts_spans(self):
        """Spans shed by drop-oldest (or swept by stop) are counted
        dropped — started always reconciles."""
        tracer = SpanTracer(2, capacity=256)
        blocked = []

        def dispatch(hdr, valid, n_valid, **kw):
            blocked.append(n_valid)
            time.sleep(0.05)  # slow consumer: the queue overflows
            return None

        rt = ServingRuntime(dispatch, queue_depth=128,
                            bucket_ladder=(128,), max_wait_us=100.0,
                            overflow_policy="drop-oldest",
                            expected_cols=COLS, tracer=tracer)
        rt.start()
        for _ in range(40):
            rt.submit(np.zeros((64, COLS), dtype=np.uint32))
        rt.stop()
        st = tracer.stats()
        assert st["started"] == st["completed"] + st["dropped"]
        assert st["dropped"] > 0  # overflow definitely evicted spans

    def test_contained_dispatch_failure_drops_spans(self):
        """A DispatchFailedError batch becomes recovery drops; its
        spans are counted dropped, not leaked incomplete."""
        tracer = SpanTracer(4, capacity=256)
        calls = []

        def dispatch(hdr, valid, n_valid, **kw):
            calls.append(n_valid)
            if len(calls) == 1:
                raise DispatchFailedError("contained")
            return None

        rt = ServingRuntime(dispatch, queue_depth=4096,
                            bucket_ladder=(64,), max_wait_us=200.0,
                            expected_cols=COLS, tracer=tracer)
        rt.start()
        rt.submit(np.zeros((64, COLS), dtype=np.uint32))
        time.sleep(0.1)
        rt.submit(np.zeros((64, COLS), dtype=np.uint32))
        time.sleep(0.1)
        snap = rt.stop()
        st = tracer.stats()
        assert snap["fault-tolerance"]["recovery-dropped"] == 64
        assert st["dropped"] >= 1
        assert st["started"] == st["completed"] + st["dropped"]

    def test_annotations_ride_the_span(self):
        rng = np.random.default_rng(14)
        _, tracer = _run_stream(_chunks(rng), sample=5)
        t = tracer.snapshot(4)["traces"][0]
        assert t["bucket"] in (64, 256)
        assert t["mode"] == "wide"
        assert t["batch-id"] == 3  # from the dispatch info dict
        assert 0 <= t["batch-pos"] < t["bucket"]

    def test_validate_obs_config_rejects_bad_knobs(self):
        with pytest.raises(ValueError, match="serving_trace_sample"):
            validate_obs_config(-1, None, 16)
        with pytest.raises(ValueError, match="profile_batches"):
            validate_obs_config(0, "/tmp/x", 0)
        assert validate_obs_config(64, None, 16) == (64, None, 16)

    def test_span_sample_requires_ingress(self):
        d = Daemon(DaemonConfig(backend="tpu", ct_capacity=1 << 10,
                                flow_ring_capacity=1 << 10))
        with pytest.raises(ValueError, match="ingress"):
            d.start_serving(trace_sample=0, span_sample=8)
        d.shutdown()


RULES = [{
    "endpointSelector": {"matchLabels": {"app": "db"}},
    "ingress": [{
        "fromEndpoints": [{"matchLabels": {"app": "web"}}],
        "toPorts": [{"ports": [{"port": "5432", "protocol": "TCP"}]}],
    }],
}]


def _fwd(db_id, n=64, base=20000):
    return make_batch([
        dict(src="10.0.1.1", dst="10.0.2.1", sport=base + i,
             dport=5432, proto=6, flags=TCP_SYN, ep=db_id, dir=0)
        for i in range(n)]).data


def _wait(pred, timeout=60.0, tick=0.002):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout:
            return False
        time.sleep(tick)
    return True


@pytest.mark.chaos
class TestTraceE2EDemotion:
    def test_trace_crosses_demotion_with_monotonic_stages(self):
        """THE acceptance e2e: serving_trace_sample=64 over a real
        tpu-backend session retrieves complete traces (seven
        monotonic stamps, stage-sum within 10% of e2e) INCLUDING one
        that
        crossed a single->wide ladder demotion (its batch was
        retried on the demoted rung, so the span carries
        demoted=True and the wide mode), and the compile-event log
        holds one executable per (rung, mode) over the walk.

        Same world/bucket as test_serving_faults so the XLA
        executables are shared across the suite."""
        d = Daemon(DaemonConfig(
            backend="tpu", ct_capacity=1 << 12,
            flow_ring_capacity=1 << 13,
            serving_queue_depth=4096,
            serving_bucket_ladder=(64,),
            serving_max_wait_us=500.0,
            serving_dispatch_deadline_ms=0.0,
            serving_restart_budget=4,
            serving_restart_backoff_ms=1.0,
            serving_demote_threshold=2,
            serving_promote_after=1000,
            serving_trace_sample=64,
            fault_injection="loader.serve_packed=1x2@1",
            fault_seed=1))
        d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        d.policy_import(RULES)
        d.start_serving(trace_sample=0, ingress=True, packed=True,
                        drain_every=2)
        rt = d._serving["runtime"]
        d.submit(_fwd(db.id))  # warm (packed)
        assert _wait(lambda: rt.stats.verdicts >= 64)
        d.submit(_fwd(db.id, base=21000))  # fault 1: contained drop
        assert _wait(lambda: rt.stats.recovery_dropped >= 64)
        d.submit(_fwd(db.id, base=22000))  # fault 2: demote + retry
        assert _wait(lambda: rt.stats.verdicts >= 128)
        assert d.serving_stats()["mode"] == "wide"
        # a few more batches so post-demotion traces complete
        d.submit(_fwd(db.id, base=23000))
        assert _wait(lambda: rt.stats.verdicts >= 192)
        # spans now complete ASYNCHRONOUSLY (the event-join worker
        # stamps device/join at true window-join time); the idle-tick
        # drain flushes the last window once traffic pauses, so wait
        # for the ledger to reconcile before snapshotting
        tracer = d._serving["tracer"]
        assert _wait(lambda: (lambda st:
                              st["started"] == st["completed"]
                              + st["dropped"])(tracer.stats()))
        tr = d.debug_traces(limit=256)
        assert tr["enabled"] and tr["sample"] == 64
        complete = tr["traces"]
        assert len(complete) >= 1
        for t in complete:
            assert t["monotonic"], t
            s = sum(t["stages-us"].values())
            assert s <= t["e2e-us"] + 1e-3
            assert abs(s - t["e2e-us"]) <= 0.1 * t["e2e-us"] + 1e-3
        # at least one trace CROSSED the demotion: retried on the
        # demoted rung, annotated demoted + wide
        crossed = [t for t in complete if t["demoted"]]
        assert crossed and all(t["mode"] == "wide" for t in crossed)
        # the span ledger reconciles: the faulted batch's spans are
        # dropped, everything else completed
        st = tr
        assert st["started"] == st["completed"] + st["dropped"]
        assert st["dropped"] >= 1  # the contained-failure batch
        # compile-event log: one executable per (rung, mode) over
        # the packed -> wide walk (events appear only for compiles
        # this process actually paid — a warm jit cache legitimately
        # records none; violations flag same-key regrowth either way)
        comp = tr["compile"]
        assert comp["violations"] == 0
        assert all(k["compiles"] == 1 for k in comp["by-key"])
        modes = {k["mode"] for k in comp["by-key"]}
        # "gather" = the occupancy-bounded ring-drain executables
        # (PR 5) — bucketed rungs under the same one-per-key guard
        assert modes <= {"packed", "wide", "gather"}
        # prometheus: the obs series ride the unified registry
        prom = d.registry.render()
        assert "cilium_obs_spans_completed_total" in prom
        assert "cilium_serving_compile_violations_total 0" in prom
        assert "cilium_serving_latency_us_bucket" in prom
        fe = d.stop_serving()["front-end"]
        ft = fe["fault-tolerance"]
        assert fe["submitted"] == (fe["verdicts"] + fe["shed"]
                                   + ft["recovery-dropped"])
        d.shutdown()


@pytest.mark.chaos
class TestTraceShardAttribution:
    def test_sharded_spans_carry_owning_shard(self):
        """Sharded dispatch annotates each span with the chip its
        packet was flow-routed to (routed position // block — the
        same mapping the router's orig index encodes), so a slow
        trace is attributable to a shard.  Distinct flows spread, so
        the sampled set must cover more than one shard."""
        from cilium_tpu.parallel import make_mesh

        d = Daemon(DaemonConfig(
            backend="tpu", ct_capacity=1 << 12,
            flow_ring_capacity=1 << 13,
            serving_queue_depth=4096,
            serving_bucket_ladder=(64,),
            serving_max_wait_us=500.0,
            serving_trace_sample=4))
        d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        d.policy_import(RULES)
        d.start_serving(trace_sample=0, ingress=True,
                        mesh=make_mesh(8), drain_every=2)
        rt = d._serving["runtime"]
        for k in range(3):
            d.submit(_fwd(db.id, base=20000 + 100 * k))
        assert _wait(lambda: rt.stats.verdicts >= 192)
        tr = d.debug_traces(limit=64)
        traces = tr["traces"]
        assert traces
        assert all(t["mode"].startswith("sharded") for t in traces)
        shards = {t["shard"] for t in traces}
        assert all(0 <= s < 8 for s in shards), shards
        assert len(shards) > 1, "spans should span multiple shards"
        d.stop_serving()
        d.shutdown()

    def test_route_overflow_spans_dropped_not_completed(self):
        """A sampled packet the router drops (full shard block) must
        land in the tracer's DROPPED count, never as a completed
        trace — a committed span would report a fake e2e latency for
        a packet the device never verdicted."""
        from cilium_tpu.parallel import make_mesh

        d = Daemon(DaemonConfig(
            backend="tpu", ct_capacity=1 << 12,
            flow_ring_capacity=1 << 13,
            serving_queue_depth=4096,
            serving_bucket_ladder=(64,),
            serving_max_wait_us=500.0,
            serving_trace_sample=1))  # sample EVERY packet
        d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        d.policy_import(RULES)
        # headroom 1 + one elephant flow: all 64 rows route to ONE
        # shard whose block is 64/8 = 8 rows -> 56 deterministic
        # router drops (the test_serving_sharded overflow scenario)
        d.start_serving(trace_sample=0, ingress=True,
                        mesh=make_mesh(8), shard_headroom=1,
                        drain_every=2)
        rt = d._serving["runtime"]
        elephant = make_batch([
            dict(src="10.0.1.1", dst="10.0.2.1", sport=7777,
                 dport=5432, proto=6, flags=TCP_SYN, ep=db.id,
                 dir=0)] * 64).data
        d.submit(elephant)
        tracer = d._serving["tracer"]
        assert _wait(lambda: tracer.stats()["completed"]
                     + tracer.stats()["dropped"] >= 64)
        st = tracer.stats()
        assert st["started"] == 64
        assert st["dropped"] == 56, st
        assert st["completed"] == 8, st
        tr = d.debug_traces(limit=64)
        assert all(t["shard"] >= 0 for t in tr["traces"])
        d.stop_serving()
        d.shutdown()


class TestAssemblyFailureEviction:
    def test_spans_evicted_when_staging_raises(self):
        """Spans claimed by take_into are evicted if batch assembly
        dies before the batch exists — a drain-loop restart must not
        pop them into (and corrupt) a later batch, and the ledger
        stays exact."""
        from cilium_tpu.serving.batcher import AdaptiveBatcher
        from cilium_tpu.serving.ingress import IngressQueue

        tracer = SpanTracer(1, seed=0)
        q = IngressQueue(1 << 10)
        q.tracer = tracer
        q.offer(np.zeros((8, COLS), dtype=np.uint32))
        b = AdaptiveBatcher((64,), max_wait_us=0.0)
        boom = RuntimeError("arena died")

        class ExplodingArena:
            def slot(self, *a, **kw):
                raise boom

        b.arena = ExplodingArena()
        with pytest.raises(RuntimeError):
            b.assemble(q, force=True)
        st = tracer.stats()
        assert st["started"] == 8
        assert st["dropped"] == 8, st
        assert q.pop_dequeued_spans() == []  # nothing orphaned


class _SpanLog:
    """Stands in for ``jax.profiler.TraceAnnotation`` while a trace is
    "active": records (name, batch id, thread) of every span opened."""

    def __init__(self, monkeypatch):
        from cilium_tpu.obs import trace

        self.spans = []
        log = self.spans

        class Recorded:
            def __init__(self, name, batch):
                self.entry = (name, batch)

            def __enter__(self):
                log.append(self.entry + (threading.get_ident(),))
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(trace, "_trace_enabled", lambda: True)
        monkeypatch.setattr(trace, "_annotation", Recorded)

    def ids(self, name):
        return [b for n, b, _ in self.spans if n == name]


class TestProfileSpans:
    """Per-batch profiler spans (obs.trace.PROFILE_SPANS): one set per
    batch under the batch's ring id, never one per row, and no
    annotation built while no trace is active."""

    def test_off_returns_shared_noop_without_building_a_span(
            self, monkeypatch):
        from cilium_tpu.obs import trace

        def boom(*a, **kw):
            raise AssertionError("annotation built with no trace on")

        monkeypatch.setattr(trace, "_annotation", boom)
        assert not trace._trace_enabled()
        assert trace.profile_span("h2d", 3) is trace._NO_SPAN
        with trace.profile_span("dispatch", 3):
            pass

    def test_each_batch_spans_share_its_batch_id(self, monkeypatch):
        log = _SpanLog(monkeypatch)
        d = Daemon(DaemonConfig(
            backend="tpu", ct_capacity=1 << 12,
            flow_ring_capacity=1 << 13,
            serving_queue_depth=4096,
            serving_bucket_ladder=(64,),
            serving_max_wait_us=500.0,
            # room for every window, so none is dropped while the
            # worker's first gather compiles
            serving_window_queue_depth=16))
        d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        d.policy_import(RULES)
        d.start_serving(trace_sample=0, ingress=True, drain_every=2)
        rt = d._serving["runtime"]
        # eight full buckets in one chunk: back-to-back dispatches
        d.submit(np.concatenate([_fwd(db.id, base=30000 + 64 * i)
                                 for i in range(8)]))
        assert _wait(lambda: rt.stats.verdicts >= 8 * 64)
        d.stop_serving()
        d.shutdown()

        batches = list(range(8))
        # one h2d / lock_wait / dispatch per batch, not per row
        for name in ("h2d", "lock_wait", "dispatch"):
            assert log.ids(name) == batches, name
        assert set(batches) <= set(log.ids("assemble"))
        # the drain tick every drain_every batches, before the
        # dispatch of the batch whose id it carries
        assert {2, 4, 6} <= set(log.ids("drain_tick"))
        drain = {t for n, _, t in log.spans if n == "dispatch"}
        assert len(drain) == 1
        for n, b, t in log.spans:
            if n in ("assemble", "h2d", "lock_wait", "dispatch"):
                assert t in drain, (n, b)
        # each swapped window is joined on the event-join worker under
        # the id of the drain tick that swapped it
        worker = {t for n, _, t in log.spans if n == "fetch"}
        assert worker and not worker & drain
        for name in ("fetch", "emit", "analytics"):
            assert set(log.ids(name)) == set(log.ids("drain_tick")), name
        assert {n for n, _, _ in log.spans} <= set(PROFILE_SPANS)

    def test_assembly_carries_the_id_the_next_dispatch_returns(
            self, monkeypatch):
        """A bare runtime learns the ring's batch ids from what its
        dispatcher returns, so a batch's assembly is tagged with the id
        its dispatch then gets (ids wrap at the ring's 13-bit field)."""
        log = _SpanLog(monkeypatch)
        seq = iter(range(8190, 8200))

        def dispatch(hdr, valid, n_valid, **kw):
            bid = next(seq) & 0x1FFF
            log.spans.append(("dispatched", bid, 0))
            return {"h2d_bytes": hdr.nbytes, "mode": "wide",
                    "batch_id": bid}

        rt = ServingRuntime(dispatch, queue_depth=4096,
                            bucket_ladder=(64,), max_wait_us=300.0,
                            expected_cols=COLS)
        rt.start()
        rt.submit(np.zeros((4 * 64, COLS), dtype=np.uint32))
        assert _wait(lambda: rt.stats.verdicts >= 4 * 64)
        rt.stop()
        tagged = []
        for i, (n, b, _) in enumerate(log.spans):
            if n == "dispatched":
                tagged.append((b, [x for x in log.spans[:i]
                                   if x[0] == "assemble"][-1][1]))
        assert [b for b, _ in tagged] == [8190, 8191, 0, 1]
        # the first assembly precedes any returned id
        assert all(b == a for b, a in tagged[1:]), tagged
