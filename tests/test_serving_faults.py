"""Chaos suite: the serving plane under injected faults (ISSUE 3).

Acceptance:
(a) an injected dispatch hang is detected within the configured
    deadline and the runtime recovers without operator action;
(b) sharded -> single-chip demotion preserves established CT flows
    (replies still pass);
(c) ``submitted == verdicts + shed + recovery_dropped`` holds EXACTLY
    under every fault schedule, with the drops visible as decoded
    events through monitor -> flow -> CLI.

Discipline: every schedule is SEEDED (infra/faults.py draws replay),
and no test sleeps longer than the watchdog deadline it exercises —
progress is observed by polling with a bounded budget.
"""

import json
import time

import numpy as np
import pytest

from cilium_tpu.agent import Daemon, DaemonConfig
from cilium_tpu.core import TCP_ACK, TCP_SYN, make_batch
from cilium_tpu.core.packets import COL_DIR, N_COLS
from cilium_tpu.datapath.verdict import (N_REASONS,
                                         REASON_DISPATCH_TIMEOUT,
                                         REASON_RECOVERY_DROP)
from cilium_tpu.flow.flow import DROP_REASON_DESC
from cilium_tpu.infra import faults
from cilium_tpu.monitor.api import (DROP_REASON_NAMES, MSG_DROP,
                                    DropNotify, materialize)
from cilium_tpu.serving import (DispatchFailedError, FallbackLadder,
                                IngressQueue, ServingError,
                                ServingRuntime,
                                validate_recovery_config)

pytestmark = pytest.mark.chaos

RULES = [{
    "endpointSelector": {"matchLabels": {"app": "db"}},
    "ingress": [{
        "fromEndpoints": [{"matchLabels": {"app": "web"}}],
        "toPorts": [{"ports": [{"port": "5432", "protocol": "TCP"}]}],
    }],
}]

# db egress-enforced: a db-sourced reply passes its egress hook ONLY
# via the CT reply fast path (same construction as the sharded
# flow-affinity proof in test_serving_sharded.py) — the CT-continuity
# oracle for demotion
RULES_EGRESS_ENFORCED = [{
    "endpointSelector": {"matchLabels": {"app": "db"}},
    "ingress": [{
        "fromEndpoints": [{"matchLabels": {"app": "web"}}],
        "toPorts": [{"ports": [{"port": "5432", "protocol": "TCP"}]}],
    }],
    "egress": [{
        "toEndpoints": [{"matchLabels": {"app": "db"}}],
        "toPorts": [{"ports": [{"port": "1", "protocol": "TCP"}]}],
    }],
}]


def _daemon(fault_spec=None, rules=RULES, **over):
    # ONE ladder rung: every distinct bucket is an XLA compile, and
    # this suite's job is fault schedules, not shape coverage
    cfg = dict(backend="tpu", ct_capacity=1 << 12,
               flow_ring_capacity=1 << 13,
               serving_queue_depth=4096,
               serving_bucket_ladder=(64,),
               serving_max_wait_us=500.0,
               serving_dispatch_deadline_ms=500.0,
               serving_restart_budget=4,
               serving_restart_backoff_ms=1.0,
               serving_demote_threshold=2,
               serving_promote_after=3,
               serving_promote_cooldown_s=0.05,
               fault_injection=fault_spec, fault_seed=1)
    cfg.update(over)
    d = Daemon(DaemonConfig(**cfg))
    d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
    db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
    d.policy_import(rules)
    return d, db


def _fwd(db_id, n=64, base=20000):
    return make_batch([
        dict(src="10.0.1.1", dst="10.0.2.1", sport=base + i,
             dport=5432, proto=6, flags=TCP_SYN, ep=db_id, dir=0)
        for i in range(n)]).data


def _rep(db_id, n=64, base=20000):
    return make_batch([
        dict(src="10.0.2.1", dst="10.0.1.1", sport=5432,
             dport=base + i, proto=6, flags=TCP_ACK, ep=db_id, dir=1)
        for i in range(n)]).data


def _wait(pred, timeout=30.0, tick=0.002):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout:
            return False
        time.sleep(tick)
    return True


def _assert_ledger(fe):
    ft = fe["fault-tolerance"]
    assert fe["submitted"] == (fe["verdicts"] + fe["shed"]
                               + ft["recovery-dropped"]), (
        f"ledger broken: {fe['submitted']} != {fe['verdicts']} + "
        f"{fe['shed']} + {ft['recovery-dropped']}")
    return ft


# ---------------------------------------------------------------------
class TestFaultFramework:
    def test_spec_parses_and_replays_deterministically(self):
        a = faults.FaultInjector("loader.serve=0.5", seed=9)
        b = faults.FaultInjector("loader.serve=0.5", seed=9)
        pattern = []
        for inj in (a, b):
            hits = []
            for _ in range(32):
                try:
                    inj.check("loader.serve")
                    hits.append(0)
                except faults.InjectedFault:
                    hits.append(1)
            pattern.append(hits)
        assert pattern[0] == pattern[1]
        assert 0 < sum(pattern[0]) < 32  # actually probabilistic

    def test_count_and_skip_limits(self):
        inj = faults.FaultInjector("serving.dispatch=1x2@1")
        inj.check("serving.dispatch")  # skipped (inert warmup pass)
        for _ in range(2):
            with pytest.raises(faults.InjectedFault):
                inj.check("serving.dispatch")
        inj.check("serving.dispatch")  # count exhausted: no-op
        assert inj.fired["serving.dispatch"] == 2

    def test_hang_sleeps_and_aborts(self):
        inj = faults.FaultInjector("serving.dispatch=1~0.08")
        t0 = time.monotonic()
        inj.check("serving.dispatch")
        assert time.monotonic() - t0 >= 0.07
        t0 = time.monotonic()
        inj.check("serving.dispatch", abort=lambda: True)
        assert time.monotonic() - t0 < 0.05  # cancelled stall

    def test_unknown_site_and_bad_entries_raise(self):
        with pytest.raises(ValueError, match="unknown fault site"):
            faults.FaultInjector("serving.disptach=1")
        with pytest.raises(ValueError, match="bad fault spec"):
            faults.FaultInjector("serving.dispatch")
        with pytest.raises(ValueError, match="not in"):
            faults.FaultInjector("serving.dispatch=1.5")

    def test_daemon_arms_validates_and_disarms(self):
        with pytest.raises(ValueError, match="unknown fault site"):
            _daemon(fault_spec="no.such.site=1")
        d, _db = _daemon(fault_spec="loader.serve=0x0")
        assert faults.active() is d._fault_injector
        d.shutdown()
        assert faults.active() is None

    def test_disarmed_check_is_a_noop(self):
        faults.disarm()
        faults.check("serving.dispatch")  # nothing armed: no-op

    def test_recovery_config_validation(self):
        with pytest.raises(ValueError, match="deadline"):
            validate_recovery_config(-1, 8, 10, 3, 64, 5.0)
        with pytest.raises(ValueError, match="budget"):
            validate_recovery_config(0, -1, 10, 3, 64, 5.0)
        with pytest.raises(ValueError, match="demote_threshold"):
            validate_recovery_config(0, 0, 0, 0, 64, 5.0)


# ---------------------------------------------------------------------
class TestQueueMemcpyAtomicity:
    def test_faulted_take_into_loses_nothing(self):
        """The dequeue memcpy site kills the consumer WITHOUT losing
        rows: nothing is popped until every copy landed, so the rows
        are still queued for the restarted drain thread."""
        q = IngressQueue(1024)
        rows = np.arange(3 * 50 * N_COLS,
                         dtype=np.uint32).reshape(150, N_COLS)
        for i in range(3):  # three chunks
            q.offer(rows[i * 50:(i + 1) * 50])
        out = np.zeros((128, N_COLS), dtype=np.uint32)
        inj = faults.arm("serving.queue.take=1x1@1")  # 2nd chunk copy
        try:
            with pytest.raises(faults.InjectedFault):
                q.take_into(out)
            assert q.pending == 150  # exception-atomic: all retained
            got, arrivals = q.take_into(out)  # retry drains normally
            assert got == 128
            np.testing.assert_array_equal(out, rows[:128])
            assert q.pending == 22
        finally:
            faults.disarm(inj)


# ---------------------------------------------------------------------
class TestDeadThreadRecovery:
    def test_restart_accounts_and_recovers(self):
        """One dispatch raises -> the drain thread dies -> the
        watchdog restarts it; the lost batch is counted + surfaced as
        REASON_RECOVERY_DROP events (monitor AND metricsmap), later
        traffic flows, and the ledger balances exactly."""
        d, db = _daemon(fault_spec="serving.dispatch=1x1")
        got = []
        d.monitor.register("t", got.append)
        d.start_serving(trace_sample=0, ingress=True, drain_every=2)
        rt = d._serving["runtime"]
        rows = _fwd(db.id)
        d.submit(rows.copy())  # this batch dies with the thread
        assert _wait(lambda: rt.stats.restarts >= 1, timeout=20)
        d.submit(rows.copy())  # post-restart traffic flows
        assert _wait(lambda: rt.stats.verdicts >= 64, timeout=30)
        fe = d.stop_serving()["front-end"]
        ft = _assert_ledger(fe)
        assert ft["restarts"] == 1
        assert ft["recovery-dropped"] == 64
        assert "InjectedFault" in ft["last-restart-cause"]
        # decoded all the way: monitor events carry the reason, the
        # DropNotify name renders, the metricsmap counts it
        drops = np.concatenate(
            [b.reason[b.msg_type == MSG_DROP] for b in got])
        assert int((drops == REASON_RECOVERY_DROP).sum()) == 64
        ev = next(materialize(b, i)
                  for b in got
                  for i in range(len(b))
                  if b.reason[i] == REASON_RECOVERY_DROP)
        assert DropNotify(ev).reason_name == "Recovery drop"
        assert DROP_REASON_DESC[REASON_RECOVERY_DROP] == \
            "RECOVERY_DROP"
        m = d.loader.metrics()
        assert int(m[REASON_RECOVERY_DROP].sum()) == 64
        d.shutdown()

    def test_submit_keeps_working_during_the_recovery_window(self):
        """A supervised death must not bounce producers: the queue is
        intact and the watchdog is healing the consumer."""
        d, db = _daemon(fault_spec="serving.dispatch=1x1",
                        serving_restart_backoff_ms=50.0)
        d.start_serving(trace_sample=0, ingress=True)
        rt = d._serving["runtime"]
        rows = _fwd(db.id)
        d.submit(rows.copy())
        # wait for the corpse (error set), then submit INTO the window
        assert _wait(lambda: rt._error is not None
                     or rt.stats.restarts >= 1, timeout=20)
        assert d.submit(rows.copy()) == 64  # no raise
        assert _wait(lambda: rt.stats.verdicts >= 64, timeout=30)
        fe = d.stop_serving()["front-end"]
        _assert_ledger(fe)
        d.shutdown()


# ---------------------------------------------------------------------
class TestHangDetection:
    def test_hang_deadlined_and_recovered(self):
        """A wedged dispatch (3s stall, 150ms deadline) is detected at
        ~deadline, its batch counted as REASON_DISPATCH_TIMEOUT, and
        the runtime recovers without operator action — well before
        the stall would have ended."""
        d, db = _daemon(fault_spec="serving.dispatch=1x1@1~3",
                        serving_dispatch_deadline_ms=150.0)
        got = []
        d.monitor.register("t", got.append)
        d.start_serving(trace_sample=0, ingress=True, drain_every=2)
        rt = d._serving["runtime"]
        rows = _fwd(db.id)
        d.submit(rows.copy())  # warm: first dispatch pays the compile
        assert _wait(lambda: rt.stats.verdicts >= 64, timeout=30)
        t0 = time.monotonic()
        d.submit(rows.copy())  # the hang
        assert _wait(lambda: rt.stats.restarts >= 1, timeout=5)
        detect = time.monotonic() - t0
        # detection at ~deadline + watchdog tick (and far inside the
        # 3s stall); generous slack for a loaded CI box
        assert detect < 1.5, f"hang detected only after {detect:.3f}s"
        d.submit(rows.copy())  # recovered: traffic flows again
        assert _wait(lambda: rt.stats.verdicts >= 128, timeout=30)
        fe = d.stop_serving()["front-end"]
        ft = _assert_ledger(fe)
        assert ft["dispatch-timeouts"] == 1
        assert ft["timeout-dropped"] == 64
        drops = np.concatenate(
            [b.reason[b.msg_type == MSG_DROP] for b in got])
        assert int((drops == REASON_DISPATCH_TIMEOUT).sum()) == 64
        assert int(d.loader.metrics()[
            REASON_DISPATCH_TIMEOUT].sum()) == 64
        d.shutdown()


    def test_first_gather_compile_in_a_dispatch_is_not_a_hang(
            self, monkeypatch):
        """A drain tick inside a warm-shape dispatch may compile the
        window's gather rung for the first time.  That compile (a
        400 ms stand-in under a 150 ms deadline) exempts the dispatch
        as a cold shape's compile does, instead of reading as a hang
        that drops the batch."""
        from cilium_tpu.monitor import ring

        real, seen = ring.ring_gather, set()

        def slow_first(buf, starts, rung, cap):
            if (len(starts), rung, cap) not in seen:
                seen.add((len(starts), rung, cap))
                time.sleep(0.4)
            return real(buf, starts, rung, cap)

        monkeypatch.setattr(ring, "_WARM_GATHERS", set())
        monkeypatch.setattr(ring, "ring_gather", slow_first)
        d, db = _daemon(serving_dispatch_deadline_ms=150.0)
        d.start_serving(trace_sample=0, ingress=True, drain_every=2)
        rt = d._serving["runtime"]
        # one doorbell of 6 buckets: the loop dispatches them back to
        # back, so the first tick runs inside the third (warm) dispatch
        d.submit(np.concatenate([_fwd(db.id, base=20000 + 64 * i)
                                 for i in range(6)]))
        assert _wait(lambda: rt.stats.verdicts >= 6 * 64, timeout=30)
        fe = d.stop_serving()["front-end"]
        d.shutdown()
        ft = _assert_ledger(fe)
        assert seen
        assert ft["dispatch-timeouts"] == 0
        assert rt.stats.restarts == 0


# ---------------------------------------------------------------------
class TestRestartBudget:
    def test_budget_exhaustion_goes_terminal_with_exact_ledger(self):
        """A persistent fault burns the budget, the runtime goes
        terminal (submit raises), and stop() still accounts every
        queued row — no silent loss even at the end of the line."""
        d, db = _daemon(fault_spec="serving.dispatch=1",
                        serving_restart_budget=2)
        d.start_serving(trace_sample=0, ingress=True)
        rt = d._serving["runtime"]
        rows = _fwd(db.id)
        # keep offering load so every restarted loop faults again;
        # terminal is reached when submit starts raising
        with pytest.raises(ServingError, match="died"):
            t0 = time.monotonic()
            while time.monotonic() - t0 < 30:
                d.submit(rows.copy())
                time.sleep(0.005)
            raise AssertionError("runtime never went terminal")
        assert rt.restarts >= 2
        # the watchdog stamps the terminal cause when it sees the
        # last corpse (may land just after submit started bouncing)
        assert _wait(lambda: "budget" in (rt._error or ""),
                     timeout=5)
        fe = d.stop_serving()["front-end"]
        ft = _assert_ledger(fe)
        assert fe["verdicts"] == 0  # every dispatch faulted
        assert ft["recovery-dropped"] == fe["submitted"]
        d.shutdown()


# ---------------------------------------------------------------------
class TestStopOverACorpse:
    """Satellite: stop() after a drain-thread death must still flush
    sheds, stamp the last completion, and count queued rows."""

    def test_stop_flushes_sheds_stamps_completion_counts_queue(self):
        import threading

        recovered = []
        calls = {"n": 0}
        release = threading.Event()

        def dispatch(hdr, valid, n_valid, packed_meta=None):
            calls["n"] += 1
            if calls["n"] == 2:
                # hold the loop here until the test has queued the
                # overflow + the never-to-dispatch rows, THEN die
                release.wait(10)
                raise RuntimeError("boom")

        sheds = []
        rt = ServingRuntime(
            dispatch, queue_depth=256, bucket_ladder=(64,),
            max_wait_us=100.0,
            on_shed=lambda rows, n: sheds.append(n),
            on_recovery_drop=lambda rows, n, r: recovered.append(
                (n, r)))  # unsupervised: budget 0 -> death is final
        rt.start()
        rows = np.ones((64, N_COLS), dtype=np.uint32)
        rt.submit(rows)  # batch 1 dispatches fine
        assert _wait(lambda: rt.stats.batches == 1, timeout=10)
        rt.submit(rows)  # batch 2 will kill the loop
        assert _wait(lambda: calls["n"] == 2, timeout=10)
        # rows that will never dispatch + a guaranteed overflow shed
        rt.submit(np.ones((300, N_COLS), dtype=np.uint32))
        release.set()
        assert _wait(lambda: rt._error is not None, timeout=10)
        snap = rt.stop()
        # 428 submitted = 64 dispatched + 44 shed (300 into a 256-cap
        # queue) + 320 recovery (batch 2 + the 256 swept rows); the
        # assertion is the LEDGER, not the constants
        ft = snap["fault-tolerance"]
        assert snap["submitted"] == (snap["verdicts"] + snap["shed"]
                                     + ft["recovery-dropped"])
        assert snap["verdicts"] == 64
        assert snap["shed"] == 44
        assert ft["recovery-dropped"] == 320
        assert sum(n for n, _r in recovered) == 320
        assert all(r == REASON_RECOVERY_DROP for _n, r in recovered)
        assert sum(sheds) == 44  # sheds flushed as events at stop
        # the completed batch's latency was stamped despite the corpse
        assert snap["latency-us"]["count"] >= 1
        assert "error" in snap

    def test_idle_wait_is_config_derived(self):
        """Satellite: the hard-coded 50ms idle tick is gone — a 40ms
        dispatch deadline derives a 10ms idle wait, so sub-50ms
        watchdog deadlines are honorable."""
        d, _db = _daemon(serving_dispatch_deadline_ms=40.0)
        d.start_serving(trace_sample=0, ingress=True)
        rt = d._serving["runtime"]
        assert rt._idle_wait_s == pytest.approx(0.01)
        d.stop_serving()
        d.shutdown()
        # default deadline (1000ms): the legacy 50ms tick
        d2, _db2 = _daemon()
        d2.start_serving(trace_sample=0, ingress=True)
        assert d2._serving["runtime"]._idle_wait_s == \
            pytest.approx(0.05)
        d2.stop_serving()
        d2.shutdown()


# ---------------------------------------------------------------------
class TestLadderStateMachine:
    def test_hysteresis_and_floor(self):
        lad = FallbackLadder(["sharded", "single", "wide"],
                             demote_threshold=3, promote_after=2,
                             cooldown_s=10.0)
        assert not lad.record_failure("a")
        assert not lad.record_failure("b")
        lad.record_success()  # flapping resets the streak
        assert not lad.record_failure("c")
        assert not lad.record_failure("d")
        assert lad.record_failure("e")  # 3 consecutive -> demote
        assert lad.demote() == "single"
        # cooldown gates promotion even after sustained health
        lad.last_change = time.monotonic()
        assert not lad.record_success()
        assert not lad.record_success()
        lad.last_change = time.monotonic() - 11.0
        lad.ok_streak = 0
        lad.record_success()
        assert lad.record_success()
        assert lad.promote() == "sharded"
        # at the floor, failures never demote (they escalate)
        lad2 = FallbackLadder(["wide"], demote_threshold=1)
        assert lad2.at_floor
        assert not lad2.record_failure("x")

    def test_rungs_follow_session_config(self):
        d, _db = _daemon()
        d.start_serving(trace_sample=0, ingress=True)  # no mesh/pack
        assert d._serving["ladder"].rungs == ("wide",)
        d.stop_serving()
        d.start_serving(trace_sample=0, ingress=True, packed=True)
        assert d._serving["ladder"].rungs == ("single", "wide")
        d.stop_serving()
        d.shutdown()


class TestLadderDemotion:
    def test_packed_demotes_to_wide_then_promotes_back(self):
        """Two packed-path faults demote single -> wide (the
        triggering batch retried on the demoted rung, not lost);
        sustained health + cooldown promote back."""
        d, db = _daemon(fault_spec="loader.serve_packed=1x2@1")
        d.start_serving(trace_sample=0, ingress=True, packed=True,
                        drain_every=2)
        rt = d._serving["runtime"]
        rows = _fwd(db.id)
        d.submit(rows.copy())  # warm (packed)
        assert _wait(lambda: rt.stats.verdicts >= 64, timeout=30)
        d.submit(rows.copy())  # fault 1: contained drop
        assert _wait(lambda: rt.stats.recovery_dropped >= 64,
                     timeout=30)
        d.submit(rows.copy())  # fault 2: demote + retry (saved)
        assert _wait(lambda: rt.stats.verdicts >= 128, timeout=60)
        st = d.serving_stats()
        assert st["mode"] == "wide"
        assert st["ladder"]["demotions"] == 1
        assert rt.stats.restarts == 0  # contained: no restart burned
        # heal: promote_after=3 healthy batches + 50ms cooldown
        for i in range(5):
            d.submit(rows.copy())
            assert _wait(
                lambda i=i: rt.stats.verdicts >= 128 + (i + 1) * 64,
                timeout=30)
            time.sleep(0.02)
        assert _wait(
            lambda: d.serving_stats()["mode"] == "single", timeout=10)
        assert d.serving_stats()["ladder"]["promotions"] == 1
        fe = d.stop_serving()["front-end"]
        _assert_ledger(fe)
        d.shutdown()

    def test_sharded_demotion_preserves_established_ct(self):
        """THE acceptance property (b): flows established while
        sharded still pass their replies after demotion to
        single-chip — db's egress hook is enforced, so a reply can
        only pass via the CT entry carried across by
        snapshot + ct_restore."""
        d, db = _daemon(fault_spec="loader.serve_sharded=1x2@1",
                        rules=RULES_EGRESS_ENFORCED,
                        serving_promote_after=1000)
        from cilium_tpu.parallel import make_mesh

        got = []
        d.monitor.register("t", got.append)
        # 4 chips: the CT-continuity property is mesh-size-invariant
        # and the sharded serve step's compile is the suite's single
        # biggest cost
        d.start_serving(ring_capacity=1 << 10, trace_sample=1,
                        ingress=True, packed=True,
                        drain_every=2, mesh=make_mesh(4))
        rt = d._serving["runtime"]
        d.submit(_fwd(db.id))  # establish 64 flows, sharded (warm)
        assert _wait(lambda: rt.stats.verdicts >= 64, timeout=60)
        assert d.serving_stats()["mode"] == "sharded"
        d.submit(_fwd(db.id, base=40000))  # fault 1: contained
        assert _wait(lambda: rt.stats.recovery_dropped >= 64,
                     timeout=60)
        d.submit(_fwd(db.id, base=41000))  # fault 2: demote + retry
        assert _wait(lambda: rt.stats.verdicts >= 128, timeout=90)
        st = d.serving_stats()
        assert st["mode"] in ("single", "wide")
        assert st["ladder"]["demotions"] == 1
        # demotion stored a CT snapshot and restored it
        assert st["ct-snapshot"]["trigger"] == "demotion"
        assert st["ct-snapshot"]["entries"] >= 64
        # replies of the PRE-DEMOTION flows on the demoted rung
        got.clear()
        d.submit(_rep(db.id))
        assert _wait(lambda: rt.stats.verdicts >= 192, timeout=60)
        fe = d.stop_serving()["front-end"]
        _assert_ledger(fe)
        rep_fwd = rep_drop = 0
        for b in got:
            m = b.hdr[:, COL_DIR] == 1
            rep_fwd += int((b.msg_type[m] != MSG_DROP).sum())
            rep_drop += int((b.msg_type[m] == MSG_DROP).sum())
        assert rep_drop == 0 and rep_fwd == 64, (
            f"CT continuity broken: {rep_drop} replies dropped, "
            f"{rep_fwd} forwarded")
        d.shutdown()


# ---------------------------------------------------------------------
class TestRandomFaultSchedule:
    @pytest.mark.parametrize("seed", [3, 11])
    def test_no_silent_loss_under_random_faults(self, seed):
        """Acceptance (c): a seeded random schedule over several sites
        — raises, contained packed failures, queue memcpy faults —
        and the ledger still balances EXACTLY at stop, with every
        recovery drop surfaced as a decoded event."""
        d, db = _daemon(
            fault_spec=("serving.dispatch=0.05;"
                        "loader.serve_packed=0.1;"
                        "serving.queue.take=0.02"),
            fault_seed=seed,
            serving_restart_budget=64,
            serving_demote_threshold=3)
        got = []
        d.monitor.register("t", got.append)
        d.start_serving(trace_sample=0, ingress=True, packed=True,
                        drain_every=2)
        rt = d._serving["runtime"]
        rows = _fwd(db.id)
        submitted = 0
        for i in range(30):
            try:
                submitted += d.submit(rows.copy())
            except ServingError:
                break  # terminal (budget gone): stop still accounts
            # bounded pacing, far under the 500ms deadline
            _wait(lambda: rt.queue.pending < 2048, timeout=1.0)
        _wait(lambda: rt.queue.pending == 0, timeout=30)
        fe = d.stop_serving()["front-end"]
        ft = _assert_ledger(fe)
        assert fe["submitted"] == submitted
        # the schedule actually bit (seeded: deterministic)
        assert ft["recovery-dropped"] > 0
        # every recovery drop surfaced as a decoded DROP event
        drops = (np.concatenate(
            [b.reason[b.msg_type == MSG_DROP] for b in got])
            if got else np.zeros(0))
        n_rec = int(np.isin(drops, (REASON_DISPATCH_TIMEOUT,
                                    REASON_RECOVERY_DROP)).sum())
        assert n_rec == ft["recovery-events"]
        assert ft["recovery-events"] == ft["recovery-dropped"]
        d.shutdown()


# ---------------------------------------------------------------------
class TestSurfacing:
    def test_reason_codes_fit_the_ring_wire_format(self):
        """The 4-bit ring reason field covers the reserved recovery
        codes (N_REASONS=13 -> 3 codes of headroom; 12 is the
        cluster router's REASON_CLUSTER_OVERFLOW)."""
        import jax.numpy as jnp

        from cilium_tpu.datapath.verdict import (EV_DROP, N_OUT,
                                                 OUT_EVENT,
                                                 OUT_REASON)
        from cilium_tpu.monitor.ring import EventRing, ring_append, \
            ring_drain

        assert N_REASONS == 13 and N_REASONS <= 0xF + 1
        for reason in (REASON_DISPATCH_TIMEOUT, REASON_RECOVERY_DROP):
            out = np.zeros((4, N_OUT), dtype=np.uint32)
            out[:, OUT_EVENT] = EV_DROP
            out[:, OUT_REASON] = reason
            ring = EventRing.create(16)
            ring = ring_append(ring, jnp.asarray(out), jnp.uint32(0),
                               trace_sample=0)
            rows, total, _lost = ring_drain(ring)
            assert total == 4
            assert (rows[:, OUT_REASON] == reason).all()
            assert reason in DROP_REASON_NAMES
            assert reason in DROP_REASON_DESC

    def test_stats_prometheus_and_health_surfacing(self):
        """Fault counters reach GET /serving, prometheus, the node
        registry (health plane), and the CLI rendering path."""
        from cilium_tpu.api.server import _metrics_text
        from cilium_tpu.kvstore import InMemoryKVStore

        kv = InMemoryKVStore()
        d = Daemon(DaemonConfig(
            backend="tpu", ct_capacity=1 << 12,
            flow_ring_capacity=1 << 13, serving_queue_depth=4096,
            serving_bucket_ladder=(64,),
            serving_max_wait_us=500.0,
            fault_injection="serving.dispatch=1x1", fault_seed=1,
            serving_restart_backoff_ms=1.0), kvstore=kv)
        d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        d.policy_import(RULES)
        d.start_serving(trace_sample=0, ingress=True)
        rt = d._serving["runtime"]
        d.submit(_fwd(db.id))
        assert _wait(lambda: rt.stats.restarts >= 1, timeout=20)
        d.submit(_fwd(db.id))
        assert _wait(lambda: rt.stats.verdicts >= 64, timeout=30)
        d.ct_snapshot_now()
        st = d.serving_stats()
        assert st["mode"] == "wide"
        assert st["fault-tolerance"]["restarts"] == 1
        assert st["ct-snapshot"]["entries"] >= 64
        prom = _metrics_text(d)
        assert "cilium_serving_restarts_total 1" in prom
        assert "cilium_serving_recovery_dropped_total 64" in prom
        assert "cilium_ct_snapshot_age_seconds" in prom
        # health plane: the node registry carries the fault state
        d.node_registry.annotate(d.config.node_name,
                                 d._node_fault_info())
        node = next(n for n in d.node_registry.nodes()
                    if n["name"] == d.config.node_name)
        assert node["serving-mode"] == "wide"
        assert node["serving-restarts"] == 1
        assert "ct-snapshot-age-seconds" in node
        # status() carries the same compact section
        assert d.status()["serving"]["serving-restarts"] == 1
        d.stop_serving()
        d.shutdown()

    def test_ct_snapshot_restore_round_trip(self):
        """ct_snapshot_now + restore_ct_snapshot: established flows
        survive a loader CT reload from the retained snapshot."""
        d, db = _daemon(rules=RULES_EGRESS_ENFORCED)
        d.process_batch(_fwd(db.id))  # establish flows (offline path)
        info = d.ct_snapshot_now(trigger="manual")
        assert info["entries"] >= 64 and info["trigger"] == "manual"
        # clobber the live CT, then restore from the snapshot
        from cilium_tpu.datapath.conntrack import ROW_WORDS

        d.loader.ct_restore(np.zeros((0, ROW_WORDS), dtype=np.uint32))
        assert d.restore_ct_snapshot()
        out = d.process_batch(_rep(db.id))
        assert int((out.msg_type == MSG_DROP).sum()) == 0
        d.shutdown()

    def test_dispatch_failed_error_is_a_serving_error(self):
        assert issubclass(DispatchFailedError, ServingError)
        j = json.dumps  # the ladder dict must be JSON-serializable
        lad = FallbackLadder(["wide"])
        j(lad.to_dict())
