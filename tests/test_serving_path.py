"""The serving-path monitor plane: fused datapath + device event ring
-> async drain -> header join -> MonitorAgent (upstream's perf-ring ->
monitor-agent -> hubble chain, with only compacted events crossing the
device->host link).
"""

import numpy as np
import pytest

from cilium_tpu.agent import Daemon, DaemonConfig
from cilium_tpu.core import TCP_SYN, make_batch
from cilium_tpu.monitor.api import MSG_DROP, MSG_POLICY_VERDICT, MSG_TRACE

RULES = [{
    "labels": [{"key": "db-policy"}],
    "endpointSelector": {"matchLabels": {"app": "db"}},
    "ingress": [{
        "fromEndpoints": [{"matchLabels": {"app": "web"}}],
        "toPorts": [{"ports": [{"port": "5432", "protocol": "TCP"}]}],
    }],
}]


def _world():
    d = Daemon(DaemonConfig(backend="tpu", ct_capacity=1 << 12))
    d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
    db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
    d.policy_import(RULES)
    return d, db


def _traffic(db_id, base_sport, n=64):
    # half allowed NEW flows, half scan-drops
    return make_batch([
        dict(src="10.0.1.1", dst="10.0.2.1",
             sport=base_sport + i,
             dport=5432 if i % 2 == 0 else 9999,
             proto=6, flags=TCP_SYN, ep=db_id, dir=0)
        for i in range(n)
    ]).data


class TestServingPath:
    def test_ring_events_reach_the_monitor(self):
        d, db = _world()
        got = []
        d.monitor.register("test", got.append)
        d.start_serving(ring_capacity=1 << 10, drain_every=2,
                        trace_sample=0)
        for i in range(6):
            d.serve_batch(_traffic(db.id, 20000 + 100 * i), now=10 + i)
        stats = d.stop_serving()

        assert stats["lost"] == 0
        assert stats["windows"] >= 3
        msg = np.concatenate([b.msg_type for b in got])
        verdicts = np.concatenate([b.verdict for b in got])
        # every batch: 32 allowed NEW (PolicyVerdict) + 32 drops
        assert int((msg == MSG_POLICY_VERDICT).sum()) == 6 * 32
        assert int((msg == MSG_DROP).sum()) == 6 * 32
        # trace_sample=0: established traffic stays on device
        assert int((msg == MSG_TRACE).sum()) == 0
        # OUT_VERDICT carries the datapath's forwarding decision:
        # 0 = dropped, 1 = forwarded (3 = redirect)
        assert set(verdicts[msg == MSG_DROP]) == {0}
        assert set(verdicts[msg == MSG_POLICY_VERDICT]) == {1}

    def test_serving_events_match_process_batch_events(self):
        """The serving path's compacted stream == the debug path's
        non-trace events (same traffic, fresh daemons)."""
        d1, db1 = _world()
        d2, db2 = _world()

        def key_set(batches):
            out = set()
            for b in batches:
                for i in range(len(b)):
                    out.add((int(b.msg_type[i]), int(b.verdict[i]),
                             int(b.identity[i]),
                             int(b.hdr[i, 8])))  # COL_SPORT
            return out

        got1 = []
        d1.monitor.register("t", got1.append)
        d1.start_serving(ring_capacity=1 << 10, drain_every=2,
                         trace_sample=0)
        for i in range(4):
            d1.serve_batch(_traffic(db1.id, 30000 + 100 * i),
                           now=10 + i)
        d1.stop_serving()

        got2 = []
        d2.monitor.register("t", got2.append)
        for i in range(4):
            d2.process_batch(_traffic(db2.id, 30000 + 100 * i),
                             now=10 + i)

        assert key_set(got1) == {
            (int(b.msg_type[i]), int(b.verdict[i]), int(b.identity[i]),
             int(b.hdr[i, 8]))
            for b in got2 for i in range(len(b))
            if b.msg_type[i] != MSG_TRACE}

    def test_redirect_events_restore_proxy_port(self):
        """L7 redirects stream their proxy port through the 4-bit
        listener-table index."""
        d = Daemon(DaemonConfig(backend="tpu", ct_capacity=1 << 12))
        d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        d.policy_import([{
            "endpointSelector": {"matchLabels": {"app": "db"}},
            "ingress": [{
                "fromEndpoints": [{"matchLabels": {"app": "web"}}],
                "toPorts": [{
                    "ports": [{"port": "80", "protocol": "TCP"}],
                    "rules": {"http": [{"method": "GET"}]},
                }],
            }],
        }])
        assert d.proxy.ports, "expected an L7 redirect listener"
        got = []
        d.monitor.register("t", got.append)
        d.start_serving(drain_every=1, trace_sample=0)
        d.serve_batch(make_batch([
            dict(src="10.0.1.1", dst="10.0.2.1", sport=41000,
                 dport=80, proto=6, flags=TCP_SYN, ep=db.id, dir=0)
        ]).data, now=5)
        d.stop_serving()
        ports = {int(p) for b in got for p in b.proxy_port}
        assert ports & set(d.proxy.ports), \
            f"proxy port lost on the ring wire: {ports}"

    def test_interpreter_backend_refuses_serving(self):
        d = Daemon(DaemonConfig(backend="interpreter"))
        with pytest.raises(RuntimeError, match="tpu"):
            d.start_serving()


class TestDefaultRingSize:
    """start_serving's default ring holds a whole window of the top
    rung even when every row is an event: a row appends at most one."""

    @pytest.mark.parametrize("ladder,kw,want", [
        ((1024, 4096, 16384, 65536), {}, 4 * 65536),
        ((1024, 4096, 16384, 65536), {"drain_every": 2}, 2 * 65536),
        ((1024, 65536), {"superbatch_k": 2}, 1 << 19),  # 5 top rungs
        ((256, 1024), {}, 1 << 15),  # never below 2^15
        ((1024, 65536), {"ring_capacity": 1 << 10}, 1 << 10),
    ])
    def test_capacity(self, ladder, kw, want):
        d = Daemon(DaemonConfig(backend="tpu", ct_capacity=1 << 12,
                                serving_bucket_ladder=ladder,
                                serving_queue_depth=ladder[-1]))
        try:
            d.start_serving(trace_sample=0, **kw)
            assert d.serving_stats()["ring"]["capacity"] == want
            d.stop_serving()
        finally:
            d.shutdown()

    def test_all_drop_window_of_the_top_rung_loses_nothing(self):
        # 4 batches x 16384 drops fill one window: 2x the old fixed
        # 2^15 default, which overwrote half of them
        top = 1 << 14
        d = Daemon(DaemonConfig(backend="tpu", ct_capacity=1 << 12,
                                serving_bucket_ladder=(1024, top),
                                serving_queue_depth=top))
        d.add_endpoint("web", ("10.0.1.1",), ["k8s:app=web"])
        db = d.add_endpoint("db", ("10.0.2.1",), ["k8s:app=db"])
        d.policy_import(RULES)
        drops = np.repeat(_traffic(db.id, 20000, n=2)[1:], top, axis=0)
        try:
            d.start_serving(trace_sample=0)
            for i in range(4):
                d.serve_batch(drops, now=10 + i)
            stats = d.stop_serving()
        finally:
            d.shutdown()
        assert stats["lost"] == 0
        assert stats["events"] == 4 * top


class TestServingAcrossRegeneration:
    def test_identity_churn_mid_serving_window(self):
        """Identity churn between serving batches: events of a
        post-churn batch must decode identities minted BY that churn.
        The row-map object is reused and mutated across
        regenerations, so the serving path's numerics snapshot must
        key on the map's version — object identity alone would serve
        the stale pre-churn table forever (r05 regression)."""
        d, db = _world()
        got = []
        d.monitor.register("t", got.append)
        d.start_serving(ring_capacity=1 << 10, drain_every=2,
                        trace_sample=0)
        d.serve_batch(_traffic(db.id, 40000), now=10)
        # churn: a brand-new identity appears and its policy allows
        # it to reach db (regeneration mutates the SAME row map)
        d.add_endpoint("cache", ("10.0.3.1",), ["k8s:app=cache"])
        d.policy_import([{
            "labels": [{"key": "cache-policy"}],
            "endpointSelector": {"matchLabels": {"app": "db"}},
            "ingress": [{
                "fromEndpoints": [{"matchLabels": {"app": "cache"}}],
                "toPorts": [{"ports": [
                    {"port": "5432", "protocol": "TCP"}]}],
            }],
        }])
        # post-churn traffic FROM the new identity
        d.serve_batch(make_batch([
            dict(src="10.0.3.1", dst="10.0.2.1", sport=41000 + k,
                 dport=5432, proto=6, flags=TCP_SYN, ep=db.id, dir=0)
            for k in range(8)
        ]).data, now=11)
        d.serve_batch(_traffic(db.id, 40200), now=12)
        stats = d.stop_serving()
        assert stats["lost"] == 0
        cache_id = d.endpoints.lookup_by_ip(
            "10.0.3.1").identity.numeric_id
        web_id = d.endpoints.lookup_by_ip(
            "10.0.1.1").identity.numeric_id
        ids = np.concatenate([b.identity for b in got])
        assert len(ids) == 2 * 64 + 8
        # the new identity decodes as ITSELF, not as 0/unknown (a
        # stale numerics snapshot maps its fresh row to 0)
        assert (ids == cache_id).sum() == 8
        assert (ids == web_id).sum() == 2 * 64
