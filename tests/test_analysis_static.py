"""The concurrency & invariant static analyzer (cilium_tpu/analysis):
per-checker fixture suites (known-bad must flag with the right code
and line, known-good must pass), suppression + baseline round-trips,
the live-repo-is-clean gate, and the annotation-presence assertions
that turn the PR 5/6 runtime monkeypatch proofs into static ones.

Pure stdlib ast — no jax, no devices; the whole suite must stay
cheap enough to live in tier-1.
"""

from __future__ import annotations

import os
import textwrap

import pytest

from cilium_tpu.analysis import Repo, repo_root, run_analysis
from cilium_tpu.analysis import (affinity, cluster_lint, guarded,
                                 hotpath, reasons, registry_lint,
                                 sharding, sysdump_lint)
from cilium_tpu.analysis.annotations import extract_lock_map
from cilium_tpu.analysis.callgraph import CallGraph
from cilium_tpu.analysis.core import Baseline

pytestmark = pytest.mark.analysis

REPO = repo_root()


def _mini_repo(tmp_path, files: dict) -> Repo:
    """A throwaway repo whose package mirrors the real layout."""
    for rel, src in files.items():
        p = tmp_path / "cilium_tpu" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    init = tmp_path / "cilium_tpu" / "__init__.py"
    if not init.exists():
        init.write_text("")
    return Repo(str(tmp_path))


def _codes(findings):
    return sorted({f.code for f in findings})


# ---------------------------------------------------------------------
# CTA001 guarded-by
# ---------------------------------------------------------------------
class TestGuardedBy:
    def test_unlocked_touch_flags_with_line(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    # guarded-by: _lock: counter
                    self.counter = 0

                def bad(self):
                    self.counter += 1

                def good(self):
                    with self._lock:
                        self.counter += 1
        """})
        fs = guarded.check(repo)
        assert [f.code for f in fs] == ["CTA001"]
        assert "counter" in fs[0].message
        bad_line = repo.files[-1].source.splitlines().index(
            "        self.counter += 1") + 1
        assert fs[0].line == bad_line

    def test_init_and_holds_exempt(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    # guarded-by: _lock: x
                    self.x = 0
                    self.x = 1  # __init__ is exempt

                def helper(self):
                    # holds: _lock
                    return self.x
        """})
        assert guarded.check(repo) == []

    def test_condition_alias_resolves_to_wrapped_lock(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cv = threading.Condition(self._lock)
                    # guarded-by: _lock: q
                    self.q = []

                def ok(self):
                    with self._cv:
                        self.q.append(1)
        """})
        assert guarded.check(repo) == []

    def test_make_lock_runtime_name_is_a_static_alias(self, tmp_path):
        """Satellite: infra/lockdebug.py make_lock names feed the
        alias map — `guarded-by: my-lock` == `guarded-by: _lock`,
        the same identity the runtime DebugLock reports under."""
        repo = _mini_repo(tmp_path, {"m.py": """
            from cilium_tpu.infra.lockdebug import make_lock

            class C:
                def __init__(self):
                    self._lock = make_lock("my-lock")
                    # guarded-by: my-lock: state
                    self.state = None

                def ok(self):
                    with self._lock:
                        self.state = 1

                def bad(self):
                    self.state = 2
        """})
        fs = guarded.check(repo)
        assert [f.code for f in fs] == ["CTA001"]
        assert "state" in fs[0].message
        import ast

        cls = [n for n in repo.files[-1].tree.body
               if isinstance(n, ast.ClassDef)][0]
        lm = extract_lock_map(cls)
        assert lm.resolve("my-lock") == "_lock"
        assert lm.resolve("_lock") == "_lock"

    def test_lambda_body_holds_nothing(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    # guarded-by: _lock: n
                    self.n = 0

                def bad(self):
                    with self._lock:
                        return lambda: self.n + 1
        """})
        fs = guarded.check(repo)
        assert [f.code for f in fs] == ["CTA001"]

    def test_unknown_lock_name_is_config_error(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    # guarded-by: _nope: n
                    self.n = 0
        """})
        fs = guarded.check(repo)
        assert [f.code for f in fs] == ["CTA000"]

    def test_live_repo_annotation_pass_is_in_place(self):
        gm = guarded.guarded_map(Repo(REPO))
        # the audited-by-hand classes from the issue now carry
        # machine-checked declarations
        expect = {
            ("cilium_tpu/serving/runtime.py", "ServingRuntime"),
            ("cilium_tpu/serving/ingress.py", "IngressQueue"),
            ("cilium_tpu/serving/eventplane.py", "EventJoinWorker"),
            ("cilium_tpu/flow/observer.py", "Observer"),
            ("cilium_tpu/obs/analytics.py", "FlowAnalytics"),
            ("cilium_tpu/monitor/agent.py", "MonitorAgent"),
            ("cilium_tpu/datapath/loader.py", "TPULoader"),
        }
        assert expect <= set(gm)
        assert gm[("cilium_tpu/serving/runtime.py",
                   "ServingRuntime")]["_inflight"] == "_rec_lock"
        assert gm[("cilium_tpu/datapath/loader.py",
                   "TPULoader")]["state"] == "_lock"


# ---------------------------------------------------------------------
# CTA002 thread-affinity
# ---------------------------------------------------------------------
class TestThreadAffinity:
    def test_drain_reaching_worker_only_flags(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            def decode(rows):
                # thread-affinity: event-worker
                return rows

            def loop():
                # thread-affinity: drain
                decode([])
        """})
        fs = affinity.check(repo, CallGraph(repo))
        assert [f.code for f in fs] == ["CTA002"]
        assert "decode" in fs[0].message and "drain" in fs[0].message

    def test_propagates_through_unannotated_middle(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            def decode(rows):
                # thread-affinity: event-worker
                return rows

            def helper():
                decode([])

            def loop():
                # thread-affinity: drain
                helper()
        """})
        fs = affinity.check(repo, CallGraph(repo))
        assert [f.code for f in fs] == ["CTA002"]

    def test_superset_and_any_pass(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            def shared():
                # thread-affinity: drain, api
                return 1

            def anything():
                # thread-affinity: any
                return 2

            def loop():
                # thread-affinity: drain
                shared()
                anything()
        """})
        assert affinity.check(repo, CallGraph(repo)) == []

    def test_unknown_affinity_is_config_error(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            def f():
                # thread-affinity: darin
                return 1
        """})
        graph = CallGraph(repo)
        assert [f.code for f in graph.config_findings] == ["CTA000"]

    def test_tentpole_annotations_present_and_exclude_drain(self):
        """THE acceptance gate: the two invariants previously proven
        only by runtime monkeypatch tests are declared statically —
        deleting either annotation fails this test, and adding a
        drain-side call site fails the live-repo-clean gate."""
        am = affinity.affinity_map(CallGraph(Repo(REPO)))
        decode = am[("cilium_tpu/monitor/api.py", "decode_ring_rows")]
        ingest = am[("cilium_tpu/obs/analytics.py",
                     "FlowAnalytics._ingest")]
        for affs in (decode, ingest):
            assert "drain" not in affs and "any" not in affs
        assert "event-worker" in decode and "event-worker" in ingest
        # and the drain loop actually declares itself, so the walk
        # has roots to generalize the proof from
        assert "drain" in am[("cilium_tpu/serving/runtime.py",
                              "ServingRuntime._loop_body")]

    def test_cluster_router_annotations_present(self):
        """ISSUE 8: the cluster tier's hot path declares the
        ``router`` domain — deleting either annotation (the enqueue
        path or the forwarder loop) fails here, and the CTA003
        purity pass loses its roots."""
        am = affinity.affinity_map(CallGraph(Repo(REPO)))
        route = am[("cilium_tpu/cluster/router.py",
                    "ClusterRouter._route")]
        fwd = am[("cilium_tpu/cluster/router.py",
                  "ClusterRouter._forward_loop")]
        assert "router" in route and "router" in fwd
        # the surfacing leg is router-reachable too (sheds decode on
        # a node's monitor plane without leaving the domain)
        assert "router" in am[("cilium_tpu/agent/daemon.py",
                               "Daemon._publish_cluster_drops")]
        # membership/failover are control-plane (api family), NOT
        # router — failover's CT replay must never look like the
        # enqueue hot path
        assert "api" in am[("cilium_tpu/cluster/membership.py",
                            "ClusterMembership._probe_loop")]
        assert "api" in am[("cilium_tpu/cluster/failover.py",
                            "FailoverOrchestrator.fail_over")]


# ---------------------------------------------------------------------
# CTA003 hot-path purity
# ---------------------------------------------------------------------
class TestHotPath:
    _BAD = """
        import json
        import logging
        import time

        def loop():
            # thread-affinity: drain
            time.sleep(0.1)
            json.dumps({})
            open("/tmp/x")
            logging.getLogger(__name__).warning("hot")
            cursor.block_until_ready()
    """

    def test_all_five_bans_flag(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": self._BAD})
        fs = hotpath.check(repo, CallGraph(repo))
        whats = sorted(f.message.split(" in ")[0] for f in fs)
        assert whats == ["device sync (block_until_ready)",
                        "file I/O (open)", "json.dumps",
                        "logging.warning (>= INFO)", "time.sleep"]
        assert {f.code for f in fs} == {"CTA003"}

    def test_reaches_through_callees_and_debug_is_fine(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            import time
            import logging

            def helper():
                logging.getLogger(__name__).debug("fine")
                time.sleep(0.1)

            def loop():
                # thread-affinity: drain
                helper()
        """})
        fs = hotpath.check(repo, CallGraph(repo))
        assert len(fs) == 1 and "time.sleep" in fs[0].message

    def test_waiver_silences(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            import time

            def loop():
                # thread-affinity: drain
                # hot-path-ok: bounded idle tick
                time.sleep(0.001)
        """})
        assert hotpath.check(repo, CallGraph(repo)) == []

    def test_non_drain_code_not_scanned(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            import json

            def capture():
                # thread-affinity: capture
                json.dumps({})
        """})
        assert hotpath.check(repo, CallGraph(repo)) == []

    def test_router_domain_is_a_hot_path_root(self, tmp_path):
        """ISSUE 8 satellite: the cluster router's enqueue path is a
        CTA003 domain of its own — router-affine code is scanned
        (and named as the router hot path), api-affine code is
        not."""
        repo = _mini_repo(tmp_path, {"m.py": """
            import time

            def enqueue():
                # thread-affinity: router
                time.sleep(0.1)

            def failover():
                # thread-affinity: api
                time.sleep(1.0)
        """})
        fs = hotpath.check(repo, CallGraph(repo))
        assert len(fs) == 1
        assert "cluster router hot path" in fs[0].message
        assert "time.sleep" in fs[0].message

    def test_transport_domain_is_a_hot_path_root(self, tmp_path):
        """ISSUE 13 satellite: the cluster transport I/O threads
        (row-frame send/recv on the forwarders and the node host's
        data reader) are a CTA003 hot domain — transport-affine code
        is purity-scanned and named as such."""
        repo = _mini_repo(tmp_path, {"m.py": """
            import json

            def data_loop():
                # thread-affinity: transport
                return json.dumps({"a": 1})

            def control_op():
                # thread-affinity: api
                return json.dumps({"b": 2})
        """})
        fs = hotpath.check(repo, CallGraph(repo))
        assert len(fs) == 1
        assert "cluster transport I/O" in fs[0].message
        assert "json.dumps" in fs[0].message
        # and the live repo's data-loop annotation is load-bearing
        from cilium_tpu.analysis.affinity import affinity_map

        full = Repo(REPO)
        am = affinity_map(CallGraph(full))
        assert "transport" in am[
            ("cilium_tpu/cluster/nodehost.py",
             "_NodeHost._data_loop")]

    def test_router_reaching_drain_only_code_flags_cta002(self,
                                                          tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            def decode():
                # thread-affinity: event-worker
                pass

            def forward_loop():
                # thread-affinity: router
                decode()
        """})
        fs = affinity.check(repo, CallGraph(repo))
        assert len(fs) == 1 and fs[0].code == "CTA002"
        assert "router" in fs[0].message


# ---------------------------------------------------------------------
# CTA004 sharding-spec spelling
# ---------------------------------------------------------------------
class TestShardingSpec:
    def test_trailing_none_in_device_put_context_flags(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            from jax.sharding import NamedSharding, PartitionSpec as P

            def place(mesh, x):
                return NamedSharding(mesh, P("data", None))
        """})
        fs = sharding.check(repo)
        assert [f.code for f in fs] == ["CTA004"]
        assert fs[0].line == 5

    def test_shard_map_specs_and_spec_vars_allowed(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": """
            from functools import partial
            from jax.sharding import PartitionSpec as P

            state_specs = (P(), P("data", None))

            def build(mesh, shard_map, fn):
                return partial(
                    shard_map, mesh=mesh,
                    in_specs=state_specs + (P("data", None),),
                    out_specs=(P("data", None),))(fn)

            def trimmed(mesh):
                return P("data")
        """})
        assert sharding.check(repo) == []

    def test_live_repo_mesh_module_is_clean(self):
        """parallel/mesh.py holds both the trap's fix (P(axis) for
        device_put) and the legitimate rank-explicit shard_map
        spellings — the checker must thread that needle."""
        repo = Repo(REPO)
        assert [f for f in sharding.check(repo)
                if f.path == "cilium_tpu/parallel/mesh.py"] == []


# ---------------------------------------------------------------------
# CTA005 reason-code budget
# ---------------------------------------------------------------------
class TestReasonCodes:
    GOOD_VERDICT = """
        REASON_FORWARDED = 0
        REASON_DENY = 1
        N_REASONS = 2
    """

    def test_duplicate_and_overflow_and_mismatch(self, tmp_path):
        repo = _mini_repo(tmp_path, {"datapath/verdict.py": """
            REASON_A = 1
            REASON_B = 1
            REASON_C = 16
            N_REASONS = 5
        """})
        fs = reasons.check(repo)
        msgs = " | ".join(f.message for f in fs)
        assert "duplicate reason code 1" in msgs
        assert "does not fit the ring's 4-bit" in msgs
        assert "N_REASONS" in msgs
        assert {f.code for f in fs} == {"CTA005"}

    def test_decode_table_coverage(self, tmp_path):
        repo = _mini_repo(tmp_path, {
            "datapath/verdict.py": """
                REASON_FORWARDED = 0
                REASON_DENY = 1
                REASON_NEW = 2
                N_REASONS = 3
            """,
            "monitor/api.py": """
                DROP_REASON_NAMES = {1: "Policy denied"}
            """})
        fs = reasons.check(repo)
        assert len(fs) == 1 and fs[0].code == "CTA005"
        assert "missing reason code(s) [2]" in fs[0].message
        assert fs[0].path == "cilium_tpu/monitor/api.py"

    def test_live_repo_tables_cover_every_code(self):
        assert reasons.check(Repo(REPO)) == []


# ---------------------------------------------------------------------
# suppressions + baseline round-trip
# ---------------------------------------------------------------------
class TestSuppressionAndBaseline:
    BAD = """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                # guarded-by: _lock: n
                self.n = 0

            def bad(self):
                self.n += 1
    """

    def test_trailing_and_standalone_suppressions(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": self.BAD.replace(
            "self.n += 1",
            "self.n += 1  # lint: disable=CTA001 -- test reason")})
        assert guarded.check(repo) == []
        repo = _mini_repo(tmp_path / "b", {"m.py": self.BAD.replace(
            "        self.n += 1",
            "        # lint: disable=CTA001 -- test reason\n"
            "        self.n += 1")})
        assert guarded.check(repo) == []

    def test_wrong_code_does_not_suppress(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": self.BAD.replace(
            "self.n += 1",
            "self.n += 1  # lint: disable=CTA003 -- wrong code")})
        assert [f.code for f in guarded.check(repo)] == ["CTA001"]

    def test_suppression_without_reason_is_config_error(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": self.BAD.replace(
            "self.n += 1",
            "self.n += 1  # lint: disable=CTA001")})
        ctx = repo.by_rel("cilium_tpu/m.py")
        assert [f.code for f in ctx.config_findings] == ["CTA000"]

    def test_baseline_round_trip(self, tmp_path):
        repo = _mini_repo(tmp_path, {"m.py": self.BAD})
        fs = guarded.check(repo)
        assert len(fs) == 1
        bl_path = str(tmp_path / "baseline.json")
        Baseline(bl_path).write(fs, repo)
        new, old = Baseline(bl_path).split(guarded.check(repo), repo)
        assert new == [] and len(old) == 1
        # the fingerprint keys on line CONTENT: drift survives...
        shifted = _mini_repo(tmp_path / "b", {
            "m.py": "\n" + textwrap.dedent(self.BAD)})
        new, old = Baseline(bl_path).split(
            guarded.check(shifted), shifted)
        assert new == [] and len(old) == 1
        # ...but a DIFFERENT violation is not grandfathered
        other = _mini_repo(tmp_path / "c", {"m.py": self.BAD.replace(
            "self.n += 1", "self.n -= 1")})
        new, old = Baseline(bl_path).split(
            guarded.check(other), other)
        assert len(new) == 1 and old == []


# ---------------------------------------------------------------------
# folded-in checkers (the former standalone scripts)
# ---------------------------------------------------------------------
class TestFoldedCheckers:
    def _relay_stub(self):
        return "\n".join(
            f'_R = "{n}"'
            for n in registry_lint.RELAY_REQUIRED_SERIES)

    def test_registry_scatter_flags_as_cta006(self, tmp_path):
        repo = _mini_repo(tmp_path, {
            "obs/registry.py": "\n".join(
                f'_R = "{n}"' for n in registry_lint.REQUIRED_SERIES),
            "obs/relay.py": self._relay_stub(),
            "scatter.py": """
                def render(v):
                    return ['# TYPE foo_total counter']
            """})
        fs = registry_lint.check(repo)
        assert [f.code for f in fs] == ["CTA006"]
        assert fs[0].path == "cilium_tpu/scatter.py"

    def test_registry_required_series_enforced(self, tmp_path):
        repo = _mini_repo(tmp_path, {
            "obs/registry.py": "# empty",
            "obs/relay.py": self._relay_stub()})
        fs = registry_lint.check(repo)
        assert len(fs) == len(registry_lint.REQUIRED_SERIES)
        assert {f.code for f in fs} == {"CTA006"}

    def test_relay_required_series_enforced(self, tmp_path):
        # the relay's scrape-plane floor (ISSUE 14): a relay module
        # that stops rendering scrape_ok/age/rtt fails CTA006
        repo = _mini_repo(tmp_path, {
            "obs/registry.py": "\n".join(
                f'_R = "{n}"' for n in registry_lint.REQUIRED_SERIES),
            "obs/relay.py": "# renders nothing"})
        fs = registry_lint.check(repo)
        assert len(fs) == len(registry_lint.RELAY_REQUIRED_SERIES)
        assert all("relay series" in f.message for f in fs)

    def test_sysdump_key_drift_flags_as_cta007(self, tmp_path):
        repo = _mini_repo(tmp_path, {
            "obs/flightrec.py": """
                SYSDUMP_REQUIRED_KEYS = (
                    "schema", "node", "taken-at", "trigger",
                    "incident", "incidents", "config", "vanished",
                )
            """,
            "agent/daemon.py": """
                class Daemon:
                    def _sysdump_collect(self):
                        out = {}
                        def section(name, fn):
                            out[name] = fn()
                        section("config", dict)
                        return out
            """})
        fs = sysdump_lint.check(repo)
        assert len(fs) == 1 and fs[0].code == "CTA007"
        assert "'vanished'" in fs[0].message

    def test_check_bundle_matches_old_script_contract(self, tmp_path):
        import json

        from cilium_tpu.obs.flightrec import (SYSDUMP_REQUIRED_KEYS,
                                              SYSDUMP_SCHEMA)

        good = {k: None for k in SYSDUMP_REQUIRED_KEYS}
        good["schema"] = SYSDUMP_SCHEMA
        p = tmp_path / "sysdump-x.json"
        p.write_text(json.dumps(good))
        assert sysdump_lint.check_bundle(str(p)) == []
        bad = dict(good)
        del bad["serving"]
        bad["schema"] = 99
        p.write_text(json.dumps(bad))
        problems = sysdump_lint.check_bundle(str(p))
        assert any("schema" in b for b in problems)
        assert any("'serving'" in b for b in problems)
        p.write_text("{not json")
        assert any("JSON" in b
                   for b in sysdump_lint.check_bundle(str(p)))

    def test_undeclared_cluster_drop_counter_flags_cta008(
            self, tmp_path):
        """ISSUE 8 satellite: an ``*_overflow``/``*_dropped``
        increment in cluster/ outside router.DROP_COUNTERS is an
        uncounted drop site."""
        repo = _mini_repo(tmp_path, {
            "cluster/router.py": """
                DROP_COUNTERS = ("router_overflow",)

                class R:
                    def drop(self, n):
                        self.router_overflow += n      # declared: ok
                        self.sneaky_dropped += n       # undeclared
            """,
            "obs/registry.py": '_S = (\n'
                '    "cilium_cluster_router_overflow_total",\n'
                '    "cilium_cluster_inflight_frames",\n'
                '    "cilium_cluster_acks_coalesced_total",\n'
                '    "cilium_cluster_window_stalls_total",\n'
                '    "cilium_cluster_crypto_rejected_total",\n'
                '    "cilium_cluster_crypto_replays_total",\n'
                '    "cilium_cluster_crypto_rotations_total",\n'
                '    "cilium_cluster_crypto_dropped_total")',
            "datapath/verdict.py": "REASON_CLUSTER_OVERFLOW = 12",
            "monitor/api.py": "DROP_REASON_NAMES = {12: 'x'}",
            "flow/flow.py": "DROP_REASON_DESC = {12: 'X'}",
            "flow/proto.py": "DROP_REASON_WIRE = {12: 0}",
        })
        fs = cluster_lint.check(repo)
        assert len(fs) == 1 and fs[0].code == "CTA008"
        assert "sneaky_dropped" in fs[0].message

    def test_missing_series_and_decode_flag_cta008(self, tmp_path):
        repo = _mini_repo(tmp_path, {
            "cluster/router.py":
                'DROP_COUNTERS = ("failover_dropped",)',
            "obs/registry.py": "# no series",
            "datapath/verdict.py": "REASON_CLUSTER_OVERFLOW = 12",
            "monitor/api.py": "DROP_REASON_NAMES = {12: 'x'}",
            "flow/flow.py": "DROP_REASON_DESC = {11: 'stale'}",
            "flow/proto.py": "DROP_REASON_WIRE = {12: 0}",
        })
        msgs = [f.message for f in cluster_lint.check(repo)]
        assert any("cilium_cluster_failover_dropped_total" in m
                   for m in msgs)
        assert any("DROP_REASON_DESC" in m for m in msgs)
        # the two present tables do NOT flag
        assert not any("DROP_REASON_NAMES" in m for m in msgs)

    def test_bench_schema_check_cta008(self, tmp_path):
        import json

        good = {k: 1 for k in cluster_lint.BENCH_CLUSTER_KEYS}
        good["schema"] = cluster_lint.BENCH_SCHEMA
        # v2: per-mode curves are schema-checked too
        good["modes"] = {
            m: {k: 1 for k in cluster_lint.BENCH_MODE_KEYS}
            for m in ("thread", "process")}
        p = tmp_path / "BENCH_cluster.json"
        p.write_text(json.dumps(good))
        assert cluster_lint.check_bench(str(p)) == []
        bad = dict(good)
        del bad["failover_blackout_ms"]
        bad["schema"] = "nope"
        bad["modes"] = {"thread": good["modes"]["thread"]}
        p.write_text(json.dumps(bad))
        problems = cluster_lint.check_bench(str(p))
        assert any("schema" in b for b in problems)
        assert any("failover_blackout_ms" in b for b in problems)
        assert any("modes" in b for b in problems)
        bad["modes"] = {
            "thread": good["modes"]["thread"],
            "process": {"scaling_n3": 1}}
        p.write_text(json.dumps(bad))
        problems = cluster_lint.check_bench(str(p))
        assert any("scaling_n2_pairs" in b for b in problems)
        p.write_text("{not json")
        assert any("JSON" in b
                   for b in cluster_lint.check_bench(str(p)))

    def test_shims_still_importable(self):
        """Old entry points survive as delegating shims — the
        contract test_obs_registry / test_flightrec import by path."""
        import importlib.util

        for name in ("check_metrics_registry", "check_sysdump_schema",
                     "check_cluster_ledger", "lint"):
            path = os.path.join(REPO, "scripts", f"{name}.py")
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            assert hasattr(mod, "main")


# ---------------------------------------------------------------------
# the live-repo gate (the acceptance criterion)
# ---------------------------------------------------------------------
# ---------------------------------------------------------------------
# CTA009 generation discipline (ISSUE 10)
# ---------------------------------------------------------------------
class TestGenerationDiscipline:
    _BAD = """
        class L:
            # active-tables: state, tensors, entries
            def __init__(self):
                self.state = None  # exempt

            def sneaky(self):
                self.state = 1
                self.tensors.verdict[:, 2] = 7
                out, self.state = f()
                self.entries.pop("x", None)
                del self.tensors
                self.other = 9
                v = self.state  # reads never flag

            # table-swap-ok: the sanctioned publish path
            def publish(self):
                self.state = 2
                self.entries["k"] = 3
        """

    def test_writes_outside_swap_ok_flag_with_lines(self, tmp_path):
        from cilium_tpu.analysis import generation

        repo = _mini_repo(tmp_path, {"m.py": self._BAD})
        fs = generation.check(repo)
        assert all(f.code == "CTA009" for f in fs)
        # assignment, subscript-chain store, tuple target, mutator
        # call, delete — one finding each, nothing else
        lines = sorted(f.line for f in fs)
        assert len(fs) == 5
        msgs = "\n".join(f.message for f in fs)
        assert "mutated via .pop()" in msgs
        assert "deleted" in msgs
        # sneaky() spans lines 8-14 of the dedented fixture
        assert lines == [8, 9, 10, 11, 12]

    def test_reasonless_swap_ok_is_a_finding_not_an_exemption(
            self, tmp_path):
        from cilium_tpu.analysis import generation

        repo = _mini_repo(tmp_path, {"m.py": """
            class L:
                # active-tables: state
                # table-swap-ok:
                def publish(self):
                    self.state = 2
            """})
        fs = generation.check(repo)
        assert any("needs a reason" in f.message for f in fs)
        assert any("without a" in f.message for f in fs)

    def test_suppression_silences(self, tmp_path):
        from cilium_tpu.analysis import generation

        repo = _mini_repo(tmp_path, {"m.py": """
            class L:
                # active-tables: state
                def hot(self):
                    self.state = 1  # lint: disable=CTA009 -- test fixture
            """})
        assert generation.check(repo) == []

    def test_nested_closure_inherits_the_builder_exemption(
            self, tmp_path):
        from cilium_tpu.analysis import generation

        repo = _mini_repo(tmp_path, {"m.py": """
            class L:
                # active-tables: tensors
                # table-swap-ok: builder -- mirrors painted post-flip
                def patch(self):
                    def mirrors():
                        self.tensors.verdict[:, 1] = 0
                    return mirrors
            """})
        assert generation.check(repo) == []

    def test_loader_annotation_presence_floor(self, tmp_path):
        """Deleting the loader's active-tables declarations (or the
        annotated _publish_tables helper) fails tier-1 — the CTA002
        tentpole-annotation idiom for the churn plane."""
        from cilium_tpu.analysis import generation

        real = open(os.path.join(
            REPO, "cilium_tpu/datapath/loader.py")).read()
        stripped = "\n".join(
            ln for ln in real.splitlines()
            if "active-tables:" not in ln)
        repo = _mini_repo(tmp_path,
                          {"datapath/loader.py": stripped})
        msgs = [f.message for f in generation.check(repo)]
        assert any("declares `state`" in m for m in msgs)
        assert any("declares `oracle`" in m for m in msgs)
        # ...and the real tree keeps all three anchors
        assert not any(
            "active-tables" in f.message
            or "_publish_tables" in f.message
            for f in generation.check(Repo(REPO)))

    def test_bench_schema_floor(self, tmp_path):
        import json

        from cilium_tpu.analysis.generation import (BENCH_CHURN_KEYS,
                                                    BENCH_SCHEMA,
                                                    check_bench)

        p = tmp_path / "BENCH_churn.json"
        good = {k: 0 for k in BENCH_CHURN_KEYS}
        good["schema"] = BENCH_SCHEMA
        p.write_text(json.dumps(good))
        assert check_bench(str(p)) == []
        bad = dict(good)
        del bad["swap_stall_p99_us"]
        bad["schema"] = "bench-churn-v0"
        p.write_text(json.dumps(bad))
        msgs = check_bench(str(p))
        assert any("swap_stall_p99_us" in m for m in msgs)
        assert any("bench-churn-v0" in m for m in msgs)


class TestLiveRepo:
    def test_analysis_clean_and_fast(self):
        """`python -m cilium_tpu.analysis` exits 0 on the repo: zero
        unsuppressed, non-baselined findings, in well under the 10s
        budget that keeps it tier-1."""
        result = run_analysis()
        assert result["findings"] == [], "\n".join(
            f.render() for f in result["findings"])
        assert result["elapsed-s"] < 10.0
        assert result["files"] > 100

    def test_seeded_violation_is_caught_end_to_end(self, tmp_path):
        """The negative control for the gate above: the SAME driver
        over the same tree plus one drain-thread decode call must
        come back dirty (so 'clean' means checked, not skipped)."""
        import shutil

        dst = tmp_path / "cilium_tpu"
        shutil.copytree(os.path.join(REPO, "cilium_tpu"), dst,
                        ignore=shutil.ignore_patterns("__pycache__"))
        daemon = dst / "agent" / "daemon.py"
        src = daemon.read_text()
        marker = ('window, s["ring"] = s["drainer"].swap_window('
                  's["ring"], on_cold)')
        assert marker in src
        src = src.replace(marker, marker + """
        from ..monitor.api import decode_ring_rows
        decode_ring_rows(None, None, None, 0.0)""")
        daemon.write_text(src)
        result = run_analysis(root=str(tmp_path))
        assert any(f.code == "CTA002"
                   and "decode_ring_rows" in f.message
                   for f in result["findings"])


# ---------------------------------------------------------------------
# regression tests for analyzer-surfaced fixes
# ---------------------------------------------------------------------
class TestSurfacedFixRegressions:
    def test_observer_server_status_is_locked_and_preferred(self):
        import numpy as np

        from cilium_tpu.core.packets import N_COLS
        from cilium_tpu.flow.observer import Observer
        from cilium_tpu.monitor.api import synth_drop_batch

        obs = Observer(capacity=8)
        obs.consume(synth_drop_batch(
            np.zeros((3, N_COLS), dtype=np.uint32), 1, 1.0))
        st = obs.server_status()
        assert st == {"num_flows": 3, "seen_flows": 3,
                      "max_flows": 8}

    def test_analytics_stats_inside_snapshot_does_not_deadlock(self):
        """stats() now takes the aggregation lock; snapshot() must
        therefore read the ledger OUTSIDE its own locked region —
        this pins the non-reentrant-deadlock fix."""
        import threading

        from cilium_tpu.obs.analytics import FlowAnalytics

        fa = FlowAnalytics(window_s=0.05, retention=2)
        out = {}

        def go():
            out["snap"] = fa.snapshot()

        t = threading.Thread(target=go, daemon=True)
        t.start()
        t.join(timeout=10.0)
        assert not t.is_alive(), "snapshot() deadlocked against stats()"
        assert out["snap"]["ledger"]["batches-submitted"] == 0

    def test_monitor_lost_counts_stay_exact_with_broken_consumer(self):
        import numpy as np

        from cilium_tpu.core.packets import N_COLS
        from cilium_tpu.monitor.agent import MonitorAgent
        from cilium_tpu.monitor.api import synth_drop_batch

        agent = MonitorAgent()

        def broken(batch):
            raise RuntimeError("boom")

        agent.register("broken", broken)
        batch = synth_drop_batch(
            np.zeros((5, N_COLS), dtype=np.uint32), 1, 1.0)
        agent.publish(batch)
        agent.publish(batch)
        assert agent.lost_count("broken") == 10

    def test_ingress_pending_property_still_tracks(self):
        import numpy as np

        from cilium_tpu.core.packets import N_COLS
        from cilium_tpu.serving.ingress import IngressQueue

        q = IngressQueue(16)
        q.offer(np.ones((4, N_COLS), dtype=np.uint32))
        assert q.pending == 4
        rows, _ = q.take(4)
        assert len(rows) == 4 and q.pending == 0


# ---------------------------------------------------------------------
# CTA013 crypto-hygiene
# ---------------------------------------------------------------------
class TestCryptoHygiene:
    def test_key_material_in_sinks_flags(self, tmp_path):
        from cilium_tpu.analysis import crypto_lint

        repo = _mini_repo(tmp_path, {"m.py": """
            import json
            import logging

            log = logging.getLogger(__name__)

            def leak_log(kp):
                log.info("key is %s", kp.private)

            def leak_incident(rec, ch):
                rec.record_incident("x", {"k": ch._send_key})

            def leak_json(kp):
                return json.dumps({"private": kp.private.hex()})

            def leak_write(path, kp):
                with open(path, "wb") as f:
                    f.write(kp.private)
            """})
        found = crypto_lint.check(repo)
        msgs = "\n".join(f.message for f in found)
        assert len(found) == 4, found
        assert "log call" in msgs
        assert "incident payload" in msgs
        assert "serializer" in msgs
        assert "written/sent" in msgs

    def test_surface_functions_and_sealed_modules_flag(self, tmp_path):
        from cilium_tpu.analysis import crypto_lint

        repo = _mini_repo(tmp_path, {
            "w.py": """
            def _crypto_block(self):
                return {"epoch": self.ch.epoch,
                        "key": self.ch._recv_key.hex()}

            def my_sysdump_collect(self):
                return {"wg": self.kp.private}
            """,
            "obs/registry.py": """
            from ..encryption import NodeKeypair

            def series(kp):
                return kp.private
            """})
        found = crypto_lint.check(repo)
        assert len(found) == 4, found
        surfaces = [f for f in found if f.path == "cilium_tpu/w.py"]
        assert len(surfaces) == 2
        assert all("operator-visible surface" in f.message
                   for f in surfaces)
        sealed = [f for f in found
                  if f.path == "cilium_tpu/obs/registry.py"]
        assert len(sealed) == 2
        assert any("imports from the encryption" in f.message
                   for f in sealed)

    def test_counters_only_surfaces_and_suppression_pass(
            self, tmp_path):
        from cilium_tpu.analysis import crypto_lint

        repo = _mini_repo(tmp_path, {"m.py": """
            import logging

            log = logging.getLogger(__name__)

            def _crypto_block(self):
                ch = self._crypto
                return {"epoch": ch.epoch, "sealed": ch.sealed,
                        "rejected": ch.rejected}

            def fine(kp):
                # the PUBLIC key is exempt by design
                log.info("pub %s", kp.public.hex())

            def waived(kp):
                log.debug(
                    "dbg %s",
                    kp.private)  # lint: disable=CTA013 -- test rig
            """})
        assert crypto_lint.check(repo) == []

    def test_live_repo_is_clean(self):
        from cilium_tpu.analysis import crypto_lint

        assert [f.render()
                for f in crypto_lint.check(Repo(REPO))] == []
