"""No fallback hides the device: a missing device raises or is named.

CPU tests of the repairs that keep a run from silently landing on the
wrong device (or on fewer devices than asked for), and of where the
entry points keep JAX's compile cache.
"""

import importlib.util
import os
import sys
from types import SimpleNamespace

import jax
import pytest

from cilium_tpu.agent.daemon import Daemon, DaemonConfig
from cilium_tpu.infra import jaxcache
from cilium_tpu.parallel import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_tpus(n):
    dev = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    return lambda *a, **k: [dev] * n


class TestMakeMesh:
    def test_raises_when_fewer_devices_than_asked(self):
        have = len(jax.devices())
        with pytest.raises(ValueError, match=f"needs {have + 1} devices"):
            make_mesh(have + 1)

    @pytest.mark.parametrize("n", [1, 4, None])
    def test_builds_the_mesh_it_was_asked_for(self, n):
        mesh = make_mesh(n)
        assert mesh.devices.size == (n or len(jax.devices()))


def test_status_names_the_device():
    d = Daemon(DaemonConfig(backend="tpu", ct_capacity=1 << 12))
    try:
        st = d.status()
    finally:
        d.shutdown()
    assert st["backend"] == "tpu"
    assert st["platform"] == jax.devices()[0].platform == "cpu"
    assert st["device-kind"] == jax.devices()[0].device_kind
    assert st["device-count"] == len(jax.devices())


class TestBenchFailsOnPhaseError:
    @pytest.fixture(scope="class")
    def bench(self):
        spec = importlib.util.spec_from_file_location(
            "bench_under_test", os.path.join(REPO, "bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_error_phase_makes_exit_code_nonzero(self, bench, capsys):
        rc = bench.main({"device": lambda: {"pps": 5, "detail": {}},
                         "stub": lambda: {"error": "boom"}})
        assert rc == 1
        assert '"failed_phases": ["stub"]' in capsys.readouterr().out

    def test_clean_phases_exit_zero(self, bench, capsys):
        assert bench.main({"stub": lambda: {"x": 1}}) == 0
        capsys.readouterr()

    def test_crashed_subprocess_is_an_error(self, bench):
        out = bench._last_json_line(
            [sys.executable, "-c", "print('{}'); raise SystemExit(3)"],
            timeout=60)
        assert out["error"].startswith("exit 3")


class TestCompileCacheDir:
    def test_env_var_wins(self):
        env = {jaxcache.ENV_VAR: "/somewhere/cache"}
        assert jaxcache.compile_cache_dir(env) == "/somewhere/cache"

    def test_default_is_the_fixed_in_repo_path(self):
        assert jaxcache.compile_cache_dir({}) == os.path.join(
            REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_enable_sets_jax_only_without_the_env_var(self, monkeypatch):
        before = jax.config.jax_compilation_cache_dir
        try:
            monkeypatch.setenv(jaxcache.ENV_VAR, "/from/env")
            jax.config.update("jax_compilation_cache_dir", None)
            assert jaxcache.enable_compile_cache() == "/from/env"
            assert jax.config.jax_compilation_cache_dir is None
            monkeypatch.delenv(jaxcache.ENV_VAR)
            assert jaxcache.enable_compile_cache() == jaxcache.DEFAULT_DIR
            assert (jax.config.jax_compilation_cache_dir
                    == jaxcache.DEFAULT_DIR)
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


def test_process_workers_refused_on_an_accelerator(monkeypatch):
    from cilium_tpu.cluster import process

    monkeypatch.setattr(jax, "devices", _fake_tpus(1))
    spawner = process.ProcessNodeSpawner()
    try:
        with pytest.raises(RuntimeError, match="need a chip each"):
            spawner.spawn("node0", DaemonConfig(backend="tpu"),
                          ("127.0.0.1", 1))
    finally:
        spawner._sock.close()
    # interpreter workers hold no device, so they are not refused
    process._refuse_accelerator_workers(DaemonConfig(backend="interpreter"))


def test_l7_matcher_runs_without_a_cpu_backend(monkeypatch):
    from cilium_tpu.proxy import proxy

    real = jax.devices

    def no_cpu(backend=None):
        if backend == "cpu":
            raise RuntimeError("Unknown backend cpu")
        return real()

    monkeypatch.setattr(jax, "devices", no_cpu)
    assert proxy._host_device() == real()[0]


def test_dryrun_refuses_a_virtual_mesh_on_an_accelerator(monkeypatch):
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__
    finally:
        sys.path.remove(REPO)
    monkeypatch.setattr(jax, "devices", _fake_tpus(1))
    with pytest.raises(RuntimeError, match="needs 4 devices"):
        __graft_entry__.dryrun_multichip(4)
