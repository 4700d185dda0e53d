"""``correct`` must come out false when the served path is wrong.

Each run drives the whole harness (deployment through the agent,
warm-up, the timed loop, the comparison) at the configurations' tiny
sizes on the CPU, skipping only the look for a chip:

- unbroken, every cell reads ``correct``;
- the control (the reference with conntrack keyed by a hash of the
  tuple in the program's place) reads not correct;
- each fault planted in the timed path reads not correct: a step that
  returns its state unchanged, half of every batch left out, and an
  answer (the identity of every event) altered where it is produced.
  The cells run on one chip, so there is no exchange between chips
  to leave out.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import time

import pytest

CELLS = ["east-west-10k.rr-closed"]
SECONDS = 1.5


def _run(cell, seed=2147483999, control=None):
    from harness import run_cell

    return run_cell(cell, seed, SECONDS, False, time.monotonic(),
                    tiny=True, control=control)


def _failed(out):
    return [k for k, (v, lim) in out["checks"].items() if v > lim]


@pytest.mark.parametrize("cell", CELLS)
def test_unbroken_run_is_correct(cell):
    out = _run(cell)
    assert out["result"]["correct"], out["checks"]
    assert out["guards"]["window_executables"] == 0
    assert out["guards"]["rows_compared"] > 10_000


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    out = _run(cell, control="ct_hash")
    assert not out["result"]["correct"]
    assert "counter_mismatch" in _failed(out)


def _stale_state(orig):
    def step(state, ring, hdr, now, batch_id, **kw):
        _, ring = orig(state, ring, hdr, now, batch_id, **kw)
        return state, ring
    return step


def _half_batch(orig):
    import jax.numpy as jnp

    def step(state, ring, hdr, now, batch_id, valid=None, **kw):
        keep = jnp.arange(hdr.shape[0]) < hdr.shape[0] // 2
        valid = keep if valid is None else valid & keep
        return orig(state, ring, hdr, now, batch_id, valid=valid, **kw)
    return step


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["stale_state", "half_batch"])
def test_broken_step_is_not_correct(cell, fault, monkeypatch):
    import jax

    from cilium_tpu.monitor import ring

    wrap = {"stale_state": _stale_state, "half_batch": _half_batch}[fault]
    monkeypatch.setattr(ring, "serve_step_jit", jax.jit(
        wrap(ring.serve_step), static_argnames=("trace_sample", "audit")))
    out = _run(cell)
    assert not out["result"]["correct"]
    assert "counter_mismatch" in _failed(out)


@pytest.fixture
def fresh_jit():
    """The patched function below is read when a step is traced, so
    compiled steps must not outlive the patch in either direction."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(cell, monkeypatch, fresh_jit):
    from cilium_tpu.datapath import verdict

    orig = verdict.datapath_step

    def step(*a, **kw):
        out, state = orig(*a, **kw)
        col = verdict.OUT_ID_ROW
        return out.at[:, col].set(out[:, col] ^ 1), state

    monkeypatch.setattr(verdict, "datapath_step", step)
    out = _run(cell)
    assert not out["result"]["correct"]
    assert "event_mismatch" in _failed(out)
