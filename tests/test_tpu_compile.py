"""The main path compiles for a TPU v5e: one chip and a 2x2 mesh.

A chip is described through ``jax.experimental.topologies``, not
attached, so these run here under JAX_PLATFORMS=cpu: the TPU compiler
refuses what the chip would refuse (a program that does not fit HBM, a
sharding it cannot partition) at no chip time.  Nothing runs, so they
say nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.  Keep these tests in this one file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from cilium_tpu.agent.daemon import DaemonConfig
from cilium_tpu.core.packets import N_COLS, PACKED_COLS
from cilium_tpu.datapath.verdict import datapath_step_packed_jit
from cilium_tpu.monitor.ring import EventRing, serve_step_packed_jit
from cilium_tpu.parallel.mesh import make_sharded_serve_step
from cilium_tpu.testing.fixtures import build_world

HBM_BYTES = 16 << 30  # one v5e chip
TOP_RUNG = DaemonConfig.serving_bucket_ladder[-1]
RING_CAPACITY = 1 << 15  # Daemon.start_serving's default
SHARD_HEADROOM = 2  # Daemon.start_serving's default


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def state_shapes():
    """BASELINE config #3: 10k identities, 64 rules, CT 2^21."""
    world = build_world(n_identities=10_000, n_rules=64,
                        ct_capacity=1 << 21)
    return world.state


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda x: _spec(x, sharding), tree)


def _scalar(sharding, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct((), dtype, sharding=sharding)


def _check(compiled):
    """Fits one chip's HBM, and no gather became a serial loop: the TPU
    lowers a partial-row gather (``table[s, :k]``) to a ``while`` over
    the rows, which made the fused step 5.8x slower on the chip."""
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, mem
    loops = [line for line in compiled.as_text().splitlines()
             if " while(" in line and "gather" in line]
    assert not loops, loops[:2]


def test_packed_datapath_step_top_rung(one_chip, state_shapes):
    compiled = datapath_step_packed_jit.lower(
        _on(state_shapes, one_chip),
        jax.ShapeDtypeStruct((TOP_RUNG, PACKED_COLS), jnp.uint32,
                             sharding=one_chip),
        _scalar(one_chip), _scalar(one_chip), _scalar(one_chip),
        jax.ShapeDtypeStruct((TOP_RUNG,), jnp.bool_, sharding=one_chip),
    ).compile()
    _check(compiled)


def test_fused_serve_step_top_rung(one_chip, state_shapes):
    ring = EventRing.create(RING_CAPACITY)
    compiled = serve_step_packed_jit.lower(
        _on(state_shapes, one_chip), _on(ring, one_chip),
        jax.ShapeDtypeStruct((TOP_RUNG, PACKED_COLS), jnp.uint32,
                             sharding=one_chip),
        _scalar(one_chip), _scalar(one_chip), _scalar(one_chip),
        _scalar(one_chip),
        valid=jax.ShapeDtypeStruct((TOP_RUNG,), jnp.bool_,
                                   sharding=one_chip),
        proxy_ports=jax.ShapeDtypeStruct((1,), jnp.uint32,
                                         sharding=one_chip),
    ).compile()
    _check(compiled)


def test_sharded_serve_step_2x2(topo, state_shapes):
    n = len(topo.devices)
    mesh = Mesh(np.asarray(topo.devices).reshape(n), ("data",))
    repl, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    st = state_shapes
    state = type(st)(
        policy=_on(st.policy, repl), ipcache=_on(st.ipcache, repl),
        ct=type(st.ct)(table=_spec(st.ct.table, rows),
                       fp=_spec(st.ct.fp, rows),
                       dropped=_spec(st.ct.dropped, repl)),
        metrics=_spec(st.metrics, repl))
    ring = EventRing.create(RING_CAPACITY)
    ring = EventRing(
        buf=jax.ShapeDtypeStruct((n * RING_CAPACITY,) + ring.buf.shape[1:],
                                 ring.buf.dtype, sharding=rows),
        cursor=jax.ShapeDtypeStruct((n, 2), jnp.uint32, sharding=rows))
    routed = SHARD_HEADROOM * TOP_RUNG  # n blocks of headroom*rung/n
    step = make_sharded_serve_step(mesh)
    compiled = step.lower(
        state, ring,
        jax.ShapeDtypeStruct((routed, N_COLS), jnp.uint32, sharding=rows),
        _scalar(repl), _scalar(repl),
        jax.ShapeDtypeStruct((routed,), jnp.bool_, sharding=rows),
        jax.ShapeDtypeStruct((1,), jnp.uint32, sharding=repl),
    ).compile()
    _check(compiled)
    assert "all-reduce" in compiled.as_text()  # the psum-ed counters
