"""Named device stages of the fused serving step.

Every serve entry point (wide, packed, superbatch) goes through
``datapath_step`` and ``ring_append``, whose stages run under
``jax.named_scope`` with the names of ``obs.trace.DEVICE_STAGES``, so a
profiler trace of the chip can split the step's device time by stage.
A scope changes only op metadata: the verdicts, the CT state and the
event ring stay bit-identical to the unscoped program (the pinned
digests below were taken from it).
"""

from __future__ import annotations

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cilium_tpu.core.packets import pack_rows
from cilium_tpu.datapath import datapath_step_jit
from cilium_tpu.monitor.ring import (EventRing, serve_step_jit,
                                     serve_step_packed_jit,
                                     serve_superbatch_jit)
from cilium_tpu.obs.trace import DEVICE_STAGES, SCOPE_UNPACK
from cilium_tpu.testing.fixtures import bench_traffic, build_world

N = 256
NOW = (100, 130)  # two steps: the second hits the CT entries of the first


def _world():
    return build_world(n_identities=64, n_rules=4, ct_capacity=1 << 10)


def _traffic(world):
    rng = np.random.default_rng(23)
    return bench_traffic(world, N, rng, new_flow_frac=0.5)


def _call(path, state, ring, hdr, now, bid):
    """(jitted step, args, kwargs) of one step of ``path``."""
    if path == "datapath":
        return datapath_step_jit, (state, jnp.asarray(hdr), now), {}
    if path == "wide":
        return serve_step_jit, (state, ring, jnp.asarray(hdr), now,
                                bid), {"trace_sample": 16}
    if path == "packed":
        return serve_step_packed_jit, (
            state, ring, jnp.asarray(pack_rows(hdr)), now, bid,
            jnp.uint32(0), jnp.uint32(0)), {"trace_sample": 16,
                                            "valid": jnp.ones(N, bool)}
    return serve_superbatch_jit, (
        state, ring, jnp.asarray(hdr.reshape(2, N // 2, -1)), now,
        bid), {"trace_sample": 16, "valid": jnp.ones((2, N // 2), bool)}


def _run(path: str):
    """Two seeded steps of ``path``; returns every output leaf."""
    world = _world()
    hdr = _traffic(world)
    state, ring = world.state, EventRing.create(1 << 10)
    leaves = []
    for i, now in enumerate(NOW):
        fn, args, kw = _call(path, state, ring, hdr, jnp.uint32(now),
                             jnp.uint32(i))
        if path == "datapath":
            out, state = fn(*args, **kw)
            leaves.append(out)
        else:
            state, ring = fn(*args, **kw)
    return leaves + jax.tree.leaves((state.ct, state.metrics, ring))


def _digest(leaves) -> str:
    h = hashlib.sha256()
    for x in leaves:
        a = np.asarray(x)
        h.update(str((a.dtype, a.shape)).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# sha256 of the outputs of the program before its stages were scoped
# (packed rows unpack to the wide rows, so both serve paths agree)
GOLDEN = {
    "datapath": "40a3201c0b1152043f7418ac5da4a2f6"
                "9c0900bcefb3073505e803a59209d041",
    "wide": "4fcd840371f3eade2ab42f650ab9e69a"
            "464c16365db71cf53b9399a5e767c87a",
    "packed": "4fcd840371f3eade2ab42f650ab9e69a"
              "464c16365db71cf53b9399a5e767c87a",
    "superbatch": "e159b1272bb06e9ae35b770cc68afdeb"
                  "c7d1041db97a14c31c08d5d5a685df0d",
}


@pytest.mark.parametrize("path", sorted(GOLDEN))
def test_scoped_step_matches_pinned_golden(path):
    assert _digest(_run(path)) == GOLDEN[path]


@pytest.mark.parametrize("path", ["wide", "packed", "superbatch"])
def test_every_device_stage_names_ops_in_compiled_hlo(path):
    """Each serve entry point's compiled module carries every stage
    scope in its ops' ``op_name`` metadata (``unpack`` on the packed
    path only): the names a trace of the chip is split by."""
    world = _world()
    fn, args, kw = _call(path, world.state, EventRing.create(1 << 10),
                         _traffic(world), jnp.uint32(NOW[0]),
                         jnp.uint32(0))
    hlo = fn.lower(*args, **kw).compile().as_text()
    scopes = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo):
        scopes.update(name.split("/"))
    want = set(DEVICE_STAGES) - ({SCOPE_UNPACK} if path != "packed"
                                 else set())
    assert want <= scopes, sorted(want - scopes)
    if path != "packed":
        assert SCOPE_UNPACK not in scopes
