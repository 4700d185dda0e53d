"""Divergence gate at the BASELINE north-star scale.

BASELINE.md: "verdict divergence <=1% on a 10k-identity policy set" —
gated here at 0%: >=100k randomized packets through the 10k-identity
world (build_world), covering /32 ipcache hits, the 192.168/16 CIDR
range, world fallback, port-range allows, the deny rule, the L7
redirect, ICMP, OTHER-proto traffic, egress DNS, CT churn across the
SYN/EST/CLOSING lifecycle, and interleaved GC sweeps on both sides.
"""

import jax.numpy as jnp
import numpy as np

from cilium_tpu.core.packets import HeaderBatch
from cilium_tpu.datapath import datapath_step_jit
from cilium_tpu.datapath.conntrack import ct_gc_jit
from cilium_tpu.datapath.verdict import DatapathState
from cilium_tpu.testing import OracleDatapath
from cilium_tpu.testing.oracle import count_divergent
from cilium_tpu.testing.fixtures import build_world, verdict_mix_traffic

N_IDENTITIES = 10_000
BATCH = 4096
N_BATCHES = 25  # 102,400 packets total


def test_10k_identity_divergence_gate():
    world = build_world(n_identities=N_IDENTITIES, n_rules=64,
                        ct_capacity=1 << 16)
    oracle = OracleDatapath({0: world.policies[0]}, world.ipcache)
    row_to_numeric = world.row_map.numeric_array()
    state = world.state
    rng = np.random.default_rng(20260729)
    now = 1000
    total = 0
    n_div = 0
    for b in range(N_BATCHES):
        data = verdict_mix_traffic(world.pod_ips, world.pod_ips[0],
                                   rng, BATCH)
        out, state = datapath_step_jit(state, jnp.asarray(data),
                                       jnp.uint32(now))
        want = oracle.step(HeaderBatch(data), now)
        bad, first = count_divergent(np.asarray(out), want, row_to_numeric)
        for i in first:
            print(f"DIVERGE batch {b} pkt {i}: "
                  f"{HeaderBatch(data).describe(i)}\n  got  "
                  f"{np.asarray(out)[i, :6]}\n  want {want[i]}")
        n_div += bad
        total += len(want)
        # clock advance: occasionally jump past the SYN lifetime so
        # half-open flows expire; GC both sides in lockstep
        if b % 7 == 6:
            now += 70
            ct, _n = ct_gc_jit(state.ct, jnp.uint32(now))
            state = DatapathState(policy=state.policy,
                                  ipcache=state.ipcache, ct=ct,
                                  metrics=state.metrics)
            oracle.gc(now)
        else:
            now += int(rng.integers(1, 30))
    assert total >= 100_000
    assert n_div == 0, f"{n_div}/{total} packets diverged"
