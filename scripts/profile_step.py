#!/usr/bin/env python
"""Per-op device time of one fused datapath step at BASELINE config #3.

The trace behind PERF.md section 5: 10k identities, 64 rules, CT 2^21,
one wide ``datapath_step_jit`` on 2^18 rows of ``verdict_mix_traffic``.
Prints the mean wall time of 4 steps (``block_until_ready``), then
profiles one step and prints device time per XLA module and the
costliest XLA ops on device 0.

Usage (on the chip; on the CPU the numbers are not device metrics)::

    chiprun -- python scripts/profile_step.py

The raw trace goes to ``chiprun_out/profile_step/`` and is deleted
once read unless ``--keep`` is given.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--rows", type=int, default=1 << 18)
    p.add_argument("--top", type=int, default=40)
    p.add_argument("--keep", action="store_true")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileData

    from cilium_tpu.datapath.verdict import datapath_step_jit
    from cilium_tpu.infra.jaxcache import enable_compile_cache
    from cilium_tpu.testing.fixtures import build_world, verdict_mix_traffic

    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    w = build_world(10_000, 64, ct_capacity=1 << 21, n_v6=256)
    rng = np.random.default_rng(0)
    x = jnp.asarray(verdict_mix_traffic(w.pod_ips, w.pod_ips[0], rng,
                                        args.rows))
    st, now = w.state, 1000
    for _ in range(3):  # compile + CT warm-up
        out, st = datapath_step_jit(st, x, jnp.uint32(now))
        now += 1
    out.block_until_ready()
    t = time.perf_counter()
    for _ in range(4):
        out, st = datapath_step_jit(st, x, jnp.uint32(now))
        now += 1
    out.block_until_ready()
    print(f"step_ms {(time.perf_counter() - t) / 4 * 1e3}", flush=True)

    trace_dir = os.path.join(ROOT, "chiprun_out", "profile_step")
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    out, st = datapath_step_jit(st, x, jnp.uint32(now))
    out.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    ops, calls, modules = (collections.Counter(), collections.Counter(),
                           collections.Counter())
    for plane in ProfileData.from_file(path).planes:
        if "TPU:0" not in plane.name:
            continue
        for line in plane.lines:
            for ev in line.events:
                if line.name == "XLA Ops":
                    key = ev.name.split(" = ")[0]
                    ops[key] += ev.duration_ns
                    calls[key] += 1
                elif line.name == "XLA Modules":
                    modules[ev.name] += ev.duration_ns
    for name, ns in modules.most_common(5):
        print(f"module {ns / 1e6:10.3f} ms  {name[:100]}")
    print(f"ops total ms {sum(ops.values()) / 1e6}")
    for name, ns in ops.most_common(args.top):
        print(f"{ns / 1e6:10.3f} ms x{calls[name]:6d}  {name[:110]}")
    if not args.keep:
        shutil.rmtree(trace_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
