#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cells, configurations and metrics are named in BENCHMARK.json at
the root of the checkout.  One process holds the chip for the whole
run: it builds the cell's deployment through the agent, warms every
shape the cell uses (set-up), offers the cell's traffic for
``--seconds`` (the window), then compares what the served path
produced with the plain reference (``reference.py``).

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``: each number
compared with the reference beside its limit.  The line before it is
the window's guards (compiles inside the window, the generator's lag
and its stalls with where each thread was, spans collected, conntrack
occupancy).  The checks are also the last
lines on stderr.

Without a TPU, or with fewer chips than the cell asks for, the run
exits 2 and prints no result.  ``--rehearse`` runs the whole path at
the configurations' tiny sizes (use JAX_PLATFORMS=cpu) and then fails
at the device check.  ``--control ct_hash`` puts the reference with a
broken conntrack guarantee in the program's place, to show that the
comparison fails.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def configure_environment(root: str, rehearse: bool = False) -> None:
    """Import JAX with the compile cache and the TPU runtime's logs
    inside the checkout, at fixed paths, whatever the caller's
    environment says: the program takes the cache directory the
    benchmark gives it.  A CPU rehearsal keeps no cache (its entries
    would sit beside the chip's in the same directory)."""
    cache = os.path.join(root, ".jax_cache")
    if rehearse:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.makedirs(cache, exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["TPU_LOG_DIR"] = os.path.join(root, ".bench_out",
                                             "tpu_logs")
    for p in (HERE, root):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on the CPU; fails at the device check")
    p.add_argument("--control", choices=("ct_hash",), default=None)
    args = p.parse_args(argv)

    configure_environment(ROOT, args.rehearse)
    from harness import (BenchError, device_info, find_cell, load_spec,
                         log, run_cell)

    try:
        cell = find_cell(load_spec(ROOT), args.workload)
    except BenchError as e:
        log(f"error: {e}")
        return 2
    dev = device_info()
    need = int(cell["chips"])
    if not args.rehearse and (dev["platform"] != "tpu"
                              or dev["count"] < need):
        log(f"error: this cell needs {need} TPU chip(s); JAX found "
            f"{dev['count']} {dev['platform']} device(s)")
        return 2
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START, tiny=args.rehearse,
                       control=args.control, root=ROOT)
    except BenchError as e:
        log(f"error: {e}")
        return 1
    print(json.dumps({"guards": out["guards"]}), flush=True)
    for name, (value, limit) in out["checks"].items():
        log(f"check {name} {value} limit {limit}")
    if args.rehearse:
        log(json.dumps(out["result"]))
        log(f"rehearsal done: platform {dev['platform']!r}, not 'tpu'; "
            f"no result")
        return 1
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
