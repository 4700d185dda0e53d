#!/usr/bin/env python
"""Prove that the served verdict path runs on a TPU chip.

    python chip_smoke.py               one chip (what the driver runs)
    python chip_smoke.py --four-chips  sharded serving on a 2x2 host
    python chip_smoke.py --tiny        CPU rehearsal at a tiny size

One process holds the chip for the whole run.  Every phase fails hard:
a failed check exits non-zero and prints no result.  Earlier stdout
lines are one JSON object per phase; the last line, printed only when
every phase passed on a TPU, is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Phases of the default run, at BASELINE.md config #3 (10k identities
with /32 ipcache entries, the 64-rule db policy, CT 2^21):

- device: the platform must be tpu (no CPU fallback); the native
  ingest parser and native crypto must load.
- fused: 2^18-row steps of ``datapath_step_jit`` and of the packed
  step, with CT GC between steps, compared row by row with
  ``OracleDatapath`` (0 divergent rows allowed); first-call and
  steady step times.
- served: a ``backend="tpu"`` Daemon serves seeded bursts that hit
  every rung of the default ladder through ``start_serving`` /
  ``submit`` / ``stop_serving``, all on their defaults.  The serving
  ledger must be exact with nothing lost, each (rung, mode) must
  compile once, and the per-reason counters must equal a
  ``backend="interpreter"`` daemon's fed the same rows.

The traffic is the verdict-class coverage mix of
tests/test_divergence_at_scale.py (``verdict_mix_traffic``), not a
model of any deployment's traffic: about 84% of its rows are
default-deny drops, and every drop is a ring event, so it is the
event plane's worst case.

``--four-chips`` runs only sharded serving (``make_mesh(4)``) on the
same deployment and traffic, against the same traffic on one chip of
that host: at the default ``shard_headroom`` route overflow must be 0,
and the ledgers and per-reason counters must be identical.

``--tiny`` runs every phase at a tiny size under JAX_PLATFORMS=cpu and
then fails at the device check: it exists only to rehearse the script
without a chip.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DB_IP = "10.0.0.2"

FULL = {"n_identities": 10_000, "n_rules": 64, "ct_capacity": 1 << 21,
        "n_v6": 256, "batch": 1 << 18, "wide_steps": 3,
        "timed_steps": 8, "ladder": None, "queue": None,
        "served_rounds": 2}
TINY = {"n_identities": 512, "n_rules": 16, "ct_capacity": 1 << 14,
        "n_v6": 16, "batch": 1 << 12, "wide_steps": 3,
        "timed_steps": 2, "ladder": (256, 1024), "queue": 4096,
        "served_rounds": 2}


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# -- device ------------------------------------------------------------
def device_phase(args) -> dict:
    import importlib.metadata

    import jax

    import cilium_tpu
    from cilium_tpu import native
    from cilium_tpu.infra.jaxcache import enable_compile_cache
    from cilium_tpu.native import crypto

    check(os.path.dirname(os.path.abspath(cilium_tpu.__file__))
          == os.path.join(HERE, "cilium_tpu"),
          f"cilium_tpu imported from {cilium_tpu.__file__}, not from "
          f"this checkout")
    cache = enable_compile_cache()
    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    t0 = time.perf_counter()
    ingest_ok, crypto_ok = native.available(), crypto.available()
    emit("device", **dev, jax=jax.__version__, libtpu=libtpu,
         native_ingest=ingest_ok, native_crypto=crypto_ok,
         native_build_s=round(time.perf_counter() - t0, 3),
         compile_cache=cache)
    if not args.tiny:
        check(dev["platform"] == "tpu",
              f"platform is {dev['platform']!r}, not 'tpu'")
    check(ingest_ok, "native ingest parser did not load")
    check(crypto_ok, "native crypto did not load")
    return dev


# -- fused step vs the oracle -------------------------------------------
def fused_phase(size: dict, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from cilium_tpu.core.packets import (HeaderBatch, pack_eligibility,
                                         pack_rows, unpack_rows_np)
    from cilium_tpu.datapath.conntrack import ct_gc_jit
    from cilium_tpu.datapath.verdict import (DatapathState,
                                             datapath_step_jit,
                                             datapath_step_packed_jit)
    from cilium_tpu.testing import OracleDatapath
    from cilium_tpu.testing.fixtures import build_world, verdict_mix_traffic
    from cilium_tpu.testing.oracle import count_divergent

    t0 = time.perf_counter()
    world = build_world(n_identities=size["n_identities"],
                        n_rules=size["n_rules"],
                        ct_capacity=size["ct_capacity"],
                        n_v6=size["n_v6"])
    build_s = time.perf_counter() - t0
    oracle = OracleDatapath({0: world.policies[0]}, world.ipcache)
    r2n = world.row_map.numeric_array()
    rng = np.random.default_rng(seed)
    batch, dst = size["batch"], world.pod_ips[0]
    state, now = world.state, 1000
    compared = divergent = 0
    first_call = {}

    def compare(kind, fn, wide_rows):
        nonlocal state, compared, divergent
        t = time.perf_counter()
        out, state = fn(state)
        out = np.asarray(out)
        first_call.setdefault(kind, time.perf_counter() - t)
        want = oracle.step(HeaderBatch(wide_rows), now)
        bad, first = count_divergent(out, want, r2n)
        if bad:
            emit("fused-divergence", step=kind, rows=bad, first=first)
        divergent += bad
        compared += len(want)

    def gc(advance):
        # clock jump past the SYN lifetime, GC on both sides in lockstep
        nonlocal state, now
        now += advance
        ct, _ = ct_gc_jit(state.ct, jnp.uint32(now))
        state = DatapathState(policy=state.policy, ipcache=state.ipcache,
                              ct=ct, metrics=state.metrics)
        oracle.gc(now)

    for step in range(size["wide_steps"]):
        rows = verdict_mix_traffic(world.pod_ips, dst, rng, batch)
        compare("wide", lambda st: datapath_step_jit(
            st, jnp.asarray(rows), jnp.uint32(now)), rows)
        gc(70 if step % 2 else int(rng.integers(1, 30)))
    # the packed 16 B/row ingest format carries one (ep, dir) stream
    rows = verdict_mix_traffic(world.pod_ips, dst, rng, batch,
                               egress_frac=0.0)
    ok, ep, dirn = pack_eligibility(rows)
    check(ok, "ingress-only traffic must be packable")
    packed = pack_rows(rows)
    compare("packed", lambda st: datapath_step_packed_jit(
        st, jnp.asarray(packed), jnp.uint32(now), ep, dirn),
        unpack_rows_np(packed, ep, dirn))

    # steady step time: pre-staged device batches, one host sync
    wide_dev = jnp.asarray(verdict_mix_traffic(world.pod_ips, dst, rng,
                                               batch))
    packed_dev = jnp.asarray(packed)
    steady = {}
    for kind, fn in (
            ("wide", lambda st, t: datapath_step_jit(st, wide_dev, t)),
            ("packed", lambda st, t: datapath_step_packed_jit(
                st, packed_dev, t, ep, dirn))):
        t = time.perf_counter()
        for _ in range(size["timed_steps"]):
            now += 1
            out, state = fn(state, jnp.uint32(now))
        out.block_until_ready()
        steady[kind] = (time.perf_counter() - t) / size["timed_steps"]
    emit("fused", identities=size["n_identities"], batch=batch,
         ct_capacity=size["ct_capacity"], compared_rows=compared,
         divergent_rows=divergent, world_build_s=round(build_s, 3),
         first_call_s={k: round(v, 3) for k, v in first_call.items()},
         steady_step_ms={k: round(v * 1e3, 3) for k, v in steady.items()})
    check(divergent == 0, f"{divergent}/{compared} rows diverge from "
                          f"the oracle")
    check(compared >= 100_000 or size is TINY,
          f"only {compared} rows compared")


# -- the served path -----------------------------------------------------
def build_daemon(backend: str, size: dict):
    """The BASELINE config #3 deployment through the agent's own entry
    points: svc identities with /32 ipcache entries, the db endpoint
    and its policy."""
    from cilium_tpu.agent.daemon import Daemon, DaemonConfig
    from cilium_tpu.labels import LabelSet
    from cilium_tpu.testing.fixtures import _pod_ip, baseline_rules

    cfg = {"backend": backend, "ct_capacity": size["ct_capacity"]}
    if size["ladder"]:
        cfg.update(serving_bucket_ladder=size["ladder"],
                   serving_queue_depth=size["queue"])
    d = Daemon(DaemonConfig(**cfg))
    pod_ips = []
    for i in range(size["n_identities"]):
        ident = d.allocator.allocate(LabelSet.parse(f"k8s:app=svc{i}",
                                                    "k8s:ns=default"))
        ip = _pod_ip(i + 256)
        d.upsert_ipcache(ip + "/32", ident.numeric_id)
        pod_ips.append(ip)
    db = d.add_endpoint("db", (DB_IP,), ["k8s:app=db"])
    d.policy_import(baseline_rules(size["n_identities"], size["n_rules"]))
    return d, db.id, pod_ips


def burst_traffic(size: dict, ladder, pod_ips, ep: int, seed: int):
    """Seeded chunks, each one burst: a full bucket and a 3/4-full one
    of every rung, ``served_rounds`` times.  Ingress only and no ICMP
    errors, so every row's verdict is independent of packet order and
    batch boundaries, and an interpreter fed the same rows must count
    the same per-reason totals."""
    import numpy as np

    from cilium_tpu.testing.fixtures import verdict_mix_traffic

    rng = np.random.default_rng(seed)
    sizes = [n for _ in range(size["served_rounds"]) for r in ladder
             for n in (r, r * 3 // 4)]
    return [verdict_mix_traffic(pod_ips, DB_IP, rng, n, ep=ep,
                                egress_frac=0.0, related_frac=0.0)
            for n in sizes]


def serve(d, chunks, **start_kw):
    """Serve ``chunks`` one burst at a time (each admitted whole into
    an empty queue, so nothing is shed).  Returns stop_serving() and
    the session's serving_stats() as it started."""
    d.start_serving(ingress=True, **start_kw)
    started = d.serving_stats()
    for rows in chunks:
        admitted = d.submit(rows)
        check(admitted == len(rows),
              f"{len(rows) - admitted} rows shed at admission")
        # pace bursts: the queue is taken and the event-join worker has
        # no window waiting, so neither admission nor the event plane
        # can shed
        deadline = time.monotonic() + 600
        while True:
            st = d.serving_stats()
            if not (st["queue-pending"]
                    or st["event-plane"]["windows-pending"]):
                break
            check(time.monotonic() < deadline,
                  "serving did not catch up within 600 s")
            time.sleep(0.002)
    return d.stop_serving(), started


def ledger(res: dict, n_rows: int) -> dict:
    fe = res["front-end"]
    out = {"submitted": fe["submitted"], "verdicts": fe["verdicts"],
           "shed": fe["shed"],
           "recovery-dropped": fe["fault-tolerance"]["recovery-dropped"],
           "lost": res["lost"],
           "events-dropped": res["event-plane"]["events-dropped"]}
    check(out["submitted"] == n_rows,
          f"submitted {out['submitted']} != {n_rows} rows offered")
    check(out["submitted"] == out["verdicts"] + out["shed"]
          + out["recovery-dropped"], f"serving ledger not exact: {out}")
    check(out["lost"] == 0 and out["events-dropped"] == 0,
          f"events lost on the ring or the event plane: {out}")
    return out


def reason_counts(metrics) -> dict:
    from cilium_tpu.monitor.api import DROP_REASON_NAMES

    names = {0: "Forwarded", **DROP_REASON_NAMES}
    return {names.get(r, str(r)): [int(v) for v in metrics[r]]
            for r in range(len(metrics)) if metrics[r].any()}


def served_phase(size: dict, seed: int) -> None:
    import numpy as np

    d, ep, pod_ips = build_daemon("tpu", size)
    try:
        ladder = d.config.serving_bucket_ladder
        chunks = burst_traffic(size, ladder, pod_ips, ep, seed)
        n_rows = sum(len(c) for c in chunks)
        t0 = time.perf_counter()
        res, started = serve(d, chunks, packed=True)
        serve_s = time.perf_counter() - t0
        book = ledger(res, n_rows)
        log = d.loader.compile_log.snapshot()
        got = d.loader.metrics()
    finally:
        d.shutdown()
    by_key = [(k["mode"], k["shape"][0], k["compiles"])
              for k in log["by-key"]]
    check(log["violations"] == 0 and all(c == 1 for *_, c in by_key),
          f"a (rung, mode) compiled twice: {by_key}")
    rungs = {(m, r) for m, r, _ in by_key}
    check({r for _, r in rungs} >= set(ladder),
          f"rungs compiled {sorted(rungs)} miss ladder {ladder}")

    di, ep_i, _ = build_daemon("interpreter", size)
    try:
        check(ep_i == ep, "endpoint ids differ between the daemons")
        for rows in chunks:
            di.process_batch(rows)
        want = di.loader.metrics()
    finally:
        di.shutdown()
    emit("served", rows=n_rows, ladder=list(ladder),
         ring_capacity=started["ring"]["capacity"], ledger=book,
         executables=sorted(rungs), serve_s=round(serve_s, 3),
         reasons=reason_counts(got),
         interpreter_equal=bool(np.array_equal(got, want)))
    check(np.array_equal(got, want),
          f"per-reason counters differ from the interpreter: "
          f"{reason_counts(got)} vs {reason_counts(want)}")


# -- four chips: sharded serving vs one chip ------------------------------
def four_chip_phase(size: dict, seed: int) -> None:
    import numpy as np

    from cilium_tpu.parallel import make_mesh

    runs = {}
    for name, kw in (("one-chip", {}),
                     ("sharded", {"mesh": make_mesh(4)})):
        d, ep, pod_ips = build_daemon("tpu", size)
        try:
            chunks = burst_traffic(size, d.config.serving_bucket_ladder,
                                   pod_ips, ep, seed)
            n_rows = sum(len(c) for c in chunks)
            t0 = time.perf_counter()
            res, started = serve(d, chunks, **kw)
            runs[name] = {"ledger": ledger(res, n_rows),
                          "started": started,
                          "metrics": d.loader.metrics(),
                          "serve_s": time.perf_counter() - t0,
                          "compile_ms": [
                              [e["mode"], *e["shape"], e["compile-ms"]]
                              for e in d.loader.compile_log.snapshot(
                                  limit=64)["events"]],
                          "route_overflow": res.get("route-overflow")}
        finally:
            d.shutdown()
    one, sh = runs["one-chip"], runs["sharded"]
    headroom = sh["started"]["shard-headroom"]
    emit("four-chips", rows=n_rows, shard_headroom=headroom,
         ring_capacity={k: r["started"]["ring"]["capacity"]
                        for k, r in runs.items()},
         route_overflow=sh["route_overflow"],
         ledger={k: r["ledger"] for k, r in runs.items()},
         serve_s={k: round(r["serve_s"], 3) for k, r in runs.items()},
         reasons=reason_counts(sh["metrics"]),
         compile_ms={k: r["compile_ms"] for k, r in runs.items()},
         counters_equal=bool(np.array_equal(one["metrics"],
                                            sh["metrics"])))
    check(sh["route_overflow"] == 0,
          f"route overflow {sh['route_overflow']} at headroom {headroom}")
    check(one["ledger"] == sh["ledger"],
          f"ledgers differ: {one['ledger']} vs {sh['ledger']}")
    check(np.array_equal(one["metrics"], sh["metrics"]),
          f"per-reason counters differ: {reason_counts(one['metrics'])} "
          f"vs {reason_counts(sh['metrics'])}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only sharded serving on a 4-chip mesh")
    p.add_argument("--tiny", action="store_true",
                   help="CPU rehearsal at a tiny size; fails at the "
                        "device check after running every phase")
    p.add_argument("--seed", type=int, default=20261015)
    args = p.parse_args(argv)
    size = TINY if args.tiny else FULL
    t0 = time.perf_counter()
    try:
        dev = device_phase(args)
        if args.four_chips:
            four_chip_phase(size, args.seed)
        else:
            fused_phase(size, args.seed)
            served_phase(size, args.seed + 1)
        check(dev["platform"] == "tpu",
              f"platform is {dev['platform']!r}, not 'tpu' (the --tiny "
              f"rehearsal ran every phase)")
        want = 4 if args.four_chips else 1
        check(dev["count"] == want,
              f"this run needs {want} device(s), found {dev['count']}")
    except SmokeFailure as e:
        print(json.dumps({"ok": False, "error": str(e)}), file=sys.stderr)
        return 1
    emit("done", wall_s=round(time.perf_counter() - t0, 3))
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
