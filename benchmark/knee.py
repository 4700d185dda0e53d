#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate, below the
first rate that fails, at which a whole window of the cell's
``run_seconds`` sheds nothing, loses no event, offers its rate (the
generator keeps up) and ends with no larger admission backlog than it
began with: the mean queue depth of the window's last third is within
a quarter of the queue's depth of its first third's (the depth is
sampled every 20 ms).  A backlog that grows faster fills the queue,
and then sheds, within a window or two.

    python3 benchmark/knee.py --workload east-west-10k.crr-open \\
        --rates 140000,170000,200000 --seed 7

One process builds and warms the cell once (the agent's controllers,
conntrack GC among them, run throughout), then offers each rate in
ascending order for ``--seconds`` (default: ``run_seconds``) after one
second of ramp, draining between points, and stops at the first
failing point.  Each point is one JSON line on stdout; the last line
is the summary with the knee and 0.8 of it.  Confirm the knee with a
second seed (``--rates <knee>,<first failing rate>``), then write the
rate into the mix file by hand, as a number.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def point(s, rate: float, seconds: float, salt: int) -> dict:
    mix = {**s.mix, "rate_rows_per_s": rate}
    gen = s.generator(mix, salt=salt)
    time.sleep(1.0)
    a = s.snap()
    backlog = []
    while time.monotonic() < a["t"] + seconds:
        backlog.append(s.runtime.queue.pending)
        time.sleep(0.02)
    b = s.snap()
    gen.stop()
    third = max(len(backlog) // 3, 1)
    t0, t1 = a["t"], b["t"]
    spans = s.spans.array()
    lat = np.zeros(0)
    if len(spans):
        w = (spans[:, -1] >= t0) & (spans[:, -1] <= t1)
        lat = spans[w, -1] - spans[w, 0]
    due = np.frombuffer(gen.due, dtype=np.float64)
    lag = np.frombuffer(gen.lag, dtype=np.float64)[(due >= t0)
                                                   & (due <= t1)]
    out = {"rate": rate,
           "offered_rows_per_s": (b["submitted"] - a["submitted"])
           / (t1 - t0),
           "verdicts_per_s": (b["verdicts"] - a["verdicts"]) / (t1 - t0),
           "shed": b["shed"] - a["shed"],
           "lost": (b["ring_lost"] - a["ring_lost"]
                    + b["ev_dropped"] - a["ev_dropped"]),
           "backlog_first_third": float(np.mean(backlog[:third])),
           "backlog_last_third": float(np.mean(backlog[-third:])),
           "gen_lag_p99_ms": (float(np.quantile(lag, 0.99) * 1e3)
                              if len(lag) else None),
           "p50_ms": (float(np.quantile(lat, 0.5) * 1e3)
                      if len(lat) else None),
           "p99_ms": (float(np.quantile(lat, 0.99) * 1e3)
                      if len(lat) else None),
           "spans": int(len(lat)),
           "window_compiles": b["compiles"] - a["compiles"]}
    out["sustained"] = bool(
        out["shed"] == 0 and out["lost"] == 0
        and out["offered_rows_per_s"] >= 0.97 * rate
        and out["backlog_last_third"]
        <= out["backlog_first_third"] + s.queue_depth / 4)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated rows/s, ascending")
    p.add_argument("--seconds", type=float, default=None,
                   help="seconds per point (default: run_seconds)")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    sys.path.insert(0, HERE)
    from run import configure_environment

    configure_environment(ROOT, args.rehearse)
    from harness import Session, device_info, load_spec

    dev = device_info()
    if dev["platform"] != "tpu" and not args.rehearse:
        print(f"error: no TPU ({dev})", file=sys.stderr)
        return 2
    seconds = args.seconds or float(load_spec(ROOT)["run_seconds"])
    s = Session(args.workload, args.seed, tiny=args.rehearse, root=ROOT)
    if s.mix["loop"] != "open":
        print("error: the knee is swept on open-loop cells",
              file=sys.stderr)
        return 2
    points = []
    try:
        s.build()
        s.warm()
        print(json.dumps({"setup_s": time.monotonic() - T_START,
                          "device": dev}), flush=True)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            pt = point(s, rate, seconds, salt=i + 1)
            print(json.dumps(pt), flush=True)
            points.append(pt)
            if not pt["sustained"]:
                break
            s.wait_idle()
    finally:
        s.shutdown()
    ok = [pt["rate"] for pt in points if pt["sustained"]]
    knee = ok[-1] if ok else None
    print(json.dumps({"workload": args.workload, "knee_rows_per_s": knee,
                      "rate_rows_per_s": 0.8 * knee if knee else None,
                      "points": points}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
