"""The trace reducer against hand-built events and a recorded trace.

``testdata/trace_rr.json.gz`` is the first 40 ms of a traced window of
``east-west-10k.rr-closed`` on one TPU v5 lite chip (``run.py --trace
1``), as ``tracereduce.load`` read it: device ops and modules of
``TPU:0`` and the host threads' events.
"""

import gzip
import json
import os

import numpy as np
import pytest

from tracereduce import reduce

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "trace_rr.json.gz")


def test_hand_built_events():
    ev = {"ops": [("fusion.1 = f()", 0, 10), ("fusion.2", 5, 10),
                  ("copy.3", 30, 5), ("fusion.1 = f()", 50, 20)],
          "modules": [("jit_serve_step(7)", 0, 40),
                      ("jit_ring_gather(2)", 45, 30)],
          "host": [("PjitFunction(serve_step)", 14, 10),
                   ("fetch", 36, 12)]}
    r = reduce(ev, window=(0, 100), module_prefix="jit_serve")
    assert r["busy_ns"] == 15 + 5 + 20
    assert r["window_ns"] == 100
    assert r["module_ns"] == 40 and r["module_count"] == 1
    assert dict(r["ops"])["fusion.1"] == 30
    gaps = dict(r["idle_gaps"])
    # gaps: [15,30) mostly under PjitFunction, [35,50) under fetch,
    # [70,100) under nothing
    assert gaps == {"PjitFunction(serve_step)": 15, "fetch": 15,
                    "unattributed": 30}
    assert sum(gaps.values()) == r["window_ns"] - r["busy_ns"]


def test_window_clips_events():
    ev = {"ops": [("a", -10, 20), ("b", 95, 10)], "modules": [],
          "host": []}
    r = reduce(ev, window=(0, 100))
    assert r["busy_ns"] == 10 + 5


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace_matches_a_timeline():
    with gzip.open(DATA, "rt") as f:
        data = json.load(f)
    w0, w1 = data["window"]
    ev = {k: [tuple(e) for e in data[k]] for k in ("ops", "modules",
                                                   "host")}
    assert ev["ops"] and ev["modules"]
    r = reduce(ev, window=(w0, w1))
    # independent reading: a 10 ns timeline of the window
    bins = np.zeros((w1 - w0) // 10 + 1, dtype=bool)
    for _, s, d in ev["ops"]:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            bins[(a - w0) // 10:(b - w0 + 9) // 10] = True
    timeline_ns = int(bins.sum()) * 10
    assert abs(timeline_ns - r["busy_ns"]) <= 20 * len(ev["ops"])
    assert 0 < r["busy_ns"] <= r["window_ns"]
    assert sum(ns for _, ns in r["idle_gaps"]) <= r["window_ns"] - \
        r["busy_ns"]
    assert r["module_ns"] > 0 and r["ops"]
