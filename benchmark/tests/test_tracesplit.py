"""The stage/span split against hand-built events and recorded traces.

``testdata/trace_rr_spans.json.gz`` is 40 ms of a traced window of
``east-west-10k.rr-closed`` on one TPU v5 lite chip, from a program
whose fused step is scoped by stage and whose drain thread and
event-join worker open per-batch profiler spans: ``ops`` and
``modules`` as ``tracereduce.load`` reads them, ``op_scopes`` and
``spans`` as ``tracesplit.load`` does, and the ``window``.
"""

import gzip
import hashlib
import json
import os

import pytest

import tracesplit
from tracereduce import reduce
from tracesplit import split

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def _events(ops, modules=(), spans=()):
    """ops: (scope, start, dur); names follow the scope."""
    return {"ops": [(f"fusion.{i}", s, d) for i, (_, s, d)
                    in enumerate(ops)],
            "op_scopes": list(ops), "modules": list(modules),
            "host": [], "spans": list(spans)}


def test_stage_split_counts_op_time_of_serve_modules_only():
    ev = _events(
        ops=[("ipcache", 0, 10), ("ct_lookup", 10, 20), (None, 30, 5),
             ("ring", 35, 5), ("ct_lookup", 60, 10)],
        modules=[("jit_serve_step(1)", 0, 40),
                 ("jit_ring_gather(2)", 55, 20)])
    r = split(ev, (0, 100))
    assert r["device_stages"] == {"ipcache": 10, "ct_lookup": 20,
                                  "unscoped": 5, "ring": 5}
    assert r["op_ns"] == 40 == sum(r["device_stages"].values())


def test_idle_goes_to_the_innermost_drain_span():
    # busy [0,10) and [50,60); idle [10,50) and [60,100)
    ev = _events(
        ops=[("policy", 0, 10), ("policy", 50, 10)],
        modules=[("jit_serve_step(1)", 0, 60)],
        spans=[("dispatch", 5, 40, 1, 7),       # [5,45)
               ("lock_wait", 12, 8, 1, 7),      # [12,20) inside it
               ("h2d", 14, 2, 1, 7),            # [14,16) innermost
               ("assemble", 70, 10, 1, 8),      # [70,80)
               ("fetch", 0, 30, 2, 6),          # worker [0,30)
               ("emit", 90, 20, 2, 6)])         # worker [90,110)
    r = split(ev, (0, 100))
    assert r["idle_ns"] == 80
    drain = r["idle_spans"]["drain"]
    # dispatch idles [10,45) less the 8 ns its lock_wait child holds
    assert drain == {"dispatch": 35 - 8, "lock_wait": 6, "h2d": 2,
                     "outside": 5 + 10 + 20, "assemble": 10}, drain
    worker = r["idle_spans"]["event_worker"]
    assert worker == {"fetch": 20, "outside": 20 + 30, "emit": 10}
    for part in (drain, worker):
        assert sum(part.values()) == r["idle_ns"]


def test_scope_is_the_innermost_stage_of_the_op_name():
    sc = tracesplit.scope_of
    assert sc("jit(serve_step)/ct_update/jit(take_along_axis)/gather:") \
        == "ct_update"
    assert sc("jit(serve_superbatch)/while/body/ring/add:") == "ring"
    assert sc("jit(serve_step_packed)/unpack/ipcache/x") == "ipcache"
    assert sc("jit(serve_step)/sort") is None
    assert sc("") is None


def _pb(*fields) -> bytes:
    """Encode (field number, int | bytes | str) as a protobuf message."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_op_names_read_from_device_event_metadata(tmp_path):
    """The op_name of a device op rides its event metadata's ``tf_op``
    stat, as a string or interned as a reference to a stat metadata
    entry; other planes are skipped."""
    stat_md = [(5, _pb((1, 1), (2, _pb((1, 1), (2, "tf_op"))))),
               (5, _pb((1, 9), (2, _pb((1, 9), (2, "jit(s)/ring/add:")))))]
    event_md = [
        (4, _pb((1, 1), (2, _pb((1, 1), (2, "%fusion.1 = u32[8] f()"),
                                (4, "fusion.1"),
                                (5, _pb((1, 1), (5, "jit(s)/policy/gather:"))
                                 ))))),
        (4, _pb((1, 2), (2, _pb((1, 2), (2, "%fusion.2"),
                                (5, _pb((1, 1), (7, 9))))))),
        (4, _pb((1, 3), (2, _pb((1, 3), (2, "%copy.3")))))]
    host = _pb((1, 5), (2, "/host:CPU"), *event_md)
    dev = _pb((1, 6), (2, "/device:TPU:0"), *stat_md, *event_md)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, host), (1, dev)))
    names = tracesplit._op_names(str(path), "TPU:0")
    assert names == {"%fusion.1 = u32[8] f()": "jit(s)/policy/gather:",
                     "fusion.1": "jit(s)/policy/gather:",
                     "%fusion.2": "jit(s)/ring/add:"}
    assert {tracesplit.scope_of(v) for v in names.values()} == {
        "policy", "ring"}


def test_names_are_the_programs():
    from cilium_tpu.obs.trace import DEVICE_STAGES, PROFILE_SPANS

    assert tracesplit.DEVICE_STAGES == DEVICE_STAGES
    threads = {"drain": tracesplit.DRAIN_SPANS,
               "event-worker": tracesplit.WORKER_SPANS}
    assert {n: (t, ) for t, names in threads.items() for n in names} \
        == {n: (t, ) for n, (t, _iv) in PROFILE_SPANS.items()}


def test_existing_reduction_is_unchanged_on_the_recorded_trace():
    """``tracereduce.reduce`` on ``trace_rr.json.gz`` gives exactly
    the output it gave when the benchmark was accepted."""
    with gzip.open(os.path.join(DATA, "trace_rr.json.gz"), "rt") as f:
        data = json.load(f)
    ev = {k: [tuple(e) for e in data[k]] for k in ("ops", "modules",
                                                   "host")}
    r = reduce(ev, window=tuple(data["window"]))
    assert (r["window_ns"], r["busy_ns"], r["module_ns"],
            r["module_count"], r["n_gaps"]) == (40000000, 39976725,
                                                39981427, 2, 174)
    digest = hashlib.sha256(json.dumps(r, sort_keys=True).encode())
    assert digest.hexdigest() == ("80e423c1a636adb7416bb1e2a12b2ca6"
                                  "80f2c880f6e140d40bdcf8e98d811fd4")


SPANS = os.path.join(DATA, "trace_rr_spans.json.gz")


def test_recorded_split_is_exact():
    """On a recorded chip trace: stages plus ``unscoped`` are the op
    time of the ``jit_serve*`` executions, and each thread's idle
    split sums to window minus busy."""
    with gzip.open(SPANS, "rt") as f:
        data = json.load(f)
    w0, w1 = window = tuple(data["window"])
    ev = {k: [tuple(e) for e in data[k]]
          for k in ("ops", "modules", "op_scopes", "spans")}
    r = split(ev, window)
    st = r["device_stages"]
    assert {"ipcache", "ct_lookup", "policy", "ct_update", "ring",
            "unscoped"} == set(st)
    # independent reading: ops that start inside a serve execution
    serve = [(max(s, w0), min(s + d, w1)) for n, s, d in ev["modules"]
             if n.startswith("jit_serve")]
    serve_ns = 0
    for _scope, s, d in ev["op_scopes"]:
        a, b = max(s, w0), min(s + d, w1)
        if b > a and any(x <= a < y for x, y in serve):
            serve_ns += b - a
    assert sum(st.values()) == r["op_ns"] == serve_ns > 0
    busy = reduce({"ops": ev["ops"], "modules": ev["modules"],
                   "host": []}, window)["busy_ns"]
    assert r["idle_ns"] == w1 - w0 - busy > 0
    for part in r["idle_spans"].values():
        assert sum(part.values()) == r["idle_ns"]
    drain = r["idle_spans"]["drain"]
    assert {"drain_tick", "h2d"} <= set(drain)
    assert drain["outside"] < r["idle_ns"] / 3
