"""Split a traced window by the program's own names.

``tracereduce`` gives the fused step's device time as a whole and
labels each idle gap with whatever host event overlaps it most (often
a runtime internal).  The program names both levels itself, and this
module reads those names from the same ``.xplane.pb``:

- device stages: each stage of the fused step runs under a
  ``jax.named_scope`` (the program's ``obs.trace.DEVICE_STAGES``), so a
  device op's HLO ``op_name`` metadata names its stage.  A fusion
  carries the scope of its root op, so a fusion that spans two stages
  counts under one of them;
- host spans: the drain thread and the event-join worker open one
  ``TraceAnnotation`` per batch at each boundary (the program's
  ``obs.trace.PROFILE_SPANS``), with the batch's ring id as ``batch``.

:func:`split` then divides the ``jit_serve*`` modules' op time by
stage (``unscoped`` for ops under no stage), and each idle instant of
the device by the innermost drain-thread span open then (``outside``
where none is), with the event-join worker's spans as a second,
separate split of the same idle time.  Both divisions are exact: the
parts sum to the op time and to window minus busy.

The names are fixed here, as the yardstick; the benchmark's tests
check them against the program's tables.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, List, Optional, Tuple

from tracereduce import _clip, _union

DEVICE_STAGES = ("unpack", "ipcache", "ct_lookup", "policy",
                 "ct_update", "metricsmap", "ring")
DRAIN_SPANS = ("assemble", "idle", "drain_tick", "h2d", "lock_wait",
               "dispatch")
WORKER_SPANS = ("fetch", "emit", "analytics")
# the stat of a TPU op's event metadata that carries its HLO op_name
OP_NAME_STAT = "tf_op"

# (name, start_ns, duration_ns, thread, batch)
Span = Tuple[str, int, int, int, int]


def scope_of(op_name: str) -> Optional[str]:
    """The innermost device stage in an HLO ``op_name`` path."""
    found = [p for p in op_name.split("/") if p in DEVICE_STAGES]
    return found[-1] if found else None


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _op_names(path: str, device: str) -> Dict[str, str]:
    """Op event name -> HLO op_name, from the event metadata of the
    device plane (``jax.profiler.ProfileData`` shows event stats
    only).  Decodes just what it needs of the XSpace proto
    (tsl/profiler/protobuf/xplane.proto): plane name (2), event
    metadata (4: name 2, display name 4, stats 5) and stat metadata
    (5: name 2); a stat's string is ``str_value`` (5) or, interned,
    the name of the stat metadata its ``ref_value`` (7) points at."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, stat_names, metas = "", {}, []
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
                if device not in name:
                    break
            elif f == 4:
                metas.append(v)
            elif f == 5:
                entry = dict(_fields(v))
                stat_names[entry.get(1, 0)] = bytes(
                    dict(_fields(entry.get(2, b""))).get(2, b"")).decode()
        if device not in name:
            continue
        out = {}
        for entry in metas:
            names, op_name = [], None
            for f, v in _fields(dict(_fields(entry)).get(2, b"")):
                if f in (2, 4):
                    names.append(bytes(v).decode())
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) != OP_NAME_STAT:
                        continue
                    if 5 in stat:
                        op_name = bytes(stat[5]).decode()
                    elif 7 in stat:
                        op_name = stat_names.get(stat[7])
            if op_name:
                out.update((n, op_name) for n in names if n)
        return out
    return {}


def load(trace_dir: str, device: str = "TPU:0") -> dict:
    """From the newest ``.xplane.pb`` under ``trace_dir``:
    ``{"op_scopes": [(stage or None, start, dur)], "spans": [(name,
    start, dur, thread, batch)]}``: the device's XLA ops in the order
    ``tracereduce.load`` gives them, and the program's host spans,
    ``thread`` numbering the host lines (one per thread)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    op_names = _op_names(paths[-1], device)
    names = set(DRAIN_SPANS) | set(WORKER_SPANS)
    out = {"op_scopes": [], "spans": []}
    thread = 0
    for plane in ProfileData.from_file(paths[-1]).planes:
        on_device = device in plane.name
        on_host = plane.name.startswith("/host:")
        for line in plane.lines:
            if on_device and line.name == "XLA Ops":
                for ev in line.events:
                    out["op_scopes"].append((
                        scope_of(op_names.get(ev.name, "")),
                        int(ev.start_ns), int(ev.duration_ns)))
            elif on_host:
                thread += 1
                for ev in line.events:
                    if ev.name not in names:
                        continue
                    batch = dict(ev.stats).get("batch")
                    if batch is None:
                        continue
                    out["spans"].append((ev.name, int(ev.start_ns),
                                         int(ev.duration_ns), thread,
                                         int(batch)))
    return out


def _innermost(gaps, spans, names) -> Dict[str, int]:
    """ns of ``gaps`` under each span of ``names``: at each instant
    the open span that started last (the innermost, as spans nest on
    a thread), ``outside`` where none is open."""
    ivs = sorted((s, s + d, n) for n, s, d, _t, _b in spans
                 if n in names and d > 0)
    starts = [s for s, _, _ in ivs]
    longest = max((e - s for s, e, _ in ivs), default=0)
    out: Dict[str, int] = collections.Counter()
    for ga, gb in gaps:
        lo = bisect.bisect_left(starts, ga - longest)
        hi = bisect.bisect_left(starts, gb)
        cand = [(s, e, n) for s, e, n in ivs[lo:hi] if e > ga]
        cuts = sorted({ga, gb} | {x for s, e, _ in cand for x in (s, e)
                                  if ga < x < gb})
        for a, b in zip(cuts, cuts[1:]):
            cover = [(s, n) for s, e, n in cand if s <= a and e >= b]
            out[max(cover)[1] if cover else "outside"] += b - a
    return dict(out)


def split(events: dict, window: Tuple[int, int],
          module_prefix: str = "jit_serve") -> dict:
    """``events``: :func:`tracereduce.load`'s dict with this module's
    :func:`load` merged in.  ``window`` as :func:`tracereduce.reduce`
    takes it.  Returns ``op_ns`` (op time inside the ``module_prefix``
    modules), ``device_stages`` (that time by stage, ``unscoped``
    where an op has no stage), ``idle_ns`` (window minus busy) and
    ``idle_spans``: ``{"drain": ..., "event_worker": ...}``, each the
    idle time by innermost span of that thread, ``outside`` where none
    is open."""
    w0, w1 = window
    mods = _union([(a, b) for name, a, b in _clip(events["modules"], w0, w1)
                   if name.startswith(module_prefix)])
    mod_starts = [a for a, _ in mods]
    stages: Dict[str, int] = collections.Counter()
    for scope, s, d in events["op_scopes"]:
        a, b = max(s, w0), min(s + d, w1)
        if b <= a:
            continue
        i = bisect.bisect_right(mod_starts, a) - 1
        if i >= 0 and a < mods[i][1]:
            stages[scope or "unscoped"] += b - a
    busy_iv = _union([(a, b) for _, a, b in _clip(events["ops"], w0, w1)])
    gaps, prev = [], w0
    for a, b in busy_iv:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if w1 > prev:
        gaps.append((prev, w1))
    spans: List[Span] = events["spans"]
    return {
        "op_ns": sum(stages.values()),
        "device_stages": dict(stages),
        "idle_ns": sum(b - a for a, b in gaps),
        "idle_spans": {"drain": _innermost(gaps, spans, DRAIN_SPANS),
                       "event_worker": _innermost(gaps, spans,
                                                  WORKER_SPANS)},
    }
