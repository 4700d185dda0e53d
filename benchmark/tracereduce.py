"""Reduce a profiler trace to the benchmark's device numbers.

A trace is read once (``load``) into plain event tuples, so the
reduction (``reduce``) runs the same on a live ``.xplane.pb`` and on
the small recorded event list in ``testdata/``.

- busy: the union of the intervals in which an XLA op ran on the
  device (the ``XLA Ops`` line of the device plane), clipped to the
  traced window;
- module time: device time per XLA module (the ``XLA Modules`` line),
  e.g. the ``jit_serve_step`` executions of the fused step;
- op time: device time per XLA op name, the costliest first;
- idle gaps: the complement of busy within the window, each labelled
  with the host activity that overlaps it most (``unattributed`` when
  no host event does).

Times are nanoseconds on the trace's clock.  The approach follows the
per-module and per-op sums of scripts/profile_step.py.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, int, int]  # (name, start_ns, duration_ns)


def load(trace_dir: str, device: str = "TPU:0") -> dict:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``:
    ``{"ops": [...], "modules": [...], "host": [...]}``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = {"ops": [], "modules": [], "host": []}
    for plane in ProfileData.from_file(paths[-1]).planes:
        on_device = device in plane.name
        on_host = plane.name.startswith("/host:")
        if not (on_device or on_host):
            continue
        for line in plane.lines:
            if on_device and line.name == "XLA Ops":
                dst = out["ops"]
            elif on_device and line.name == "XLA Modules":
                dst = out["modules"]
            elif on_host:
                dst = out["host"]
            else:
                continue
            for ev in line.events:
                dst.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _clip(events: List[Event], w0: int, w1: int):
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b


def _op_name(name: str) -> str:
    return name.split(" = ")[0]


def reduce(events: dict, window: Optional[Tuple[int, int]] = None,
           module_prefix: str = "jit_serve", top: int = 10) -> dict:
    """``events`` as :func:`load` returns it.  ``window`` is the traced
    interval on the trace's clock; by default the extent of all
    events."""
    ops, modules, host = events["ops"], events["modules"], events["host"]
    if window is None:
        every = ops + modules + host
        if not every:
            raise ValueError("empty trace")
        window = (min(s for _, s, _ in every),
                  max(s + d for _, s, d in every))
    w0, w1 = window
    busy_iv = _union([(a, b) for _, a, b in _clip(ops, w0, w1)])
    busy = sum(b - a for a, b in busy_iv)
    per_op: Dict[str, int] = collections.Counter()
    for name, a, b in _clip(ops, w0, w1):
        per_op[_op_name(name)] += b - a
    per_module: Dict[str, int] = collections.Counter()
    n_modules = 0
    for name, a, b in _clip(modules, w0, w1):
        per_module[name] += b - a
        n_modules += name.startswith(module_prefix)
    module_ns = sum(ns for name, ns in per_module.items()
                    if name.startswith(module_prefix))
    gaps, prev = [], w0
    for a, b in busy_iv:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if w1 > prev:
        gaps.append((prev, w1))
    # umbrella events that span most of the window (a profiler
    # session, a thread's whole life) say nothing about one gap
    host_iv = sorted((s, s + d, name) for name, s, d in host
                     if 0 < d < (w1 - w0) // 2)
    starts = [s for s, _, _ in host_iv]
    longest = max((e - s for s, e, _ in host_iv), default=0)
    by_label: Dict[str, int] = collections.Counter()
    for ga, gb in gaps:
        overlap: Dict[str, int] = collections.Counter()
        lo = bisect.bisect_left(starts, ga - longest)
        hi = bisect.bisect_left(starts, gb)
        for s, e, name in host_iv[lo:hi]:
            o = min(e, gb) - max(s, ga)
            if o > 0:
                overlap[name] += o
        label = (max(overlap, key=overlap.get) if overlap
                 else "unattributed")
        by_label[label] += gb - ga
    return {
        "window_ns": w1 - w0,
        "busy_ns": busy,
        "module_ns": module_ns,
        "module_count": n_modules,
        "modules": sorted(per_module.items(), key=lambda x: -x[1])[:top],
        "ops": sorted(per_op.items(), key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(by_label.items(), key=lambda x: -x[1])[:top],
        "n_gaps": len(gaps),
    }
