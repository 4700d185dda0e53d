"""One benchmark cell, run once: set-up, warm-up, the measured window,
the comparison with the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix or metric
lives in its own file, found by the name ``BENCHMARK.json`` gives:
``configs/<config>.json``, ``mixes/<traffic>.json`` and
``metrics/<metric>.py``; a mix names its ``shapes/<shape>.py`` and
``loops/<loop>.py``, a configuration its ``policies/<generator>.py``
(``byname.py``).  This module holds only what all cells share.

From the program it takes the system under test (``Daemon`` with its
controllers started, ``start_serving(ingress=True)`` and ``submit``),
its counters
(``serving_stats()``, the loader's per-reason metrics, the compile
log, the map-pressure sample), its span tracer (every committed span
is collected by wrapping the tracer's ``commit``) and its monitor
event stream (a consumer registered on the monitor agent).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback
from array import array
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 3.0


class BenchError(Exception):
    """The run cannot produce a result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- the specification ------------------------------------------------------
def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            cfg = [c for c in spec["configs"] if c["name"] == w["config"]]
            if len(cfg) != 1:
                raise BenchError(f"cell {name}: no configuration "
                                 f"{w['config']!r}")
            return {**w, "config_file": cfg[0]["file"]}
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(spec: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics the cell reports."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def metric_reader(name: str):
    import byname

    return byname.load("metrics", name).read


# -- what the run collects from the program -----------------------------------
class SpanLog:
    """Every span the program's tracer commits: its stage stamps."""

    def __init__(self, tracer):
        self.rows: List[tuple] = []
        orig = tracer.commit

        def commit(span, _orig=orig, _rows=self.rows):
            if not span.done:
                _rows.append(tuple(span.ts))
            _orig(span)

        tracer.commit = commit

    def array(self) -> np.ndarray:
        return np.asarray(self.rows, dtype=np.float64).reshape(
            len(self.rows), -1)


class EventLog:
    """Monitor consumer: keeps every published event batch."""

    def __init__(self):
        self.batches: list = []
        self.policy_verdicts = 0

    def __call__(self, batch) -> None:
        self.batches.append((batch.hdr, batch.identity, batch.msg_type,
                             batch.reason))
        self.policy_verdicts += int(np.count_nonzero(batch.msg_type == 9))


class CompileCounter:
    """Executables JAX builds or loads (backend compiles, cache loads
    included) while armed."""

    def __init__(self):
        import jax

        self.count = 0
        self.names: List[tuple] = []  # (monotonic end, function, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, seconds=0.0, **kw) -> None:
        if name == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.names.append((time.monotonic(), str(kw.get("fun_name")),
                               round(float(seconds), 3)))


class Generator(threading.Thread):
    """Offers the stream from ``pos`` by the mix's loop,
    ``loops/<loop>.py``: its ``run(gen)`` calls ``offer`` and ``idle``
    until ``stopping()``.  Records every chunk's due time and lag and
    every partial admission; ``beat`` is when the loop last ran."""

    def __init__(self, session, mix: dict, salt: int):
        super().__init__(name="bench-generator", daemon=True)
        self.s = session
        self.mix = mix
        self.salt = salt
        self.pos = session.pos
        self.due = array("d")
        self.lag = array("d")
        self.beat = time.monotonic()
        self.error: Optional[BaseException] = None
        self._stop_ev = threading.Event()

    def stop(self) -> None:
        self._stop_ev.set()
        self.join(timeout=60)
        if self.is_alive():
            raise BenchError("generator did not stop")
        if self.error is not None:
            raise BenchError(f"generator failed: {self.error!r}")
        self.s.pos = self.pos

    def stopping(self) -> bool:
        return self._stop_ev.is_set()

    def idle(self, seconds: float) -> None:
        time.sleep(seconds)
        self.beat = time.monotonic()

    def run(self) -> None:
        import byname

        try:
            byname.load("loops", self.mix["loop"]).run(self)
        except BaseException as e:  # noqa: BLE001 -- re-raised in stop()
            self.error = e

    def offer(self, n: int, due: Optional[float] = None) -> None:
        """Submit the next ``n`` rows; ``due`` (default: now) is their
        arrival stamp."""
        s = self.s
        rows = s.stream.rows(self.pos, n)
        now = time.monotonic()
        due = now if due is None else due
        acc = s.daemon.submit(rows, t=due)
        self.beat = time.monotonic()
        self.due.append(due)
        self.lag.append(now - due)
        if acc < n:
            s.partials.append((self.pos, n, acc))
        self.pos += n


class StallProbe(threading.Thread):
    """Watches the generator's beat.  When the loop has not run for
    ``after`` seconds it records the stall (start, length) and, for
    the first few, where every thread of the process was at that
    moment: what held the generator back."""

    def __init__(self, gen: Generator, after: float = 0.4, keep: int = 3):
        super().__init__(name="bench-stall-probe", daemon=True)
        self.gen, self.after, self.keep = gen, after, keep
        self.stalls: List[list] = []  # [start, seconds, stacks or None]
        self._stop_ev = threading.Event()

    def run(self) -> None:
        cur = None
        while not self._stop_ev.wait(0.02):
            beat = self.gen.beat
            quiet = time.monotonic() - beat
            if quiet < self.after:
                cur = None
                continue
            if cur is None or cur[0] != beat:
                stacks = None
                if sum(1 for x in self.stalls if x[2]) < self.keep:
                    names = {t.ident: t.name for t in threading.enumerate()}
                    stacks = {names.get(i, str(i)): [
                        f"{f.filename.rsplit('/', 2)[-1]}:{f.lineno} "
                        f"{f.name}" for f in traceback.extract_stack(fr)[-6:]]
                        for i, fr in sys._current_frames().items()}
                cur = [beat, quiet, stacks]
                self.stalls.append(cur)
            cur[1] = quiet

    def stop(self) -> None:
        self._stop_ev.set()
        self.join(timeout=5)


# -- one session: the deployment serving one mix ------------------------------
class Session:
    def __init__(self, cell_name: str, seed: int, tiny: bool = False,
                 root: str = ROOT):
        sys.path.insert(0, HERE)
        from deploy import load_config
        from traffic import load_mix

        self.root = root
        self.spec = load_spec(root)
        self.cell = find_cell(self.spec, cell_name)
        self.cfg = load_config(self.cell["config"], tiny,
                               os.path.join(root, self.cell["config_file"]))
        self.mix = load_mix(self.cell["traffic"], tiny)
        self.seed = int(seed)
        self.pos = 0  # next stream position
        self.partials: List[tuple] = []  # (start, rows, admitted)
        self.setup_partials: List[tuple] = []
        self.setup_rows = None
        self.daemon = None
        self.phase_s: Dict[str, float] = {}  # set-up seconds by phase

    def _phase(self, name: str, t: float) -> float:
        now = time.monotonic()
        self.phase_s[name] = round(now - t, 3)
        return now

    # -- set-up ---------------------------------------------------------
    def build(self) -> None:
        from deploy import build
        from traffic import Stream

        t = time.monotonic()
        self.dep = build(self.cfg)
        t = self._phase("deployment", t)
        d = self.daemon = self.dep.daemon
        self.stream = Stream(self.mix, self.dep, self.seed)
        self.events = EventLog()
        d.monitor.register("benchmark", self.events)
        d.start_serving(ingress=True,
                        trace_sample=int(self.cfg["trace_sample"]),
                        span_sample=int(self.mix["span_sample"]))
        self.runtime = d._serving["runtime"]
        self.spans = SpanLog(d._serving["tracer"])
        self.ladder = list(d.config.serving_bucket_ladder)
        self.queue_depth = int(d.config.serving_queue_depth)
        t = self._phase("start_serving", t)
        # the agent's background controllers, as a node runs them:
        # conntrack GC, map pressure, SLO sampling, ...  Each runs once
        # now, so what it compiles is compiled in set-up
        d.start()
        self._phase("controllers", t)

    def wait_idle(self, timeout: float = 600.0) -> None:
        """Until every admitted row has dispatched and its window has
        reached the event plane."""
        deadline = time.monotonic() + timeout
        while True:
            st = self.daemon.serving_stats()
            done = (st["verdicts"] + st["shed"]
                    + st["fault-tolerance"]["recovery-dropped"])
            if (st["queue-pending"] == 0 and done >= st["submitted"]
                    and st["event-plane"]["windows-pending"] == 0):
                return
            if time.monotonic() > deadline:
                raise BenchError("serving did not drain within "
                                 f"{timeout:.0f} s")
            time.sleep(0.005)

    def _piece(self, rows: np.ndarray, start: int, setup: bool) -> None:
        acc = self.daemon.submit(rows, t=time.monotonic())
        if acc < len(rows):
            (self.setup_partials if setup else self.partials).append(
                (start, len(rows), acc))
        self.wait_idle()

    def warm(self) -> None:
        """Every event-ring gather rung; one piece of state-free warm
        rows per ladder rung, each alone in the queue so the batcher
        forms exactly that bucket; then the mix's set-up traffic (the
        rr SYNs and the server's replies), in pieces the queue holds."""
        t = time.monotonic()
        self._warm_gathers()
        t = self._phase("gather_rungs", t)
        pieces, a = [], 0
        for r in self.ladder:
            pieces.append((f"rung_{r}", self.stream.warm_rows(a, r)))
            a += r
        step = self.queue_depth
        for name, rows in (("syn", self.stream.setup_rows()),
                           ("reply", self.stream.reply_rows())):
            pieces += [(name, rows[i:i + step])
                       for i in range(0, len(rows), step)]
        self.setup_rows = np.concatenate([p for _, p in pieces])
        a = 0
        for name, rows in pieces:
            self._piece(rows, a, setup=True)
            a += len(rows)
            t = self._phase(name, t)

    def _warm_gathers(self) -> None:
        import jax.numpy as jnp

        from cilium_tpu.monitor import ring as ringmod

        s = self.daemon._serving
        cap = int(s["ring_capacity"])
        buf = jnp.zeros_like(s["ring"].buf)
        starts = np.zeros(1, dtype=np.uint32)
        rung = min(ringmod.GATHER_MIN_RUNG, cap)
        while True:
            ringmod.ring_gather(buf, starts, rung, cap).block_until_ready()
            if rung >= cap:
                break
            rung = min(rung * 2, cap)

    # -- measurement ----------------------------------------------------
    def snap(self) -> dict:
        st = self.daemon.serving_stats()
        ft, ep = st["fault-tolerance"], st["event-plane"]
        return {"t": time.monotonic(), "submitted": st["submitted"],
                "shed": st["shed"], "verdicts": st["verdicts"],
                "padded": st["padded-rows"],
                "recovery": ft["recovery-dropped"],
                "restarts": ft["restarts"],
                "dispatch_timeouts": ft["dispatch-timeouts"],
                "dispatches": st["dispatch"]["dispatches"],
                "ring_lost": st["ring"]["lost"],
                "ev_dropped": ep["events-dropped"],
                "ev_joined": ep["events-joined"], "d2h": ep["d2h-bytes"],
                "policy_verdicts": self.events.policy_verdicts,
                "queue": st["queue-pending"],
                "compiles": st["compile"]["compiles"]}

    def generator(self, mix: Optional[dict] = None,
                  salt: int = 0) -> Generator:
        g = Generator(self, mix or self.mix, salt)
        g.start()
        return g

    # -- the end --------------------------------------------------------
    def finish(self) -> dict:
        d = self.daemon
        res = d.stop_serving()
        self.metrics = np.asarray(d.loader.metrics()).astype(np.int64)
        return res

    def shutdown(self) -> None:
        if self.daemon is not None:
            self.daemon.shutdown()
            self.daemon = None

    def admitted(self):
        """The admitted rows in admission order, block by block."""
        from traffic import BLOCK

        def cut(rows, lo, partials):
            keep = np.ones(len(rows), dtype=bool)
            for start, n, acc in partials:
                a, b = start + acc - lo, start + n - lo
                if b > 0 and a < len(rows):
                    keep[max(a, 0):min(b, len(rows))] = False
            return rows[keep]

        for a in range(0, len(self.setup_rows), BLOCK):
            yield cut(self.setup_rows[a:a + BLOCK], a, self.setup_partials)
        for a in range(0, self.pos, BLOCK):
            n = min(BLOCK, self.pos - a)
            yield cut(self.stream.rows(a, n), a, self.partials)

    def check(self, res: dict, control: Optional[str] = None) -> tuple:
        """Compare what the served path produced with the reference.
        Returns (checks, facts)."""
        from reference import (HOST_REASONS, MSG_TRACE, Reference,
                               event_keys, event_vals, multiset_mismatch)

        every = bool(self.cfg["every_verdict_is_an_event"])
        dep = self.dep
        kw = dict(rules=dep.rules, endpoints=dep.endpoints,
                  client_labels=dep.client_labels,
                  client_ips=dep.client_ips, client_ids=dep.client_ids,
                  every_event=every)
        ref = Reference(**kw)
        ctl = (Reference(control=control,
                         ct_hash_bits=int(self.cfg["control_ct_hash_bits"]),
                         **kw) if control else None)
        for rows in self.admitted():
            ref.feed(rows)
            if ctl is not None:
                ctl.feed(rows)
        if ctl is not None:
            got_counters = ctl.counters
            got_keys, got_vals = ctl.events()
            host_events = 0
        else:
            got_counters = np.zeros_like(ref.counters)
            m = self.metrics
            got_counters[:m.shape[0], :m.shape[1]] = m
            keys, vals, host_events = [], [], 0
            for hdr, ident, msg, reason in self.events.batches:
                host = np.isin(reason, HOST_REASONS)
                host_events += int(host.sum())
                keep = ~host if every else ~host & (msg != MSG_TRACE)
                keys.append(event_keys(hdr[keep, 3], hdr[keep, 7],
                                       hdr[keep, 8], hdr[keep, 9]))
                vals.append(event_vals(ident[keep], msg[keep],
                                       reason[keep]))
            got_keys = (np.concatenate(keys) if keys
                        else np.zeros(0, np.uint64))
            got_vals = (np.concatenate(vals) if vals
                        else np.zeros(0, np.uint64))
        want_keys, want_vals = ref.events()
        rows_mask = np.ones(ref.counters.shape[0], dtype=bool)
        rows_mask[list(HOST_REASONS)] = False
        counter_gap = int(np.abs(got_counters - ref.counters)[rows_mask]
                          .sum())
        event_gap = multiset_mismatch(got_keys, got_vals, want_keys,
                                      want_vals)
        fe = res.get("front-end", {})
        rec = fe.get("fault-tolerance", {}).get("recovery-dropped", 0)
        ledger_gap = abs(fe.get("submitted", 0) - fe.get("verdicts", 0)
                         - fe.get("shed", 0) - rec)
        lost = int(res.get("lost", 0)
                   + res.get("event-plane", {}).get("events-dropped", 0))
        checks = {"counter_mismatch": [counter_gap, 0],
                  "event_mismatch": [event_gap, 0],
                  "ledger_gap": [int(ledger_gap), 0],
                  "events_lost": [lost, 0]}
        facts = {"rows_compared": int(ref.rows),
                 "events_compared": int(len(want_keys)),
                 "host_events": int(host_events),
                 "control": control}
        return checks, facts


# -- the whole run --------------------------------------------------------------
def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> Optional[int]:
    import jax

    peaks = []
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats() or {}
        except Exception:  # noqa: BLE001 -- backends without stats
            stats = {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _quantile_ms(x: np.ndarray, q: float) -> Optional[float]:
    return float(np.quantile(x, q) * 1e3) if len(x) else None


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, tiny: bool = False,
             control: Optional[str] = None, root: str = ROOT) -> dict:
    """Run the cell once.  Returns {"result", "checks", "guards"}."""
    import jax

    s = Session(cell_name, seed, tiny=tiny, root=root)
    counter = CompileCounter()
    dev = device_info()
    profile = None
    try:
        s.build()
        s.warm()
        gen = s.generator()
        probe = StallProbe(gen)
        probe.start()
        time.sleep(float(s.mix["settle_s"]))
        c0 = counter.count
        a = s.snap()
        t0 = a["t"]
        if trace:
            time.sleep(max(0.0, (seconds - TRACE_SECONDS) / 2))
            profile = _profile(s, min(TRACE_SECONDS, seconds / 2))
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        b = s.snap()
        t1 = b["t"]
        c1 = counter.count
        gen.stop()
        probe.stop()
        mem = memory_peak()
        ct = s.daemon.loader.map_pressure(s.daemon._now())["ct"]
        compile_events = s.daemon.loader.compile_log.snapshot(
            limit=64)["events"]
        res = s.finish()
    finally:
        s.shutdown()
    setup_s = t0 - t_start
    if profile is not None:
        _reduce_profile(profile)
    checks, facts = s.check(res, control)
    if control:
        # the same run's own comparison, so a control run is also a
        # seed of the program
        facts["program_checks"] = {k: v for k, (v, _lim)
                                   in s.check(res)[0].items()}
    spans = s.spans.array()
    in_window = ((spans[:, -1] >= t0) & (spans[:, -1] <= t1)
                 if len(spans) else np.zeros(0, bool))
    due = np.frombuffer(gen.due, dtype=np.float64)
    lag = np.frombuffer(gen.lag, dtype=np.float64)
    lag_w = lag[(due >= t0) & (due <= t1)]
    delta = {k: b[k] - a[k] for k in a}
    from cilium_tpu.obs.trace import SPAN_STAGES

    ctx = {"setup_s": setup_s, "window_s": t1 - t0, "delta": delta,
           "spans": spans[in_window], "stages": tuple(SPAN_STAGES),
           "gen_lag_s": lag_w, "loop": s.mix["loop"],
           "every_event": bool(s.cfg["every_verdict_is_an_event"]),
           "profile": profile, "device": dev}
    if dev["platform"] == "tpu":
        from roofline import peaks

        ctx["peaks"] = peaks(dev["kind"])
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(s.spec, cell_name, kind):
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    failed = delta["shed"] + delta["recovery"]
    if ctx["every_event"]:
        failed += delta["ring_lost"] + delta["ev_dropped"]
    device = {**dev, "memory_peak_bytes": mem}
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": int(delta["submitted"]), "failed": int(failed),
              "metrics": metrics, "device": device}
    if profile is not None:
        r = profile["reduced"]
        if r is not None:
            device["busy_s"] = r["busy_ns"] / 1e9
            device["window_s"] = r["window_ns"] / 1e9
            result["breakdown"] = {
                "device_ops": [[n, ns / 1e9] for n, ns in r["ops"]],
                "idle_gaps": [[n, ns / 1e9] for n, ns in r["idle_gaps"]]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    guards = {"window_compiles": delta["compiles"],
              "window_executables": c1 - c0,
              "window_executable_names": [
                  [round(t - t0, 3), n, sec] for t, n, sec in counter.names
                  if t0 <= t <= t1],
              "window_shed": delta["shed"],
              "window_recovery_dropped": delta["recovery"],
              "window_restarts": delta["restarts"],
              "window_dispatch_timeouts": delta["dispatch_timeouts"],
              "gen_lag_worst_at_s": (float(due[(due >= t0) & (due <= t1)][
                  np.argmax(lag_w)] - t0) if len(lag_w) else None),
              "gen_lag_p99_ms": _quantile_ms(lag_w, 0.99),
              "gen_lag_max_ms": (float(lag_w.max() * 1e3)
                                 if len(lag_w) else None),
              "stalls": [[round(st - t0, 3), round(sec, 3), stacks]
                         for st, sec, stacks in probe.stalls
                         if t0 <= st <= t1],
              "spans": int(in_window.sum()),
              "ct_occupied": ct.get("occupied"),
              "ct_occupancy": ct.get("occupancy"),
              "ct_insert_drops": ct.get("insert-drops"),
              "offered_rows_per_s": delta["submitted"] / (t1 - t0),
              "window_s": t1 - t0, "setup_s": setup_s,
              "setup_phases_s": s.phase_s,
              "compiles": [[e["mode"], e["shape"], e["compile-ms"]]
                           for e in compile_events], **facts}
    if profile is not None:
        guards["profile"] = {k: v for k, v in profile.items()
                             if k != "reduced"}
        if profile["reduced"] is not None:
            guards["profile"]["modules"] = profile["reduced"]["modules"]
    return {"result": result, "checks": checks, "guards": guards}


WINDOW_MARK = "benchmark-traced-window"


def _profile(s: Session, seconds: float) -> dict:
    """Trace ``seconds`` of steady state with the JAX profiler (host
    spans, no Python tracer).  The counters read around it give the
    verdicts and events the traced device work served; the trace is
    reduced after the window (:func:`_reduce_profile`)."""
    import shutil

    import jax

    out = os.path.join(s.root, ".bench_out", "trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        a = s.snap()
        with jax.profiler.TraceAnnotation(WINDOW_MARK):
            time.sleep(seconds)
        b = s.snap()
    finally:
        jax.profiler.stop_trace()
    return {"dir": out, "seconds": (b["t"] - a["t"]),
            "verdicts": b["verdicts"] - a["verdicts"],
            "padded": b["padded"] - a["padded"],
            "dispatches": b["dispatches"] - a["dispatches"],
            "events": b["ev_joined"] - a["ev_joined"],
            "inserts": b["policy_verdicts"] - a["policy_verdicts"],
            "reduced": None}


def _reduce_profile(prof: dict) -> None:
    """Read and reduce the trace ``_profile`` wrote, then delete it.
    The window is the host annotation around the traced sleep."""
    import shutil

    from tracereduce import load, reduce

    out = prof.pop("dir")
    try:
        events = load(out)
    finally:
        shutil.rmtree(out, ignore_errors=True)
    if not (events["ops"] or events["modules"]):
        return
    marks = [(st, st + d) for n, st, d in events["host"]
             if n == WINDOW_MARK]
    prof["reduced"] = reduce(events, marks[0] if marks else None)
    prof["module_count"] = prof["reduced"]["module_count"]
