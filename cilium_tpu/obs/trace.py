"""Sampled per-packet trace spans through the serving pipeline.

Dapper-shaped (Sigelman et al., 2010): a trace context is allocated
at admission for 1-in-N packets and carried THROUGH the hot path —
never looked up — so the cost when sampling is off is a single
``is not None`` branch per chunk, and when on it is O(sampled
packets), not O(packets).

The seven stage timestamps (``SPAN_STAGES``):

================  ===================================================
``admit``         the packet's chunk was admitted by ``IngressQueue``
``dequeue``       ``take_into`` memcpy'd its row out of the queue
``staged``        the batcher finished arena staging/packing+masking
``dispatch``      the drain loop handed the batch to the device leg
``dispatch-ret``  the (async) dispatch call returned
``device``        the batch's drain window was fetched — device work
                  provably complete (stamped by the event-join
                  worker; under-reported as the dispatch return
                  before the async event plane existed)
``join``          the batch's events were emitted to the monitor
                  plane
================  ===================================================

Timestamps are ``time.monotonic`` so consecutive stamps are
monotonic by construction and the six stage intervals telescope to
exactly the end-to-end latency — the property the determinism tests
assert.  Without an event-join worker (a bare ServingRuntime), the
``device``/``join`` stamps fall back to the completion boundary the
latency histogram uses, so the telescoping property holds on every
path.

Sampling is DETERMINISTIC over the admitted-packet sequence: packet
``seq`` is sampled iff ``(seq + seed) % sample == 0``, so the same
seed + the same packet stream yields the identical sampled-trace
set (the replayable-chaos property the fault-injection plane already
has, applied to tracing).

Completed spans land in a fixed-size ring (newest wins — the
wrap-overwrite discipline every other ring in this codebase uses);
per-stage log2 histograms aggregate across ALL completed spans so
the breakdown survives ring wrap.  Spans that die mid-pipeline
(shed by drop-oldest, swept by recovery, lost to a dead dispatch)
are counted, never silently vanished — the no-silent-loss contract
the serving ledger has, applied to its own instrumentation.

The same boundaries on the profiler's clock, for a ``jax.profiler``
trace (``GET /debug/profile``, ``--profile-dir``):

- ``DEVICE_STAGES``: the fused step's stages run under
  ``jax.named_scope`` of these names, so each device op's ``op_name``
  metadata names its stage (a fusion carries its root op's scope);
- ``PROFILE_SPANS``: per-BATCH host spans (``TraceAnnotation``, never
  per row) on the drain thread and the event-join worker, each with
  the batch's ring id (``batch=``, the ``batch-id`` of a packet span)
  as the shared identifier, and the per-packet interval it lies in.
  :func:`profile_span` costs one enabled check when no trace is on.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from ..serving.stats import LatencyHistogram

SPAN_STAGES = ("admit", "dequeue", "staged", "dispatch",
               "dispatch-ret", "device", "join")
N_STAGES = len(SPAN_STAGES)
# indices into TraceSpan.ts
STAGE_ADMIT, STAGE_DEQUEUE, STAGE_STAGED, STAGE_DISPATCH, \
    STAGE_DISPATCH_RET, STAGE_DEVICE, STAGE_JOIN = range(N_STAGES)

# jax.named_scope names of the fused step (datapath/verdict.py,
# monitor/ring.py), in pipeline order
DEVICE_STAGES = ("unpack", "ipcache", "ct_lookup", "policy",
                 "ct_update", "metricsmap", "ring")
SCOPE_UNPACK, SCOPE_IPCACHE, SCOPE_CT_LOOKUP, SCOPE_POLICY, \
    SCOPE_CT_UPDATE, SCOPE_METRICSMAP, SCOPE_RING = DEVICE_STAGES
# a sub-scope of ``ct_update``, not a stage: the CT insert claim, run
# only by a step whose batch has a pending insert, so a trace's count
# of ``ct_claim`` op executions over its ``jit_serve*`` module count is
# the share of steps that inserted (its time stays under ``ct_update``)
SCOPE_CT_CLAIM = "ct_claim"

# profiler span -> (thread, the per-packet interval it lies in;
# None where no packet waits on it)
PROFILE_SPANS = {
    "assemble": ("drain", "dequeue->staged"),  # batcher.assemble*
    "idle": ("drain", None),  # wait_nonempty, the deadline sleep
    "drain_tick": ("drain", "dispatch->dispatch-ret"),  # cursor sync
    "h2d": ("drain", "dispatch->dispatch-ret"),  # hdr/valid/scalars
    "lock_wait": ("drain", "dispatch->dispatch-ret"),  # loader lock
    "dispatch": ("drain", "dispatch->dispatch-ret"),  # the jit call
    "fetch": ("event-worker", "dispatch-ret->device"),  # d2h wait
    "emit": ("event-worker", "device->join"),  # decode + fan-out
    "analytics": ("event-worker", None),  # flow analytics drain
}

_NO_SPAN = contextlib.nullcontext()
_trace_enabled = TraceAnnotation.is_enabled
_annotation = TraceAnnotation


def profile_span(name: str, batch: int):
    # thread-affinity: any
    """The ``PROFILE_SPANS`` span ``name`` of batch ``batch`` as a
    context manager: a profiler ``TraceAnnotation`` while a trace is
    active, else a shared no-op (no kwargs, no string built)."""
    if not _trace_enabled():
        return _NO_SPAN
    return _annotation(name, batch=batch)

DEFAULT_SPAN_RING = 512


def validate_obs_config(trace_sample, profile_dir,
                        profile_batches) -> tuple:
    """Validate the observability DaemonConfig knobs; returns the
    normalized ``(trace_sample, profile_dir, profile_batches)``.
    Same contract as ``validate_serving_config``: a bad knob fails at
    daemon construction, not as tracing that silently never fires."""
    sample = int(trace_sample)
    if sample < 0:
        raise ValueError("serving_trace_sample must be >= 0 "
                         "(0 disables span tracing)")
    batches = int(profile_batches)
    if batches < 1:
        raise ValueError("profile_batches must be >= 1 "
                         "(the capture window traces N batches)")
    if profile_dir is not None and not str(profile_dir):
        profile_dir = None
    return sample, profile_dir, batches


class TraceSpan:
    """One sampled packet's trip through the pipeline.  Mutated only
    by the thread currently holding the packet (producer at admit,
    drain thread thereafter) — no lock needed until the final commit
    into the tracer ring."""

    __slots__ = ("trace_id", "seq", "ts", "bucket", "n_valid",
                 "batch_pos", "batch_id", "mode", "shard", "demoted",
                 "done")

    def __init__(self, trace_id: int, seq: int):
        self.trace_id = trace_id
        self.seq = seq  # admitted-packet sequence number
        self.ts: List[float] = [0.0] * N_STAGES
        self.bucket = 0  # padded batch size
        self.n_valid = 0
        self.batch_pos = -1  # row index within the bucket
        self.batch_id = -1  # serving seq (ring batch field)
        self.mode = ""  # dispatch mode ("wide"|"packed"|"sharded-*")
        self.shard = -1  # owning shard (sharded dispatch only)
        self.demoted = False  # dispatch crossed a ladder demotion
        self.done = False

    # -- derived reads -------------------------------------------------
    def stage_us(self) -> Dict[str, float]:
        """The five stage intervals in microseconds (telescoping:
        their sum IS the end-to-end latency)."""
        return {
            f"{SPAN_STAGES[i]}->{SPAN_STAGES[i + 1]}":
                (self.ts[i + 1] - self.ts[i]) * 1e6
            for i in range(N_STAGES - 1)
        }

    def e2e_us(self) -> float:
        return (self.ts[STAGE_JOIN] - self.ts[STAGE_ADMIT]) * 1e6

    def monotonic(self) -> bool:
        return all(self.ts[i + 1] >= self.ts[i]
                   for i in range(N_STAGES - 1))

    def to_dict(self) -> dict:
        return {
            "trace-id": self.trace_id,
            "seq": self.seq,
            "timestamps": list(self.ts),
            "stages-us": {k: round(v, 3)
                          for k, v in self.stage_us().items()},
            "e2e-us": round(self.e2e_us(), 3),
            "monotonic": self.monotonic(),
            "bucket": self.bucket,
            "n-valid": self.n_valid,
            "batch-pos": self.batch_pos,
            "batch-id": self.batch_id,
            "mode": self.mode,
            "shard": self.shard,
            "demoted": self.demoted,
        }


class SpanTracer:
    """The per-session span plane: deterministic 1-in-N admission
    sampling, a fixed-size completed-span ring, per-stage aggregate
    histograms, and exact loss accounting for spans that die
    mid-pipeline.

    Thread model: :meth:`sample_chunk` runs under the IngressQueue
    lock (the admitted-seq counter needs no lock of its own); stage
    stamping happens on whichever single thread owns the packet at
    that stage; :meth:`commit` / :meth:`evict` / :meth:`snapshot`
    take the tracer lock (commit is O(1): one ring write + six
    histogram records, far off the per-packet path)."""

    def __init__(self, sample: int, seed: int = 0,
                 capacity: int = DEFAULT_SPAN_RING):
        sample = int(sample)  # coerce FIRST: int(0.5) == 0 must be
        if sample <= 0:  # rejected here, not as a ZeroDivisionError
            raise ValueError("SpanTracer wants sample >= 1; use "
                             "tracer=None for disabled tracing")
        self.sample = sample
        self.seed = int(seed)
        self.capacity = int(capacity)
        self._ring: List[Optional[TraceSpan]] = [None] * self.capacity
        self._w = 0  # total committed (ring cursor)
        self._lock = threading.Lock()
        # guarded-by: _lock: _ring, _w, completed, dropped
        # (started/_seq/_next_id are guarded EXTERNALLY by the
        # IngressQueue lock — sample_chunk's documented contract —
        # which a per-class lexical checker cannot see)
        self._seq = 0  # admitted packets seen (queue-lock guarded)
        self._next_id = 0
        self.started = 0
        self.completed = 0
        self.dropped = 0  # spans evicted mid-pipeline (shed/lost)
        self.stage_hist = [LatencyHistogram() for _ in
                           range(N_STAGES - 1)]
        self.e2e_hist = LatencyHistogram()

    # -- admission side (under the IngressQueue lock) ------------------
    def sample_chunk(self, n: int,
                     t: float) -> List[Tuple[int, TraceSpan]]:
        # thread-affinity: any
        """Advance the admitted-seq counter by ``n`` and allocate
        spans for the sampled offsets; returns ``[(offset_in_chunk,
        span)]`` (usually empty).  ``t`` is the chunk's arrival
        stamp — the same clock the queue-wait histogram uses."""
        base = self._seq
        self._seq += n
        # first offset with (base + off + seed) % sample == 0
        first = (-(base + self.seed)) % self.sample
        if first >= n:
            return []
        out = []
        for off in range(first, n, self.sample):
            sp = TraceSpan(self._next_id, base + off)
            self._next_id += 1
            sp.ts[STAGE_ADMIT] = t
            out.append((off, sp))
        self.started += len(out)
        return out

    # -- pipeline side -------------------------------------------------
    def commit(self, span: TraceSpan) -> None:
        # thread-affinity: any
        """A span reached the join boundary with all six stamps."""
        if span.done:
            return
        span.done = True
        with self._lock:
            self._ring[self._w % self.capacity] = span
            self._w += 1
            self.completed += 1
            for i in range(N_STAGES - 1):
                self.stage_hist[i].record(
                    (span.ts[i + 1] - span.ts[i]) * 1e6)
            self.e2e_hist.record(span.e2e_us())

    def evict(self, spans) -> None:
        # thread-affinity: any
        """Spans whose packet died mid-pipeline (admission shed,
        recovery drop, lost batch): counted, never completed."""
        n = 0
        for sp in spans:
            if not sp.done:
                sp.done = True
                n += 1
        if n:
            with self._lock:
                self.dropped += n

    # -- reading (API threads) -----------------------------------------
    def stats(self) -> dict:
        # thread-affinity: any
        """The compact summary riding ``serving_stats()``."""
        with self._lock:
            return {
                "sample": self.sample,
                "started": self.started,
                "completed": self.completed,
                "dropped": self.dropped,
                "ring-capacity": self.capacity,
                "ring-held": min(self._w, self.capacity),
            }

    def snapshot(self, limit: int = 64) -> dict:
        """``GET /debug/traces``: summary + per-stage aggregate
        histograms + the most recent completed spans + the
        slowest-trace table (over the spans the ring still holds)."""
        with self._lock:
            held = min(self._w, self.capacity)
            # newest first
            spans = [self._ring[(self._w - 1 - i) % self.capacity]
                     for i in range(held)]
            out = {
                "sample": self.sample,
                "seed": self.seed,
                "started": self.started,
                "completed": self.completed,
                "dropped": self.dropped,
                "stages-us": {
                    f"{SPAN_STAGES[i]}->{SPAN_STAGES[i + 1]}":
                        self.stage_hist[i].snapshot()
                    for i in range(N_STAGES - 1)},
                "e2e-us": self.e2e_hist.snapshot(),
            }
        out["traces"] = [sp.to_dict() for sp in spans[:limit]
                         if sp is not None]
        slowest = sorted((sp for sp in spans if sp is not None),
                         key=lambda s: s.e2e_us(), reverse=True)
        out["slowest"] = [sp.to_dict() for sp in slowest[:limit]]
        return out
