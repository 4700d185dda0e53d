"""Daemon: the full agent wiring (NewDaemon, SURVEY.md §3.1).

Reference: upstream cilium ``daemon/cmd`` — config parse, state
restore, identity allocator, policy repository, datapath init,
endpoint restore/regeneration, monitor + Hubble, API serve.

Lifecycle here: construct -> (optionally) ``restore(dir)`` ->
add endpoints / import policy -> ``process_batch`` per packet tensor ->
``checkpoint(dir)`` on shutdown.  Background work (CT GC) runs in
named controllers; identity churn invalidates resolve caches and
coalesces into one regeneration (the SelectorCache-notification
analogue).
"""

from __future__ import annotations

import json
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..datapath.loader import InterpreterLoader, Loader, TPULoader
from ..flow import FlowExporter, FlowMetrics, Observer, ThreeFourParser
from ..identity.allocator import CachingIdentityAllocator
from ..infra.controller import ControllerManager
from ..ipcache import IPCache
from ..kvstore import InMemoryKVStore
from ..labels import LabelSet, SOURCE_CIDR
from ..monitor import MonitorAgent, decode_out
from ..monitor.api import EventBatch
from ..policy.api import rule_to_dict
from ..policy.repository import PolicyRepository
from .endpoint import Endpoint
from .endpointmanager import EndpointManager

VERSION = "0.1.0"


@dataclass
class DaemonConfig:
    """Reference: pkg/option.DaemonConfig (the ~300 viper flags; the
    subset that matters here)."""

    node_name: str = "node0"
    backend: str = "tpu"  # "tpu" | "interpreter"
    ct_capacity: int = 1 << 20
    ct_gc_interval: float = 30.0
    flow_ring_capacity: int = 4096
    export_path: Optional[str] = None
    state_dir: Optional[str] = None
    enable_hubble: bool = True
    anomaly_model_path: Optional[str] = None  # trained AnomalyModel .npz
    anomaly_threshold: float = 0.8
    fqdn_gc_interval: float = 15.0  # pkg/fqdn TTL sweep cadence
    # gRPC Observer address ("unix:///run/hubble.sock" or "host:port");
    # None = in-process only (REST /flows still serves)
    hubble_listen: Optional[str] = None
    # AF_UNIX path of this agent's REST API socket; advertised in the
    # node registry so peers' health meshes can probe it
    api_socket_path: Optional[str] = None
    health_probe_interval: float = 10.0
    # mutual authentication (pkg/auth): the manager observes
    # AUTH_REQUIRED drops and handshakes via the provider; TTL is the
    # grant lifetime (upstream: derived from certificate expiry)
    mesh_auth: bool = True
    auth_ttl: int = 3600
    auth_gc_interval: float = 30.0
    # transparent encryption (pkg/wireguard analogue): node keypair
    # published via the node registry; node-to-node batch transport
    # seals with ChaCha20-Poly1305 (cilium_tpu/encryption)
    enable_encryption: bool = False
    encryption_key_path: Optional[str] = None
    # egress masquerade (bpf/lib/nat.h analogue; service/nat.py)
    masquerade: bool = False
    node_ip: Optional[str] = None
    # additional node addresses nodePort frontends bind, beyond
    # node_ip (reference: --nodeport-addresses)
    nodeport_addresses: Tuple[str, ...] = ()
    non_masquerade_cidrs: Tuple[str, ...] = ("10.0.0.0/8",)
    # identity value-ref lease (reference: etcd lease on pkg/allocator
    # slave keys): None = unleased refs (single-process tests); set it
    # when the kvstore is networked so a crashed agent's refs expire
    # and identity GC can sweep.  A keepalive controller refreshes at
    # ttl/3.
    identity_lease_ttl: Optional[float] = None
    # policy-audit-mode (reference: --policy-audit-mode): policy
    # denials forward while verdict events keep the would-be reason
    policy_audit_mode: bool = False
    # monitor trace aggregation (reference: --monitor-aggregation):
    # "none" emits a TraceNotify per forwarded packet; "medium" only
    # for flow-state-changing packets (non-TCP, or TCP SYN/FIN/RST).
    # Per-endpoint Debug=True exempts an endpoint from aggregation.
    monitor_aggregation: str = "none"
    # serving front end (cilium_tpu/serving; the XDP/RSS + per-CPU
    # ring analogue).  Validated at construction — see
    # serving.validate_serving_config for the rules.
    # admission queue capacity in PACKETS; overflow sheds by policy
    serving_queue_depth: int = 1 << 16
    # power-of-two padding buckets, strictly ascending: each distinct
    # batch shape is one XLA compile, so the ladder bounds recompiles
    serving_bucket_ladder: Tuple[int, ...] = (1024, 4096, 16384,
                                              65536)
    # max microseconds a queued packet waits before a partial bucket
    # flushes (tail-latency bound at low load)
    serving_max_wait_us: float = 2000.0
    # "drop-tail" (arriving overflow sheds) | "drop-oldest" (stale
    # queued rows shed to admit the arrival)
    serving_overflow_policy: str = "drop-tail"
    # ship eligible IPv4 single-stream batches as the packed
    # 16 B/packet h2d wire format (core/packets.py PACKED_*) instead
    # of wide 64 B/packet rows; ineligible traffic (IPv6, mixed
    # ep/dir streams) keeps the wide fallback shape either way.
    # start_serving(packed=...) overrides per session.
    serving_packed_ingest: bool = False
    # K-batch superbatch dispatch (ISSUE 11): fuse up to K ready
    # batches into ONE device dispatch — a lax.scan runs datapath +
    # ring append for all K steps, so the drain loop's per-dispatch
    # Python cost (lock window, arena bookkeeping, one jit call) is
    # paid once per K batches.  Power of two; 1 disables.  K is a
    # fallback-ladder rung property: demotion shrinks K before it
    # would ever change mode.  Interaction with serving_max_wait_us:
    # assembly never WAITS for K batches — it takes what is already
    # queued (single-batch fallback below two full buckets), so tail
    # latency at low offered load is unchanged; the tradeoff it DOES
    # buy is generation-pinning latency (one dispatch pins one table
    # generation for K batches — BENCH_churn measures it at K>1).
    # start_serving(superbatch_k=...) overrides per session.
    serving_superbatch_k: int = 1
    # -- the async event plane (serving/eventplane.py).  How many
    # drain windows the event-join worker's bounded queue may hold;
    # overflow drops the OLDEST offered window, counted
    # (windows-dropped), never silently.  Also sizes the batcher
    # arena's recycling horizon: header slots must outlive every
    # window in flight on the worker, so arena memory scales with
    # (depth + 2) * drain_every slots per bucket shape
    serving_window_queue_depth: int = 4
    # -- the L7 proxy plane (serving/l7plane.py + proxy/worker.py):
    # redirected rows fan out of the event-join worker into a bounded
    # pool of L7 workers (upstream: the Envoy/proxylib userspace
    # proxy).  Worker count and task-queue depth; overflow sheds the
    # OLDEST queued task, counted l7_shed, never silently.  The pool
    # shares serving_restart_budget for its restart-on-death budget
    l7_workers: int = 2
    l7_queue_depth: int = 128
    # occupancy-bounded ring drain: fetch a power-of-two-rung device
    # GATHER of just the window's occupied slots instead of the full
    # ring — d2h bytes scale with events appended, not ring capacity.
    # False falls back to the full-capacity copy (the pre-PR5 wire)
    serving_event_gather: bool = True
    # -- serving fault tolerance (cilium_tpu/serving runtime watchdog
    # + degraded-mode ladder; the cilium-health / endpoint-
    # regeneration analogue for the serving plane).  Validated at
    # construction like the knobs above.
    # per-batch dispatch deadline in ms; a dispatch exceeding it is
    # declared hung, its rows counted as REASON_DISPATCH_TIMEOUT
    # drops, and the drain loop restarted.  0 disables hang detection
    serving_dispatch_deadline_ms: float = 1000.0
    # how many drain-loop restarts the watchdog may spend before the
    # runtime goes terminal (0 disables supervision entirely: a dead
    # drain loop stays a visible corpse, the pre-PR3 behavior)
    serving_restart_budget: int = 8
    # initial restart backoff in ms (doubles per consecutive restart,
    # capped at 1s; resets after a healthy interval)
    serving_restart_backoff_ms: float = 10.0
    # consecutive dispatch failures on one ladder rung before the
    # serving session demotes (sharded -> single-chip -> wide); one
    # success resets the streak
    serving_demote_threshold: int = 3
    # consecutive healthy batches before a degraded session promotes
    # one rung back up...
    serving_promote_after: int = 64
    # ...and the minimum seconds since the last rung change (the
    # hysteresis half: a flapping shard burns a full cooldown per
    # re-promotion attempt)
    serving_promote_cooldown_s: float = 5.0
    # periodic CT snapshot cadence in seconds (0 = only on demotion /
    # checkpoint): the last snapshot rides recovery paths where the
    # live device CT is unreadable, so a loader rebuild keeps
    # established flows
    ct_snapshot_interval: float = 0.0
    # deterministic fault injection (infra/faults.py spec string,
    # e.g. "serving.dispatch=1x1~0.3"); armed process-global at
    # construction, disarmed on shutdown.  For chaos testing — leave
    # None in production
    fault_injection: Optional[str] = None
    fault_seed: int = 0
    # -- observability (cilium_tpu/obs; the Hubble/pkg/monitor-depth
    # introspection layer for the serving plane).
    # sampled per-packet trace spans: 1-in-N admitted packets get a
    # span carried admission -> batcher -> staging -> dispatch ->
    # verdict join with six monotonic stage timestamps (GET
    # /debug/traces, `cilium-tpu trace`).  0 = off = zero overhead
    serving_trace_sample: int = 0
    # jax.profiler capture window: trace the first profile_batches
    # serving dispatches into this directory, then stop (viewable in
    # TensorBoard/Perfetto).  None = off
    profile_dir: Optional[str] = None
    profile_batches: int = 16
    # -- flow analytics plane (obs/analytics.py): windowed
    # per-identity aggregation, top-K talkers, drop-spike detection
    # over the decoded event stream.  The aggregation work runs on
    # the event-join worker and query threads, NEVER the serving
    # drain thread (publishing threads only pay an O(1) reference
    # park) — disabling it removes already-off-path work only
    flow_agg_enabled: bool = True
    # rolling window width in seconds, and how many CLOSED windows
    # the ring-of-windows retains behind the open one
    flow_agg_window_s: float = 1.0
    flow_agg_windows: int = 8
    # space-saving sketch capacity K (top talkers by flow 4-tuple
    # and by identity pair): any key with true count > N/K is
    # guaranteed retained, every estimate overshoots by <= N/K
    flow_agg_topk: int = 32
    # decoded batches parked between monitor publish and the
    # worker-side drain; overflow drops the OLDEST pending batch,
    # counted (the event plane's drop-oldest discipline)
    flow_agg_queue_depth: int = 16
    # aggregation duty-cycle cap (fraction of wall time per rolling
    # second the worker may spend aggregating): "off the dispatch
    # path" must also mean "not eating the dispatch path's machine"
    # on CPU hosts (python-held segments contend on the GIL with the
    # drain loop), so past the budget pending batches become counted
    # drops instead of stolen cycles.  0.1 = 100 ms/s — ample for
    # 1-in-N sampled traffic plus drop-storm accounting
    flow_agg_max_duty: float = 0.1
    # drop-spike detector: a closed window whose drop count crosses
    # max(spike_min_drops, spike_factor * trailing-baseline) raises
    # ONE drop-spike incident; hysteresis holds the state until
    # drops fall back to baseline, and spike windows are excluded
    # from the baseline (a burst must not teach itself normal)
    spike_factor: float = 4.0
    spike_min_drops: int = 64
    spike_baseline_windows: int = 4
    # -- incident flight recorder (obs/flightrec.py).  Where sysdump
    # bundles land; None records incident history but captures no
    # bundles.  Incidents that fire a capture: drop-spike, watchdog
    # restart/terminal, ladder demotion, terminal event-join worker,
    # and the manual API/CLI trigger
    sysdump_dir: Optional[str] = None
    # bundles kept on disk (oldest pruned past this)
    sysdump_retention: int = 8
    # bundle size cap; oversize bundles shed their largest optional
    # sections (metrics text, flows, traces...) until they fit
    sysdump_max_bytes: int = 1 << 20
    # auto-captures inside this interval are skipped (counted) so a
    # restart storm cannot write a bundle per restart; manual
    # triggers bypass the limit
    sysdump_min_interval_s: float = 1.0
    # last-N Observer flows included per bundle
    sysdump_flows: int = 128
    # -- clustermesh serving tier (cilium_tpu/cluster): N in-process
    # daemon replicas behind one flow-affine front-end router, built
    # by start_cluster_serving(nodes=N, config=...).  Validated at
    # ClusterServing construction (cluster.validate_cluster_config).
    # per-node forward queue capacity in PACKETS between the router
    # and a replica's admission queue; overflow sheds drop-tail as
    # counted REASON_CLUSTER_OVERFLOW drops
    cluster_forward_depth: int = 1 << 15
    # membership liveness sweep cadence...
    cluster_probe_interval_s: float = 0.5
    # ...and how many CONSECUTIVE failed probes declare a node dead
    # (then: CT-replay failover onto the designated peer)
    cluster_death_threshold: int = 2
    # how long identity/policy mutations may take to reach every
    # replica over the kvstore before wait_identity / wait_policy
    # report divergence
    cluster_convergence_deadline_s: float = 5.0
    # "remote" serves the shared store over a real socket
    # (kvstore/remote.py — one client per replica, the deployment
    # shape); "memory" shares the InMemoryKVStore object (cheapest
    # tests)
    cluster_kvstore: str = "remote"
    # "thread" = in-process replicas (the PR 8 shape: cheapest tests,
    # N nodes share one GIL); "process" = one spawned worker PROCESS
    # per node (cluster/nodehost.py) with row forwarding over real
    # sockets (cluster/transport.py) — the upstream per-node-agent
    # shape where N nodes buy N cores.  Process mode requires
    # cluster_kvstore="remote"
    cluster_mode: str = "thread"
    # slots per INITIAL node in the router's fixed flow-hash space:
    # the re-pin granularity for failover and live scale-out (a
    # joining node steals ~1/new_n of the slots, nobody else's flows
    # move)
    cluster_slot_factor: int = 16
    # -- cluster observability relay (obs/relay.py; ISSUE 14).  The
    # parent-side scrape loop's cadence in seconds: every tick pulls
    # each node's registry exposition, flow-ring tail, analytics
    # top-K, tracer stats, and incident list into the merged cluster
    # views (GET /cluster/metrics, flows --cluster, top --cluster,
    # cluster sysdump).  0 disables the periodic loop — queries then
    # scrape on demand
    cluster_obs_interval_s: float = 1.0
    # a node whose scrape fails keeps serving its last-known-good
    # snapshot this long; past the bound its per-node series drop
    # (only the relay's scrape_ok/age meta-series remain)
    cluster_obs_stale_after_s: float = 30.0
    # cross-process trace stitching: every Nth forwarded chunk
    # carries (trace_id, router stamps) through the data channel and
    # the worker's stage stamps ride the ack back — one stitched span
    # per sample (router-queue -> forward -> worker-admit -> ack).
    # 0 = off (the hot-path cost when off is one int compare)
    cluster_trace_sample: int = 0
    # -- pipelined data channel (ISSUE 17).  Frames a process-mode
    # forwarder may have ON THE WIRE (sent, not yet cumulatively
    # acked) per node before it blocks for credit.  1 = the PR 13
    # synchronous per-frame-ack protocol, byte-identical on the wire;
    # >= 2 switches to sequenced frames + cumulative acks and pays
    # the round trip once per window
    cluster_forward_window: int = 8
    # worker-side ack coalescer: one cumulative ack per this many
    # admitted frames (or immediately when the channel drains —
    # nothing else buffered after an admit — so low-load frames ack
    # sync-like)...
    cluster_ack_every: int = 4
    # ...or after this many ms of quiet (the flush-on-idle timer that
    # bounds the tail latency coalescing could otherwise add)
    cluster_ack_flush_ms: float = 2.0
    # -- encrypted data channel (ISSUE 18).  When ON (process mode),
    # every router->worker data frame AND every worker->router ack
    # travels as one AEAD seal (EncryptedChannel; X25519 session keys
    # exchanged through the spawn handshake + node registry), decrypt
    # failures are counted + contained (typed reject record, never a
    # worker crash), and ClusterServing.rotate_epoch() rotates every
    # channel live.  OFF = byte-identical to the plaintext wire.
    # Thread mode has no sockets, so the knob is a no-op there.
    cluster_encrypt: bool = False
    # how long the receive side keeps the PREVIOUS epoch's key alive
    # after rotate_epoch() (its own replay window), so in-flight
    # frames sealed pre-rotation still open; past the grace they
    # reject as epoch-old
    cluster_epoch_grace_s: float = 2.0
    # -- queue-depth autoscale (cluster/scale.py ClusterAutoscaler).
    # When ON, a named controller samples the router's forward queues
    # and add_node()s after `ticks` consecutive samples over
    # `high_frac * cluster_forward_depth`, up to `max_nodes`;
    # when `low_frac` > 0 it also remove_node()s after `ticks`
    # consecutive samples under `low_frac * cluster_forward_depth`,
    # down to `min_nodes` (scale-in, ISSUE 17)
    cluster_autoscale: bool = False
    cluster_autoscale_max_nodes: int = 8
    cluster_autoscale_high_frac: float = 0.5
    cluster_autoscale_ticks: int = 3
    cluster_autoscale_interval_s: float = 0.5
    cluster_autoscale_min_nodes: int = 1
    cluster_autoscale_low_frac: float = 0.0
    # -- live policy churn (datapath/tables.py table versioning;
    # ISSUE 10).  Delta attach: repaint only fingerprint-changed
    # policies on a re-attach instead of recompiling the world
    # (policy.incremental.delta_compile); False forces every attach
    # down the full-compile path (debug / A-B comparison)
    policy_delta_compile: bool = True
    # warn when a table publish holds the dispatch lock longer than
    # this many ms (the flip is supposed to be a pointer swap; a slow
    # one means device work leaked inside the lock).  0 = off
    policy_swap_warn_ms: float = 0.0
    # -- map-pressure graceful degradation (datapath/pressure.py;
    # ISSUE 12 — the ctmap adaptive-GC / map-pressure-gauge
    # analogue).  A named controller samples CT occupancy, insert-
    # drop rate, and NAT pool failures off the drain thread; crossing
    # a threshold accelerates the CT aging sweep, records ONE
    # `map-pressure` incident per episode (sysdump capture), and
    # surfaces the state in serving stats / GET /serving / CLI.
    # sample cadence in seconds; 0 disables the monitor entirely
    map_pressure_interval: float = 5.0
    # CT occupancy fraction (occupied slots / capacity) entering the
    # pressure state...
    ct_pressure_threshold: float = 0.85
    # ...and the hysteresis exit: pressure clears only once occupancy
    # falls back under this AND a sample window sees no new insert
    # drops / NAT failures (a storm cannot flap incidents)
    ct_pressure_clear: float = 0.70
    # the ACCELERATED CT aging-sweep cadence while under pressure
    # (ct_gc_interval is the normal cadence it returns to)
    ct_gc_pressure_interval: float = 1.0
    # SNAT port-pool size (service/nat.py NATTable): power of two,
    # pool must fit the port space above NAT_PORT_MIN.  None = the
    # NAT_DEFAULT_CAPACITY (1 << 14).  Small pools are the
    # nat_exhaustion scenario's pressure shape
    nat_pool_capacity: Optional[int] = None
    # -- adaptive GC relaxation (ISSUE 19 satellite; the other half
    # of ctmap's adaptive interval: the sweep accelerates under
    # pressure AND relaxes back out when the map stays calm).  After
    # every ct_gc_relax_after seconds of CONTINUOUS calm (state ok,
    # occupancy under the clear bound, no new drops/failures) the
    # monitor stretches the normal CT-GC cadence by
    # ct_gc_relax_factor, compounding up to the ct_gc_relax_max
    # multiplier; any pressure episode snaps the multiplier back to
    # 1 — relaxation can never fire mid-episode.  0 = off
    ct_gc_relax_after: float = 300.0
    ct_gc_relax_factor: float = 2.0
    ct_gc_relax_max: float = 4.0
    # -- SLO plane (obs/history.py + obs/slo.py; ISSUE 19).  One
    # sampler thread (CTA002 domain `slo`, duty-governed) retains a
    # declared registry subset in two fixed-memory ring tiers and
    # evaluates the shipped SLO set with fast+slow burn rates; a
    # page-severity burn opens a `slo-burn` incident episode.
    # sampler cadence in seconds; 0 disables history AND SLO
    # evaluation entirely
    history_interval: float = 10.0
    # fast-tier ring slots (span = history_interval * slots)
    history_slots: int = 360
    # every Nth sample also lands in the slow tier...
    history_slow_every: int = 30
    # ...whose ring holds this many slots (default 5 min x 288 = 24 h)
    history_slow_slots: int = 288
    # the multi-window burn evaluation windows (seconds); both must
    # fit the rings' span to ever leave no-data
    slo_fast_window: float = 60.0
    slo_slow_window: float = 600.0
    # burn-rate thresholds: PAGE when both windows burn at/over
    # slo_page_burn (opens the incident episode), WARN at
    # slo_warn_burn
    slo_page_burn: float = 10.0
    slo_warn_burn: float = 2.0
    # hysteresis: an episode closes only after this many consecutive
    # calm evaluations (both windows under the warn burn)
    slo_clear_ticks: int = 3
    # the sampler's duty-governor ceiling (the flow-analytics
    # max_duty idiom): sampling+evaluation time stays under this
    # fraction of wall clock by stretching the cadence. 0 = fixed
    slo_max_duty: float = 0.05


class Daemon:
    def __init__(self, config: Optional[DaemonConfig] = None,
                 kvstore: Optional[InMemoryKVStore] = None,
                 encryption_keypair=None):
        """``kvstore``: pass one shared store to multiple daemons and
        they agree on identity numerics through the distributed
        allocator protocol AND replicate each other's allocations by
        watch (reference: pkg/kvstore + pkg/allocator + clustermesh).
        Without it the daemon allocates locally.

        ``encryption_keypair``: inject the node's Curve25519 identity
        instead of generating/loading one — the process-per-node
        worker hands over the keypair it already introduced in its
        spawn handshake, so the registry-advertised pubkey and the
        cluster data channel's key are the SAME identity."""
        from ..kvstore import ClusterIdentitySync, KVStoreAllocatorBackend
        from ..serving import (validate_recovery_config,
                               validate_serving_config,
                               validate_superbatch_config)

        self.config = config or DaemonConfig()
        # serving knobs fail at CONSTRUCTION (config resolution hands
        # them over as strings from env/config-dir): a typo'd policy
        # or non-power-of-two bucket must not surface as a recompile
        # storm under load.  Normalized values write back so the
        # /config surface shows what actually runs.
        (self.config.serving_queue_depth,
         self.config.serving_bucket_ladder,
         self.config.serving_max_wait_us,
         self.config.serving_overflow_policy) = validate_serving_config(
            self.config.serving_queue_depth,
            self.config.serving_bucket_ladder,
            self.config.serving_max_wait_us,
            self.config.serving_overflow_policy)
        (self.config.serving_dispatch_deadline_ms,
         self.config.serving_restart_budget,
         self.config.serving_restart_backoff_ms,
         self.config.serving_demote_threshold,
         self.config.serving_promote_after,
         self.config.serving_promote_cooldown_s
         ) = validate_recovery_config(
            self.config.serving_dispatch_deadline_ms,
            self.config.serving_restart_budget,
            self.config.serving_restart_backoff_ms,
            self.config.serving_demote_threshold,
            self.config.serving_promote_after,
            self.config.serving_promote_cooldown_s)
        self.config.serving_superbatch_k, _ = (
            validate_superbatch_config(
                self.config.serving_superbatch_k))
        if self.config.ct_snapshot_interval < 0:
            raise ValueError("ct_snapshot_interval must be >= 0")
        self.config.serving_window_queue_depth = int(
            self.config.serving_window_queue_depth)
        if self.config.serving_window_queue_depth < 1:
            raise ValueError(
                "serving_window_queue_depth must be >= 1 (the "
                "event-join worker's bounded window queue)")
        self.config.l7_workers = int(self.config.l7_workers)
        if self.config.l7_workers < 1:
            raise ValueError(
                "l7_workers must be >= 1 (the L7 proxy worker pool)")
        self.config.l7_queue_depth = int(self.config.l7_queue_depth)
        if self.config.l7_queue_depth < 1:
            raise ValueError(
                "l7_queue_depth must be >= 1 (the L7 pool's bounded "
                "task queue)")
        from ..obs import validate_obs_config

        (self.config.serving_trace_sample,
         self.config.profile_dir,
         self.config.profile_batches) = validate_obs_config(
            self.config.serving_trace_sample,
            self.config.profile_dir,
            self.config.profile_batches)
        # deterministic fault injection (chaos testing): arm the
        # process-global injector; spec typos fail here, not as a
        # silently-inert chaos run.  shutdown() disarms what we armed
        self._fault_injector = None
        if self.config.fault_injection:
            from ..infra import faults

            self._fault_injector = faults.arm(
                self.config.fault_injection,
                seed=self.config.fault_seed)
        # last CT snapshot (periodic controller / demotion / on
        # demand): recovery paths restore from it when the live
        # device CT is unreadable
        self._ct_snap: Optional[dict] = None
        self.kvstore = kvstore if kvstore is not None else InMemoryKVStore()
        backend = None
        if kvstore is not None:
            backend = KVStoreAllocatorBackend(
                self.kvstore, node=self.config.node_name,
                lease_ttl=self.config.identity_lease_ttl)
        self.allocator = CachingIdentityAllocator(backend=backend)
        self.identity_sync: Optional[ClusterIdentitySync] = None
        self.repo = PolicyRepository(self.allocator)
        self.ipcache = IPCache()
        self.config.policy_swap_warn_ms = float(
            self.config.policy_swap_warn_ms)
        if self.config.policy_swap_warn_ms < 0:
            raise ValueError("policy_swap_warn_ms must be >= 0")
        # map-pressure knobs (datapath/pressure.py) + the SNAT pool
        # size: fail at construction like every serving knob
        from ..datapath.pressure import validate_pressure_config

        (self.config.map_pressure_interval,
         self.config.ct_pressure_threshold,
         self.config.ct_pressure_clear,
         self.config.ct_gc_pressure_interval
         ) = validate_pressure_config(
            self.config.map_pressure_interval,
            self.config.ct_pressure_threshold,
            self.config.ct_pressure_clear,
            self.config.ct_gc_pressure_interval)
        from ..datapath.pressure import validate_relax_config

        (self.config.ct_gc_relax_after,
         self.config.ct_gc_relax_factor,
         self.config.ct_gc_relax_max) = validate_relax_config(
            self.config.ct_gc_relax_after,
            self.config.ct_gc_relax_factor,
            self.config.ct_gc_relax_max)
        # SLO-plane knobs (obs/history.py + obs/slo.py): same
        # fail-at-construction contract
        from ..obs import validate_history_config, validate_slo_config

        (self.config.history_interval,
         self.config.history_slots,
         self.config.history_slow_every,
         self.config.history_slow_slots) = validate_history_config(
            self.config.history_interval,
            self.config.history_slots,
            self.config.history_slow_every,
            self.config.history_slow_slots)
        (self.config.slo_fast_window,
         self.config.slo_slow_window,
         self.config.slo_page_burn,
         self.config.slo_warn_burn,
         self.config.slo_clear_ticks,
         self.config.slo_max_duty) = validate_slo_config(
            self.config.slo_fast_window,
            self.config.slo_slow_window,
            self.config.slo_page_burn,
            self.config.slo_warn_burn,
            self.config.slo_clear_ticks,
            self.config.slo_max_duty)
        if self.config.nat_pool_capacity is not None:
            # NAT_PORT_MIN is the single pool-base authority
            # (service/nat.py); NATTable.create re-validates — this
            # check exists so the failure names the config knob, not
            # a lazy first-masquerade deep in a serving leg
            from ..service.nat import NAT_PORT_MIN

            cap = int(self.config.nat_pool_capacity)
            if cap < 8 or cap & (cap - 1) \
                    or NAT_PORT_MIN + cap > 65536:
                raise ValueError(
                    f"nat_pool_capacity must be a power of two with "
                    f"NAT_PORT_MIN + capacity <= 65536 (the pool is "
                    f"[{NAT_PORT_MIN}, {NAT_PORT_MIN} + capacity) "
                    f"node ports)")
            self.config.nat_pool_capacity = cap
        if self.config.backend == "tpu":
            self.loader: Loader = TPULoader(
                self.config.ct_capacity,
                delta_compile=self.config.policy_delta_compile,
                swap_warn_ms=self.config.policy_swap_warn_ms,
                nat_capacity=self.config.nat_pool_capacity)
        else:
            self.loader = InterpreterLoader(
                nat_capacity=self.config.nat_pool_capacity)
        self.endpoints = EndpointManager(self.repo, self.ipcache,
                                         self.loader)
        self.monitor = MonitorAgent()
        self.controllers = ControllerManager()
        self.encryption = None  # set below when enabled + kvstore
        self._dns_listeners: Dict[int, object] = {}
        self._boot_time = time.time()
        self._started = False

        # L7 proxy plane: listeners follow the resolved redirects
        # (reference: pkg/proxy redirect lifecycle + Envoy filter);
        # created before hubble so the seven parser can subscribe
        from ..proxy import L7Proxy

        self.proxy = L7Proxy()
        self.endpoints.on_attach(self.proxy.update)

        # xDS push surface for an EXTERNAL proxy (reference: pkg/envoy
        # NPDS) — the native L7 path above stays the default; the
        # cache just tracks every attach so a fronting Envoy can
        # subscribe via proxy/xds.serve_xds
        from ..proxy.xds import XDSCache

        self.xds = XDSCache()
        self.endpoints.on_attach(self.xds.update_from_policies)

        # the live L7 proxy plane (serving/l7plane.py): constructed
        # per serving session in start_serving, read lock-free from
        # the event-join worker via this attribute (NEVER through
        # self._serving — _emit_ring_rows is contractually barred
        # from touching the session dict).  _l7_last keeps the final
        # stats of the most recent session for post-stop reads.
        self._l7plane = None
        self._l7_last: Optional[dict] = None
        # embedder seams for the plane's parse leg: a request source
        # (port, kind, task) -> requests, and a DNS resolver
        # (qname) -> (ips, ttl) feeding live FQDN identity mints
        self.l7_request_source = None
        self.l7_dns_resolver = None

        # hubble plane
        self.observer = Observer(
            capacity=self.config.flow_ring_capacity,
            identity_getter=self._identity_labels,
            endpoint_getter=self._endpoint_info)
        self.parser = ThreeFourParser(self.observer)
        self.flow_metrics = FlowMetrics()
        self.exporter: Optional[FlowExporter] = None
        if self.config.enable_hubble:
            self.monitor.register("hubble", self.parser.consume)
            self.monitor.register("metrics", self.flow_metrics.consume)
            # the seven parser: proxy access records -> L7 flows in
            # the same ring (reference: pkg/hubble/parser/seven)
            from ..flow.seven import SevenParser

            self.seven = SevenParser(
                self.observer,
                numeric_of_row=lambda r: (
                    self.loader.row_map.numeric(r)
                    if self.loader.row_map else 0))
            self.proxy.on_record(self.seven.consume)
        if self.config.export_path:
            self.exporter = FlowExporter(
                self.config.export_path, self.config.node_name,
                identity_getter=self._identity_labels,
                endpoint_getter=self._endpoint_info)
            self.monitor.register("exporter", self.exporter.consume)
        # learned path: advisory anomaly scores on the monitor stream
        self.anomaly = None
        if self.config.anomaly_model_path:
            from ..ml import AnomalyScorer, load_model

            self.anomaly = AnomalyScorer(
                load_model(self.config.anomaly_model_path),
                lambda numeric: (self.loader.row_map.row(numeric)
                                 if self.loader.row_map else 0),
                threshold=self.config.anomaly_threshold)
            self.monitor.register("anomaly", self.anomaly.consume)

        # flow analytics + incident flight recorder (obs/analytics,
        # obs/flightrec): the analytics engine rides the monitor
        # stream as one O(1) reference-park consumer and aggregates
        # on the event-join worker / query threads; incidents —
        # spike, watchdog restart, ladder demotion, terminal event
        # worker, manual — capture a sysdump bundle when a dir is
        # configured
        from ..obs import (FlightRecorder, FlowAnalytics,
                           validate_analytics_config,
                           validate_flightrec_config)

        (self.config.flow_agg_window_s,
         self.config.flow_agg_windows,
         self.config.flow_agg_topk,
         self.config.flow_agg_queue_depth,
         self.config.spike_factor,
         self.config.spike_min_drops,
         self.config.spike_baseline_windows,
         self.config.flow_agg_max_duty
         ) = validate_analytics_config(
            self.config.flow_agg_window_s,
            self.config.flow_agg_windows,
            self.config.flow_agg_topk,
            self.config.flow_agg_queue_depth,
            self.config.spike_factor,
            self.config.spike_min_drops,
            self.config.spike_baseline_windows,
            self.config.flow_agg_max_duty)
        (self.config.sysdump_dir,
         self.config.sysdump_retention,
         self.config.sysdump_max_bytes,
         self.config.sysdump_min_interval_s,
         self.config.sysdump_flows) = validate_flightrec_config(
            self.config.sysdump_dir,
            self.config.sysdump_retention,
            self.config.sysdump_max_bytes,
            self.config.sysdump_min_interval_s,
            self.config.sysdump_flows)
        self.flightrec = FlightRecorder(
            self._sysdump_collect,
            sysdump_dir=self.config.sysdump_dir,
            retention=self.config.sysdump_retention,
            max_bytes=self.config.sysdump_max_bytes,
            min_interval_s=self.config.sysdump_min_interval_s,
            node=self.config.node_name)
        self.analytics = FlowAnalytics(
            window_s=self.config.flow_agg_window_s,
            retention=self.config.flow_agg_windows,
            topk=self.config.flow_agg_topk,
            queue_depth=self.config.flow_agg_queue_depth,
            spike_factor=self.config.spike_factor,
            spike_min_drops=self.config.spike_min_drops,
            spike_baseline_windows=self.config.spike_baseline_windows,
            max_duty=self.config.flow_agg_max_duty,
            ep_identity=self._endpoint_identity,
            on_incident=self.record_incident,
            enabled=self.config.flow_agg_enabled)
        self.monitor.register("analytics", self.analytics.submit)
        # map-pressure monitor + graceful degradation
        # (datapath/pressure.py): samples CT occupancy / insert-drop
        # rate / NAT pool failures on a named controller (started in
        # start()), accelerates the CT aging sweep under pressure,
        # and records a `map-pressure` incident per episode
        from ..datapath.pressure import MapPressureMonitor

        self.pressure = MapPressureMonitor(
            sample_fn=lambda: self.loader.map_pressure(self._now()),
            on_accelerate=self._ct_gc_accelerate,
            on_restore=self._ct_gc_restore,
            record_incident=self.record_incident,
            ct_threshold=self.config.ct_pressure_threshold,
            ct_clear=self.config.ct_pressure_clear,
            gc_pressure_interval_s=self.config
            .ct_gc_pressure_interval,
            relax_after_s=self.config.ct_gc_relax_after,
            relax_factor=self.config.ct_gc_relax_factor,
            relax_max=self.config.ct_gc_relax_max,
            on_relax=self._ct_gc_relax)
        # hubble-relay analogue: add_relay_peer() builds it lazily;
        # when peers exist the sysdump bundle carries a relay-merged
        # flow sample stamped with node names
        self.relay = None

        # service LB: VIP -> Maglev backend selection, applied before
        # the policy pipeline (reference: pkg/service + bpf/lib/lb.h)
        from ..service import ServiceManager

        self.services = ServiceManager()
        self._serving = None  # start_serving() installs the ring path
        # set by ClusterServing on every member replica: the back
        # reference the Cluster serving-stats block, GET
        # /cluster/status and the cilium_cluster_* registry series
        # read (None = not part of a cluster serving tier)
        self._cluster = None
        # bandwidth manager (pkg/bandwidth analogue): per-endpoint
        # egress rates; None until some endpoint is limited
        self._bw = None
        self._bw_rates = None
        self._bw_limits: Dict[int, int] = {}
        # egress-gateway policies (name -> spec); endpoint churn
        # re-expands the pod selectors over local endpoints
        self._egress_policies: Dict[str, dict] = {}
        self._egress_rules_cache = None  # last expanded rule tuple
        self.endpoints.on_attach(
            lambda _pols: (self._recompile_nat()
                           if self._egress_policies else None))
        # connect-time LB flow cache (service/socklb.py, the bpf_sock
        # analogue): created on first service traffic
        self._socklb = None
        self._svc_version_seen = None  # affinity prune bookkeeping
        # mutual auth (pkg/auth): drop-observing handshake manager.
        # Fed explicitly where the batch's LOGICAL clock is in hand
        # (process_batch / the serving-path drain) — grants must be
        # stamped on the same clock the datapath compares against
        if self.config.mesh_auth:
            from .auth import AuthManager
            self.auth_manager = AuthManager(self)
        else:
            self.auth_manager = None
        # egress masquerade (applies after LB, before the datapath, so
        # CT tracks the post-NAT tuple)
        self.nat = None
        if self.config.masquerade:
            if not self.config.node_ip:
                # silently running WITHOUT masquerade when the operator
                # asked for it would leak pod source IPs
                raise ValueError(
                    "masquerade=True requires node_ip to be set")
            from ..service.nat import NATConfig

            self.nat = NATConfig(
                node_ip=self.config.node_ip,
                non_masquerade_cidrs=self.config.non_masquerade_cidrs,
            ).compile()

        # fqdn loop: DNS answers observed by the proxy become
        # identities + ipcache entries (reference: pkg/fqdn)
        from ..fqdn import NameManager

        self.fqdn = NameManager(self.allocator, self.delete_ipcache)
        self.proxy.observe_dns(self.fqdn.observe)

        # recorder: FlowFilter-gated pcap capture off the monitor
        # stream (reference: pkg/hubble/recorder)
        from ..flow.recorder import Recorder

        self.recorder = Recorder()
        self.monitor.register("recorder", self.recorder.consume)

        # clustermesh: remote clusters mirror in as incremental
        # identity/ipcache patches (reference: pkg/clustermesh)
        from ..clustermesh import (ClusterMesh, publish_endpoint_ip,
                                   withdraw_endpoint_ip)

        self.clustermesh = ClusterMesh(self.allocator,
                                       self.upsert_ipcache,
                                       self.delete_ipcache)
        if kvstore is not None:
            # agent side of the ipcache shared store: announce local
            # endpoint IPs for remote clusters/nodes to mirror
            def _publish_ep(kind: str, ep) -> None:
                if ep.identity is None:
                    return
                for ip in ep.ips:
                    if kind == "add":
                        publish_endpoint_ip(self.kvstore, ip,
                                            ep.identity.numeric_id)
                    else:
                        withdraw_endpoint_ip(self.kvstore, ip)

            self.endpoints.on_endpoint_change(_publish_ep)

        # ipcache catch-all: IPs no entry covers belong to WORLD
        # (reference: ipcache misses resolve to the world identity, so
        # toEntities:[world] policies see all external traffic)
        world = self.allocator.allocate(LabelSet.parse("reserved:world"))
        self.ipcache.upsert("0.0.0.0/0", world.numeric_id,
                            source="reserved")
        self.ipcache.upsert("::/0", world.numeric_id, source="reserved")

        # wiring: rule changes and identity churn both end in one
        # coalesced regeneration (SURVEY.md §3.3)
        self.repo.on_change(lambda rev: self.endpoints.regenerate())
        self.allocator.observe(self._on_identity_change)

        # initial empty attach so the datapath is live pre-endpoints
        self.endpoints.regenerate()

        # join the cluster identity plane LAST (the watch replays every
        # existing remote identity through the observer->patch chain,
        # which needs the wiring above in place)
        self.health = None
        if kvstore is not None:
            self.identity_sync = ClusterIdentitySync(self.kvstore,
                                                     self.allocator)
            # node registry + probe mesh (reference: cilium-health)
            from ..health import HealthMesh, NodeRegistry

            self.node_registry = NodeRegistry(self.kvstore)
            info = {}
            if self.config.api_socket_path:
                info["api_socket"] = self.config.api_socket_path
            if self.config.enable_encryption:
                from ..encryption import EncryptionManager

                self.encryption = EncryptionManager(
                    self.config.node_name, self.node_registry,
                    key_path=self.config.encryption_key_path,
                    keypair=encryption_keypair)
                info = self.encryption.advertise(info)
            self.node_registry.register(self.config.node_name, info)
            self.health = HealthMesh(self.node_registry,
                                     self.config.node_name)

        # the unified metrics registry (obs/registry.py): every
        # prometheus series GET /metrics serves is declared here —
        # collectors are lazy closures over this daemon, so
        # registration costs the hot path nothing
        from ..obs import build_daemon_registry

        self.registry = build_daemon_registry(self)
        # the SLO plane (ISSUE 19): history rings + burn-rate engine,
        # constructed AFTER the registry because the sampler pulls
        # registry.sample() — the registry's own cilium_slo_*
        # collectors resolve this attribute lazily for the same
        # reason.  The engine exists even with the sampler disabled
        # (history_interval 0): tests and operators can drive
        # tick() synchronously
        from ..obs import SLOEngine, SeriesHistory, default_slos
        from ..obs.slo import HISTORY_SERIES

        self.history = SeriesHistory(
            sample_fn=lambda: self.registry.sample(HISTORY_SERIES),
            kinds={name: kind for name in HISTORY_SERIES
                   if (kind := self.registry.kind(name)) is not None},
            interval_s=self.config.history_interval,
            slots=self.config.history_slots,
            slow_every=self.config.history_slow_every,
            slow_slots=self.config.history_slow_slots)
        self.slo = SLOEngine(
            self.history, default_slos(),
            record_incident=self.record_incident,
            interval_s=self.config.history_interval,
            fast_window_s=self.config.slo_fast_window,
            slow_window_s=self.config.slo_slow_window,
            page_burn=self.config.slo_page_burn,
            warn_burn=self.config.slo_warn_burn,
            clear_ticks=self.config.slo_clear_ticks,
            max_duty=self.config.slo_max_duty)

    # -- getters for flow enrichment ---------------------------------
    def _identity_labels(self, numeric: int) -> Tuple[str, ...]:
        ident = self.allocator.lookup_by_id(numeric)
        return tuple(str(l) for l in ident.labels) if ident else ()

    def _endpoint_info(self, ep_id: int) -> Tuple[str, int]:
        ep = self.endpoints.get(ep_id)
        return (ep.name, ep.id) if ep else ("", ep_id)

    def _endpoint_identity(self, ep_id: int) -> int:
        """ep id -> LOCAL numeric identity (the analytics plane's
        src/dst attribution for the local side of a flow)."""
        ep = self.endpoints.get(ep_id)
        if ep is not None and ep.identity is not None:
            return int(ep.identity.numeric_id)
        return 0

    # -- incidents + flight recorder -----------------------------------
    # thread-affinity: any
    def record_incident(self, kind: str, detail=None,
                        capture: bool = True):
        """The one incident entry every hook funnels through: spike
        detection (analytics, worker thread), watchdog restart /
        terminal (serving/runtime.py on_restart, watchdog thread),
        ladder demotion (_serving_demote, drain thread), terminal
        event-join worker (serving/eventplane.py on_terminal), and
        the manual API/CLI trigger.  Never raises — incident
        recording must not take down whatever plane just faulted."""
        try:
            return self.flightrec.record_incident(kind, detail,
                                                  capture=capture)
        except Exception:  # noqa: BLE001
            # hot-path-ok: the incident-recorder-itself-broke path —
            # by definition not steady state, and swallowing it
            # silently would hide a dead flight recorder
            logging.getLogger(__name__).warning(
                "incident recording failed (kind=%s)", kind,
                exc_info=True)
            return None

    def _serving_restart_incident(self, cause: str,
                                  terminal: bool) -> None:
        """ServingRuntime's on_restart hook (watchdog thread)."""
        from ..obs.flightrec import KIND_RESTART, KIND_TERMINAL

        self.record_incident(
            KIND_TERMINAL if terminal else KIND_RESTART,
            {"cause": cause})

    def _eventworker_incident(self, error: str) -> None:
        """EventJoinWorker's on_terminal hook (worker thread)."""
        from ..obs.flightrec import KIND_EVENTWORKER

        self.record_incident(KIND_EVENTWORKER, {"error": error})

    def _l7pool_incident(self, error: str) -> None:
        """L7WorkerPool's on_terminal hook (dying l7 thread)."""
        from ..obs.flightrec import KIND_L7POOL

        self.record_incident(KIND_L7POOL, {"error": error})

    def sysdump_now(self, trigger: str = "manual") -> dict:
        """The manual trigger (``GET /debug/sysdump?trigger=1``,
        ``cilium-tpu sysdump``): records a manual incident and
        captures OUTSIDE the auto rate limit.  A disabled recorder
        declines WITHOUT recording — a probe polling the 400-ing
        endpoint must not evict real incidents from the bounded
        history."""
        from ..obs.flightrec import KIND_MANUAL

        if not self.flightrec.enabled:
            return {"written": None, "enabled": False,
                    "bundles": [], "stats": self.flightrec.stats()}
        inc = self.flightrec.record_incident(KIND_MANUAL,
                                             {"trigger": trigger},
                                             capture=False)
        path = self.flightrec.capture(trigger=KIND_MANUAL,
                                      incident=inc, manual=True)
        return {"written": path,
                "enabled": self.flightrec.enabled,
                "bundles": self.flightrec.list_bundles(),
                "stats": self.flightrec.stats()}

    def flows_aggregate(self, top: int = 16) -> dict:
        """``GET /flows/aggregate``: the analytics snapshot (drains
        pending batches on THIS thread — query threads are off the
        dispatch path by definition)."""
        return self.analytics.snapshot(top=top)

    def obs_scrape_snapshot(self, cursor: int = 0, flows: int = 512,
                            top: int = 16) -> dict:
        """One relay scrape (ISSUE 14): registry exposition + the
        flow-ring tail since the caller's cursor + analytics top-K +
        tracer stats + the incident list, in one round trip —
        everything the parent-side ``ClusterObsRelay`` merges into
        the cluster views.  The ONE definition behind BOTH node
        modes (``ClusterNode.obs_scrape`` in-process and the
        ``nodehost`` ``obs_scrape`` control op): a field added to a
        single copy would silently diverge thread-mode and
        process-mode merged views (the PR 12 warm-recipe regression
        class)."""
        from ..proxy import registry as l7registry

        fls, new_cursor = self.observer.flows_since(int(cursor),
                                                    limit=int(flows))
        s = self._serving
        tr = s.get("tracer") if s is not None else None
        return {
            "metrics-text": self.registry.render(),
            "flows": [f.to_dict() for f in fls],
            "cursor": new_cursor,
            "top": self.flows_aggregate(top=int(top)),
            "trace": tr.stats() if tr is not None else None,
            "incidents": self.flightrec.incidents(),
            # per-plugin L7 parse latency (ISSUE 17 — PR 16 residue
            # c): the relay renders it node+plugin-labeled in the
            # merged exposition instead of summed across plugins
            "l7-by-plugin": l7registry.latency_snapshot(),
        }

    def slo_snapshot(self) -> dict:
        """``GET /slo`` body, node-stamped.  The ONE definition
        behind BOTH node modes (``ClusterNode.slo`` in-process and
        the ``nodehost`` ``slo`` control op) — the
        obs_scrape_snapshot contract."""
        out = self.slo.snapshot()
        out["node"] = self.config.node_name
        return out

    def history_snapshot(self, series=None, since: float = 0.0
                         ) -> dict:
        """``GET /metrics/history`` body, node-stamped — the one
        definition behind both node modes, like
        :meth:`slo_snapshot`."""
        out = self.history.query(series=series, since=float(since))
        out["node"] = self.config.node_name
        return out

    def add_relay_peer(self, name: str, observer) -> None:
        """Register a peer agent's Observer(-protocol object) for
        relay-merged flow views (the hubble-relay analogue; prep for
        the clustermesh serving tier).  Once any peer is registered,
        sysdump bundles include a relay flow sample stamped with
        node_name."""
        from ..flow.relay import Relay

        if self.relay is None:
            self.relay = Relay({self.config.node_name: self.observer})
        self.relay.add_peer(name, observer)

    def _sysdump_collect(self) -> dict:
        """The flight recorder's section collector.  Each section is
        INDIVIDUALLY contained — incident time is exactly when
        subsystems misbehave, and one failing snapshot must not cost
        the whole artifact."""
        from dataclasses import asdict

        out: dict = {}

        def section(name, fn):
            try:
                out[name] = fn()
            except Exception as e:  # noqa: BLE001
                out[name] = {"error": f"{type(e).__name__}: {e}"}

        cfg = self.config
        section("config", lambda: asdict(cfg))
        section("serving", self.serving_stats)
        section("compile",
                lambda: (self.loader.compile_log.snapshot()
                         if getattr(self.loader, "compile_log", None)
                         is not None else None))
        section("traces", lambda: self.debug_traces(limit=16))
        section("flows",
                lambda: [f.to_dict() for f in self.observer.get_flows(
                    number=cfg.sysdump_flows)])
        section("flow-aggregation",
                lambda: self.analytics.snapshot(top=16))
        section("metrics", self.registry.render)
        section("ct-snapshot", self.ct_snapshot_info)
        section("pressure", self.pressure.stats)
        # the SLO plane (ISSUE 19): a slo-burn capture must carry
        # the evidence — the full evaluation/episode state plus the
        # retained series window the burn was computed over
        section("slo", self.slo.snapshot)
        section("history", self.history.query)
        if self.relay is not None:
            section("relay-flows", lambda: self.relay.get_flows(
                number=min(cfg.sysdump_flows, 64)))
        return out

    # -- identity churn ----------------------------------------------
    def _on_identity_change(self, kind: str, ident) -> None:
        # CIDR-derived identities feed the ipcache (reference: ipcache
        # CIDR entries appear when policy references them).  Only the
        # MOST SPECIFIC cidr label is the identity's prefix — the
        # parent-prefix labels (r05, label-selecting fromCIDR) are
        # selection metadata; upserting them would route the whole
        # parent range onto this identity.
        cidr_labels = []
        if kind == "add":
            cidrs = [l.key for l in ident.labels
                     if l.source == SOURCE_CIDR]
            if cidrs:
                exact = max(cidrs,
                            key=lambda c: int(c.rsplit("/", 1)[1]))
                self.ipcache.upsert(exact, ident.numeric_id,
                                    source="generated")
                cidr_labels.append(exact)
        if not self._started:
            # no serve loop to patch yet, but cached resolutions are
            # STALE (peer sets freeze at resolve time) — without this,
            # an endpoint added after a policy import resolves against
            # the pre-churn peer sets and its traffic default-denies
            # until an unrelated revision bump (r04 endpoint-after-
            # policy ordering bug).  Cache-only clear: the
            # regeneration add_endpoint triggers re-resolves fresh,
            # and a full invalidate() would regen once per replayed
            # identity at startup.
            self.repo.invalidate_cache()
            # ...EXCEPT a CIDR/fqdn identity minted into a LIVE
            # pre-start world (the DNS proxy observes answers before
            # start()): its ipcache upsert must reach the datapath
            # NOW — no later regeneration is coming, so the cache-only
            # shortcut left toFQDNs traffic default-denying until an
            # unrelated revision bump.  Startup replay keeps the
            # cache-only path: identities restore before any endpoint
            # registers, so the gate below stays closed there.
            if not (kind == "add" and cidr_labels
                    and self.endpoints.list()):
                return
        # Incremental fast path (SURVEY.md §7 hard part #3): patch the
        # identity's verdict row + LPM slots in place — no re-resolve,
        # no compile_policy, no re-attach.  Falls back to a full
        # regeneration when the backend can't express the patch.
        if self.endpoints.patch_identity(kind, ident):
            ok = all(self.endpoints.patch_ipcache(c, ident.numeric_id)
                     for c in cidr_labels)
            if ok:
                return
        self.repo.invalidate()  # also triggers regeneration

    # -- graceful degradation (datapath/pressure.py hooks) -------------
    def _ct_gc_schedule(self, interval: float) -> None:
        """(Re-)register the CT aging sweep at ``interval`` — ONE
        definition for start(), patch_config, and the pressure
        monitor's accelerate/restore transitions."""
        self.controllers.update(
            "ct-gc", lambda: self.loader.gc(self._now()), interval)

    def _ct_gc_accelerate(self, interval: float) -> None:
        # thread-affinity: api -- the map-pressure controller thread
        """Pressure entered: accelerate the aging sweep and run one
        NOW (the ctmap adaptive-GC response)."""
        if not self._started:
            return
        self._ct_gc_schedule(interval)
        c = self.controllers.get("ct-gc")
        if c is not None:
            c.trigger()

    def _ct_gc_restore(self) -> None:
        # thread-affinity: api -- the map-pressure controller thread
        """Pressure cleared: back to the configured cadence."""
        if not self._started:
            return
        self._ct_gc_schedule(self.config.ct_gc_interval)

    def _ct_gc_relax(self, multiplier: float) -> None:
        # thread-affinity: api -- the map-pressure controller thread
        """A sustained-calm relax step (ISSUE 19 satellite): stretch
        the normal cadence by the monitor's bounded multiplier."""
        if not self._started:
            return
        self._ct_gc_schedule(self.config.ct_gc_interval * multiplier)

    # -- lifecycle ----------------------------------------------------
    def start(self) -> None:
        """Start background controllers (CT GC, fqdn TTL GC)."""
        self._started = True
        self._ct_gc_schedule(self.config.ct_gc_interval)
        if self.config.map_pressure_interval > 0:
            # one SYNCHRONOUS warm sample before the controller
            # ticks: compiles the occupancy executable while no
            # serving session's compile-count freeze is live, and
            # seeds the insert-drop/NAT-failure delta baselines
            self.pressure.sample()
            self.controllers.update(
                "map-pressure", self.pressure.sample,
                self.config.map_pressure_interval)
        # the SLO plane's sampler thread (obs/slo.py `slo-sampler`,
        # CTA002 domain `slo`): history sampling + burn evaluation,
        # duty-governed, never the drain thread.  start() is a no-op
        # when history_interval is 0
        self.slo.start()
        self.controllers.update(
            "fqdn-gc", self.fqdn.gc, self.config.fqdn_gc_interval)
        if self.auth_manager is not None:
            self.controllers.update(
                "auth-gc",
                lambda: self.auth_manager.gc(self._now()),
                self.config.auth_gc_interval)
        if self.config.hubble_listen:
            from ..flow.grpc_server import serve as hubble_serve

            self.hubble_server = hubble_serve(
                self.observer, self.config.hubble_listen,
                node_name=self.config.node_name)
        if self.health is not None:
            def _health_sweep():
                self.node_registry.heartbeat(self.config.node_name)
                # advertise the serving plane's fault state alongside
                # reachability (reference: cilium-health carries more
                # than liveness) — peers see a degraded/restarting
                # node in their node info, not just "reachable"
                self.node_registry.annotate(self.config.node_name,
                                            self._node_fault_info())
                self.health.probe_all()

            self.controllers.update(
                "health-probe", _health_sweep,
                self.config.health_probe_interval)
        if self.config.ct_snapshot_interval > 0:
            # periodic CT snapshots (the pinned-map persistence
            # analogue, but in-memory + on a cadence): recovery and
            # loader rebuilds restore established flows from the
            # last one when the live CT is gone
            self.controllers.update(
                "ct-snapshot",
                lambda: self.ct_snapshot_now(trigger="interval"),
                self.config.ct_snapshot_interval)
        if self.config.flow_agg_enabled:
            # close aggregation windows on WALL time: a drop burst
            # followed by total silence must still reach the spike
            # detector (ingest-driven rolls need a later batch that
            # may never come).  Controller thread = off the dispatch
            # path, like every other drain() caller
            self.controllers.update(
                "flow-agg-roll", self.analytics.drain,
                self.config.flow_agg_window_s)
        # endpoints whose identity allocation failed (kvstore outage)
        # retry here until they leave waiting-for-identity
        self.controllers.update(
            "identity-retry", self.endpoints.retry_pending_identities,
            5.0)
        # leased identity refs need a heartbeat (reference: etcd lease
        # keepalive on allocator slave keys)
        ttl = self.config.identity_lease_ttl
        backend = self.allocator._backend
        if ttl and backend is not None and hasattr(backend,
                                                   "refresh_refs"):
            self.controllers.update(
                "identity-keepalive", backend.refresh_refs,
                max(ttl / 3.0, 0.05))

    hubble_server = None

    def shutdown(self) -> None:
        self.slo.stop()
        self.controllers.stop_all()
        self.stop_serving()  # no-op when idle; drains in-flight work
        self.stop_dns_proxy()
        if self.hubble_server is not None:
            self.hubble_server.stop(grace=0.5)
        if self.exporter:
            self.exporter.close()
        if self.config.state_dir:
            self.checkpoint(self.config.state_dir)
        # unsubscribe kvstore watchers: a shared store outliving this
        # daemon would otherwise keep invoking (and retaining) it
        if self.identity_sync is not None:
            self.identity_sync.close()
        self.allocator.close()
        if self._fault_injector is not None:
            from ..infra import faults

            faults.disarm(self._fault_injector)
            self._fault_injector = None

    def _now(self) -> int:
        return int(time.time() - self._boot_time) + 1

    # -- egress gateway (CiliumEgressGatewayPolicy analogue) -----------
    def add_egress_gateway(self, name: str, selector: dict,
                           dest_cidrs, egress_ip: str) -> None:
        """Pods matching ``selector`` (a k8s LabelSelector dict) SNAT
        via ``egress_ip`` toward ``dest_cidrs`` (reference:
        CiliumEgressGatewayPolicy; single-node scope — the designated
        gateway is this node).

        Validates BEFORE storing: a malformed policy must raise here
        (and be skipped by the watcher), never poison every later
        regeneration's recompile."""
        import ipaddress as _ip

        eip = _ip.IPv4Address(egress_ip)  # raises on v6/garbage
        cidrs = []
        for c in dest_cidrs:
            net = _ip.ip_network(c, strict=False)
            if net.version != 4:
                raise ValueError(
                    f"egress gateway destinationCIDR {c!r}: the SNAT "
                    "path is v4-only")
            cidrs.append(str(net))
        if not cidrs:
            raise ValueError("egress gateway needs destinationCIDRs")
        selectors = (selector if isinstance(selector, (list, tuple))
                     else (selector,))
        if not selectors:
            raise ValueError("egress gateway needs a selector")
        # selectors must PARSE before the store: a stored-but-invalid
        # policy would raise from every later recompile (the
        # regeneration hook), breaking endpoint churn node-wide
        from ..policy.api import EndpointSelector

        for sel in selectors:
            EndpointSelector.from_dict(sel)  # raises on bad operators
        self._egress_policies[name] = {
            "selectors": tuple(selectors),
            "dest_cidrs": tuple(cidrs),
            "egress_ip": str(eip),
        }
        self._recompile_nat()

    def remove_egress_gateway(self, name: str) -> bool:
        if self._egress_policies.pop(name, None) is None:
            return False
        self._recompile_nat()
        return True

    def _egress_rules(self):
        """Expand the policies over the CURRENT local endpoints:
        (pod IP, destination CIDR, egress IP) triples."""
        from ..policy.api import EndpointSelector

        rules = []
        for pol in self._egress_policies.values():
            sels = [EndpointSelector.from_dict(s)
                    for s in pol["selectors"]]
            for ep in self.endpoints.list():
                if not any(s.matches(ep.labels) for s in sels):
                    continue
                for ip in ep.ips:
                    if ":" in ip:
                        continue  # v4-only SNAT path
                    for cidr in pol["dest_cidrs"]:
                        rules.append((ip, cidr, pol["egress_ip"]))
        return tuple(rules)

    def _recompile_nat(self) -> None:
        """Rebuild the NAT tensors from masquerade config + egress
        policies (endpoint churn re-expands the selectors — wired to
        the regeneration hook).  Skips the rebuild when the expanded
        rule set is unchanged (most regenerations don't touch the
        selected endpoints)."""
        from ..service.nat import NATConfig

        rules = self._egress_rules()
        if rules == self._egress_rules_cache:
            return
        self._egress_rules_cache = rules
        if self.config.masquerade:
            self.nat = NATConfig(
                node_ip=self.config.node_ip,
                non_masquerade_cidrs=self.config.non_masquerade_cidrs,
                egress_rules=rules,
            ).compile()
        elif rules:
            # egress gateway without masquerade: the exemption list
            # covers everything, so ONLY policy-matched rows SNAT
            self.nat = NATConfig(
                node_ip=self.config.node_ip or "0.0.0.0",
                non_masquerade_cidrs=("0.0.0.0/0",),
                egress_rules=rules,
            ).compile()
        else:
            self.nat = None

    # -- bandwidth manager (pkg/bandwidth / EDT analogue) --------------
    def set_bandwidth(self, ep_id: int,
                      bytes_per_sec: Optional[int]) -> None:
        """Set (or clear with None/0) an endpoint's egress rate limit
        in bytes/s (reference: kubernetes.io/egress-bandwidth pod
        annotation -> pkg/bandwidth -> EDT in bpf_lxc)."""
        import jax.numpy as jnp

        from ..datapath.bandwidth import (BandwidthState, rates_array)

        if bytes_per_sec:
            if self._bw_limits.get(int(ep_id)) == int(bytes_per_sec):
                return  # unchanged: skip the tensor rebuild
            self._bw_limits[int(ep_id)] = int(bytes_per_sec)
        else:
            if self._bw_limits.pop(int(ep_id), None) is None:
                return  # nothing was limited: nothing to rebuild
        if self._bw_limits:
            self._bw_rates = jnp.asarray(rates_array(self._bw_limits))
            if self._bw is None:
                self._bw = BandwidthState.create()
        else:
            self._bw_rates = None
            self._bw = None

    def _bw_police(self, hdr, now: int):
        """-> per-row REASON codes for the datapath's
        ``pre_drop_reason`` (None when no endpoint is limited)."""
        if self._bw_rates is None:
            return None
        import jax.numpy as jnp

        from ..datapath.bandwidth import bw_stage_jit

        if isinstance(hdr, np.ndarray):
            hdr = jnp.asarray(np.ascontiguousarray(hdr))
        reasons, self._bw = bw_stage_jit(self._bw, hdr,
                                         jnp.uint32(now),
                                         self._bw_rates)
        return reasons

    # -- the serve loop ----------------------------------------------
    def process_batch(self, hdr: np.ndarray,
                      now: Optional[int] = None) -> EventBatch:
        # thread-affinity: offline, api, cli
        """One packet tensor through LB -> datapath -> monitor."""
        if now is None:
            now = self._now()
        if (len(self.services) or self.nat is not None
                or self._bw_rates is not None):
            import jax.numpy as jnp

            # hdr stays ON DEVICE across the LB -> SNAT -> datapath
            # stages (loader.step accepts device arrays); the one host
            # fetch below feeds event decode, which needed the
            # rewritten rows anyway
            hdr_dev = hdr
            if len(self.services):
                # connect-time translation with a per-flow cache
                # (socket-LB analogue): established flows ride a
                # window probe; only genuinely-new flows pay the
                # frontend compare + Maglev
                from ..service.socklb import (SockLBTable,
                                              socklb_stage_jit)

                if self._socklb is None:
                    self._socklb = SockLBTable.create()
                svc_ver = self.services.version
                if self._svc_version_seen != svc_ver:
                    # backend-set change: expire ClientIP affinity
                    # pins whose backend no longer exists anywhere.
                    # Gated on affinity actually being in use — the
                    # sweep pays a d2h fetch of the pin table
                    if self.services.any_affinity:
                        self._socklb = self._socklb.prune_affinity(
                            self.services.backend_set())
                    self._svc_version_seen = svc_ver
                hdr_dev, _hits, svc_nobe, self._socklb = \
                    socklb_stage_jit(
                        self._socklb, self.services.tensors(),
                        jnp.asarray(np.ascontiguousarray(hdr_dev)),
                        jnp.uint32(now))
                t6 = self.services.tensors6()
                if t6 is not None:
                    # dual-stack: v6 frontends ride the per-packet
                    # pass (socklb judged only v4 rows)
                    from ..service import lb6_stage_jit

                    hdr_dev, _h6, nobe6 = lb6_stage_jit(t6, hdr_dev)
                    svc_nobe = svc_nobe | nobe6
            else:
                svc_nobe = None
            nat_drop = None
            if self.nat is not None:
                # conntrack-aware egress SNAT with port allocation
                # (service.nat.snat_egress): inbound-connection
                # replies keep their source; pool exhaustion marks the
                # row for a REASON_NAT_EXHAUSTED drop in the step
                hdr_dev, nat_drop = self.loader.masquerade(
                    self.nat, hdr_dev, now)
            bw_reasons = self._bw_police(hdr_dev, now)
            # svc_nobe (frontend hit, no backend) rides the dedicated
            # lb_drop channel: upstream's LB lookup runs BEFORE the
            # endpoint program, so NO_SERVICE wins over policy too
            out, row_map = self.loader.step(
                hdr_dev, now, pre_drop=nat_drop,
                pre_drop_reason=bw_reasons, lb_drop=svc_nobe,
                audit=self.config.policy_audit_mode)
            if self.nat is not None:
                # reverse translation AFTER the verdict (CT/policy see
                # the wire tuple; delivery + events see the restored
                # pod destination)
                hdr_dev = self.loader.reverse_nat(self.nat, hdr_dev,
                                                  now)
            hdr = np.asarray(hdr_dev)
            return self._finish_batch(out, hdr, row_map, now)
        out, row_map = self.loader.step(
            hdr, now, audit=self.config.policy_audit_mode)
        return self._finish_batch(out, hdr, row_map, now)

    def _finish_batch(self, out, hdr: np.ndarray, row_map,
                      now: int) -> EventBatch:
        # thread-affinity: offline, api, cli
        """The shared process_batch tail: decode -> auth observe ->
        monitor publish (ONE definition; a per-batch hook added here
        reaches both the routed and the plain path)."""
        batch = decode_out(out, hdr, row_map.numeric_array(),
                           timestamp=time.time())
        if self.auth_manager is not None:
            self.auth_manager.observe(batch, now)
        self.monitor.publish(self._filter_events(batch))
        # offline path: aggregate inline on the CALLER's thread (the
        # serving path instead drains on the event-join worker)
        self.analytics.drain()
        return batch

    def _filter_events(self, batch: EventBatch) -> EventBatch:
        """Per-endpoint event options + monitor aggregation (reference:
        pkg/option endpoint options DropNotification/TraceNotification/
        Debug and --monitor-aggregation).  Filters what the MONITOR
        plane sees; the caller's EventBatch (and metrics) keep every
        row — the reference likewise only gates event emission."""
        from ..core.packets import (COL_EP, COL_FLAGS, COL_PROTO,
                                    TCP_FIN, TCP_RST, TCP_SYN)
        from ..monitor.api import MSG_DROP, MSG_TRACE

        opts = self.endpoints.event_options()
        aggregate = self.config.monitor_aggregation == "medium"
        if not opts and not aggregate:
            return batch
        keep = np.ones(len(batch), dtype=bool)
        ep_col = batch.hdr[:, COL_EP]
        if aggregate:
            proto = batch.hdr[:, COL_PROTO]
            flags = batch.hdr[:, COL_FLAGS]
            boring = ((proto == 6)
                      & ((flags & (TCP_SYN | TCP_FIN | TCP_RST)) == 0)
                      & (batch.msg_type == MSG_TRACE))
            debug_eps = [e for e, o in opts.items() if o.get("Debug")]
            for e in debug_eps:
                boring &= ep_col != e
            keep &= ~boring
        for ep_id, o in opts.items():
            m = ep_col == ep_id
            if not o.get("DropNotification", True):
                keep &= ~(m & (batch.msg_type == MSG_DROP))
            if not o.get("TraceNotification", True):
                keep &= ~(m & (batch.msg_type == MSG_TRACE))
        if keep.all():
            return batch
        return EventBatch(
            msg_type=batch.msg_type[keep], verdict=batch.verdict[keep],
            reason=batch.reason[keep], ct_state=batch.ct_state[keep],
            identity=batch.identity[keep],
            proxy_port=batch.proxy_port[keep], hdr=batch.hdr[keep],
            timestamp=batch.timestamp)

    # -- policy API ---------------------------------------------------
    def policy_import(self, obj) -> int:
        return self.repo.add_obj(obj)

    def policy_delete(self, labels: List[str]) -> int:
        return self.repo.delete_by_labels(labels)

    def policy_get(self) -> dict:
        return {"revision": self.repo.revision,
                "rules": [rule_to_dict(r) for r in self.repo.rules()]}

    # -- endpoint API --------------------------------------------------
    def add_endpoint(self, name: str, ips: Tuple[str, ...],
                     labels: List[str],
                     named_ports: Optional[Dict[str, int]] = None
                     ) -> Endpoint:
        return self.endpoints.add(name, ips, LabelSet.parse(*labels),
                                  named_ports=named_ports)

    # -- L7 proxy API (the listener-facing entry) ----------------------
    def handle_l7_http(self, proxy_port: int, requests,
                       src_identity: int = 0) -> np.ndarray:
        """Verdict HTTP requests arriving on a redirect listener
        (1 = forward, 0 = 403)."""
        row = (self.loader.row_map.row(src_identity)
               if self.loader.row_map else 0)
        return self.proxy.handle_http(proxy_port, requests, row)

    def handle_l7_dns(self, proxy_port: int, qnames,
                      src_identity: int = 0) -> np.ndarray:
        row = (self.loader.row_map.row(src_identity)
               if self.loader.row_map else 0)
        return self.proxy.handle_dns(proxy_port, qnames, row)

    def handle_l7_kafka(self, proxy_port: int, requests,
                        src_identity: int = 0) -> np.ndarray:
        row = (self.loader.row_map.row(src_identity)
               if self.loader.row_map else 0)
        return self.proxy.handle_kafka(proxy_port, requests, row)

    def handle_l7(self, kind: str, proxy_port: int, requests,
                  src_identity: int = 0) -> np.ndarray:
        """Verdict requests of a PLUGIN protocol (cassandra,
        memcached, or anything proxy/registry.py knows)."""
        row = (self.loader.row_map.row(src_identity)
               if self.loader.row_map else 0)
        return self.proxy.handle(kind, proxy_port, requests, row)

    def proxy_stats(self) -> dict:
        """``GET /proxy/stats`` / ``cilium-tpu proxy stats``: the
        proxy plane's full picture — listener table, offline proxy
        counters, the LIVE L7 worker-pool ledger (or the last
        session's final one), per-plugin parse latency."""
        from ..proxy import registry as l7registry

        l7 = self._l7plane
        out = {
            "listeners": self.proxy.listeners(),
            "requests-total": self.proxy.requests_total,
            "requests-denied": self.proxy.requests_denied,
            "plane-active": l7 is not None,
            "parse-latency-by-plugin": l7registry.latency_snapshot(),
        }
        if l7 is not None:
            out["plane"] = l7.stats()
        elif self._l7_last is not None:
            out["plane"] = self._l7_last
        return out

    # -- k8s integration ----------------------------------------------
    _k8s_hub = None

    def k8s_watchers(self):
        """The k8s watcher aggregate (pkg/k8s/watchers analogue);
        drive it from an informer stream or fixture replay."""
        if self._k8s_hub is None:
            from ..k8s.watchers import K8sWatcherHub

            self._k8s_hub = K8sWatcherHub(self)
        return self._k8s_hub

    # -- clustermesh API ----------------------------------------------
    def connect_cluster(self, name: str, cluster_id: int, kv):
        """Join a remote cluster's store (reference: clustermesh
        config per remote cluster)."""
        return self.clustermesh.connect(name, cluster_id, kv)

    # -- serving path: device event ring -> monitor plane --------------
    def start_serving(self, ring_capacity: Optional[int] = None,
                      drain_every: int = 4,
                      trace_sample: int = 1024,
                      ingress: bool = False,
                      packed: Optional[bool] = None,
                      mesh=None,
                      shard_headroom: int = 2,
                      span_sample: Optional[int] = None,
                      window_queue_depth: Optional[int] = None,
                      event_gather: Optional[bool] = None,
                      superbatch_k: Optional[int] = None) -> None:
        """Switch to the SERVING monitor path: batches run through the
        fused datapath + device event-ring append (one dispatch, no
        per-packet host fetch), and only the compacted events cross to
        the host at the drain cadence — upstream's perf-ring economics
        (the kernel streams events, not packets).  :meth:`serve_batch`
        feeds it; :meth:`stop_serving` drains what is in flight.

        ``ingress=True`` additionally starts the serving FRONT END
        (cilium_tpu/serving): a bounded admission queue + adaptive
        batcher + drain loop, configured by the DaemonConfig
        ``serving_*`` knobs.  :meth:`submit` then feeds a packet
        STREAM; batches assemble, pad to the bucket ladder, and
        dispatch through :meth:`serve_batch` with sheds surfaced as
        monitor DROP events (``REASON_INGRESS_OVERFLOW``).

        ``packed=True`` (default: the ``serving_packed_ingest``
        config knob) ships eligible IPv4 single-stream buckets as the
        packed 16 B/packet wire format — 4x fewer h2d bytes — through
        :meth:`TPULoader.serve_packed`; ineligible traffic falls back
        to the wide shape per batch.

        ``span_sample`` (default: the ``serving_trace_sample``
        config knob) arms PER-PACKET TRACE SPANS on the ingress
        path: 1-in-N admitted packets carry a span through admission
        -> dequeue -> staging -> dispatch -> device -> verdict join
        (six monotonic stage timestamps + batch/bucket/mode
        annotations), surfaced via ``GET /debug/traces`` and
        ``cilium-tpu trace``.  0 = off = zero overhead; sampling is
        deterministic over the admitted-packet sequence.

        ``window_queue_depth`` / ``event_gather`` (defaults: the
        ``serving_window_queue_depth`` / ``serving_event_gather``
        config knobs) shape the ASYNC EVENT PLANE
        (serving/eventplane.py): drained windows are handed to a
        dedicated event-join worker over a bounded queue (overflow
        drops the offered window, COUNTED) and the fetch ships an
        occupancy-bounded device gather — d2h bytes scale with the
        events a window appended, not the ring capacity.  The drain
        thread's only event work is the 8-byte cursor sync + a queue
        push; decode / wide-column join / monitor fan-out all run on
        the worker.

        ``superbatch_k`` (default: the ``serving_superbatch_k``
        config knob) arms K-BATCH SUPERBATCH DISPATCH on the ingress
        path: the drain loop fuses up to K ready batches into one
        device dispatch (``lax.scan`` over the K steps — datapath +
        ring append per step, one cursor sync per drain tick), so
        per-dispatch Python cost is paid once per K batches.  K is a
        fallback-ladder rung property (demotion shrinks K before
        changing mode); assembly never waits for K batches, so
        low-load latency is unchanged.  1 disables.

        ``mesh=...`` (a ``jax.sharding.Mesh`` or a device count)
        switches to MULTI-CHIP serving: each assembled bucket is
        flow-routed (``parallel.route_by_flow`` — the RSS analogue)
        into per-shard blocks and dispatched through the sharded
        serve step (CT private per chip, policy/ipcache replicated,
        per-chip event rings drained round-robin).  Router overflow
        is counted in the metricsmap as ``REASON_ROUTE_OVERFLOW`` and
        surfaced as monitor DROP events.  ``shard_headroom`` sizes
        each shard's block at ``headroom * bucket / n_shards`` — the
        RSS ring-sizing trade-off: headroom 1 ships the fewest bytes
        but a full bucket of uniform flows overflows ~every shard
        (block == fair share, zero slack); the default 2 makes skew
        loss negligible for ~2x link/lane padding, and every drop is
        counted either way.

        ``ring_capacity`` (default: sized so no window can lap the
        ring) is the per-chip event ring's slot count.  A row appends
        at most one event, and a window spans at most
        ``drain_every + superbatch_k - 1`` dispatches of the top ladder
        rung (or of its shard block, ``headroom * rung / n_shards``
        rows per chip, where that is larger), so the default is that
        row count rounded up to a power of two, and never below 2^15.
        A smaller explicit ring overwrites under drop-heavy traffic,
        and every lost event is counted in ``lost``.

        Requires the tpu backend (the interpreter loader has no device
        ring).  Redirect events carry their proxy port as an index
        into the CURRENT listener table (monitor/ring.py); listeners
        added later stream as port 0 until serving is restarted."""
        import jax.numpy as jnp

        from ..datapath.loader import TPULoader
        from ..monitor.ring import (AsyncRingDrainer, MAX_PROXY_PORTS,
                                    ShardedAsyncRingDrainer)
        from ..serving import (BucketArena, ServingAlreadyActiveError,
                               ServingBackendError)

        if not isinstance(self.loader, TPULoader):
            raise ServingBackendError(
                "serving path requires backend='tpu'")
        if self._serving is not None:
            # silently replacing the drainer would drop its in-flight
            # window without any loss accounting
            raise ServingAlreadyActiveError(
                "already serving; stop_serving() first")
        if packed is None:
            packed = self.config.serving_packed_ingest
        if span_sample is None:
            span_sample = self.config.serving_trace_sample
        span_sample = int(span_sample)
        if span_sample < 0:
            # the whole obs-knob contract, applied to the explicit
            # argument: reject here, before self._serving is
            # assigned or the loader re-sharded — a raise below
            # would wedge the daemon in a phantom "already serving"
            # state
            raise ValueError("span_sample must be >= 0 "
                             "(0 disables span tracing)")
        if span_sample and not ingress:
            # validate BEFORE any side effect: below this point the
            # loader may already be re-sharded, and an error path
            # that leaves mutated placement behind is worse than the
            # misconfiguration it reports.  The config knob resolves
            # first so a daemon armed with serving_trace_sample fails
            # just as loudly as an explicit span_sample= argument
            # instead of silently tracing nothing
            raise ValueError(
                "span_sample tracing needs ingress=True: spans are "
                "allocated at IngressQueue admission")
        if window_queue_depth is None:
            window_queue_depth = self.config.serving_window_queue_depth
        window_queue_depth = int(window_queue_depth)
        if window_queue_depth < 1:
            raise ValueError("window_queue_depth must be >= 1")
        if event_gather is None:
            event_gather = self.config.serving_event_gather
        event_gather = bool(event_gather)
        # K-batch superbatch dispatch (ISSUE 11): validate the
        # per-session override exactly like the config knob — before
        # any side effect below
        from ..serving import validate_superbatch_config

        if superbatch_k is None:
            superbatch_k = self.config.serving_superbatch_k
        superbatch_k, k_ladder = validate_superbatch_config(
            superbatch_k)
        table = np.asarray(sorted(self.proxy.ports)[:MAX_PROXY_PORTS],
                           dtype=np.uint32)
        n_shards = 0
        if mesh is not None:
            from ..parallel import make_mesh, make_sharded_ring

            if isinstance(mesh, int):
                mesh = make_mesh(mesh)
            if "data" not in mesh.axis_names:
                # the sharded serving stack steers over the "data"
                # axis end-to-end (shard_state, make_sharded_ring,
                # make_sharded_serve_step); a differently-named axis
                # would die deep inside jax with an unbound-axis error
                raise ValueError(
                    f"serving mesh must have a 'data' axis, got "
                    f"axis_names={mesh.axis_names} (make_mesh builds "
                    f"the right one)")
            n_shards = int(mesh.devices.size)
            ladder = self.config.serving_bucket_ladder
            if ladder[0] % n_shards:
                raise ValueError(
                    f"sharded serving needs every ladder bucket "
                    f"divisible by the {n_shards}-chip mesh; smallest "
                    f"bucket is {ladder[0]}")
            if shard_headroom < 1:
                raise ValueError("shard_headroom must be >= 1")
        if ring_capacity is None:
            # a sharded session demotes onto one chip with this ring
            # size, so it covers both a whole rung and a shard block
            top = self.config.serving_bucket_ladder[-1]
            if n_shards:
                top = max(top, shard_headroom * top // n_shards)
            rows = (drain_every + superbatch_k - 1) * top
            ring_capacity = max(1 << 15, 1 << (rows - 1).bit_length())
        if mesh is not None:
            self.loader.serving_shard(mesh)
            drainer = ShardedAsyncRingDrainer(
                ring_capacity, n_shards,
                fresh_fn=lambda: make_sharded_ring(mesh,
                                                   ring_capacity),
                proxy_ports=table, gather=event_gather,
                compile_log=self.loader.compile_log)
        else:
            drainer = AsyncRingDrainer(
                ring_capacity, proxy_ports=table,
                gather=event_gather,
                compile_log=self.loader.compile_log)
        # the degraded-mode ladder (serving/ladder.py): rungs this
        # session can actually run — no mesh, no "sharded" rung; no
        # packing, no "single" rung; "wide" is always the floor
        from ..serving.ladder import (FallbackLadder, RUNG_SHARDED,
                                      RUNG_SINGLE, RUNG_WIDE)

        rungs = ([RUNG_SHARDED] if mesh is not None else []) \
            + ([RUNG_SINGLE] if packed else []) + [RUNG_WIDE]
        cfg = self.config
        # arena recycling horizon (batcher.py ownership-handoff
        # contract), EXTENDED to cover the async event plane: a
        # header slot must outlive the batches filling the next
        # window (drain_every) plus every window in flight on the
        # worker (bounded queue + the one being joined, +1 window of
        # mid-join slack) — windows keep their records by REFERENCE
        # (the swap-time snapshot), so the slot count is the only
        # thing that scales.  The worker REFUSES joins older than
        # join_horizon batches (counted drops), which is what makes
        # this depth a guarantee rather than a hope when the plane
        # stalls: a window's records span [seq-2*drain_every, seq),
        # slots recycle depth batches after allocation, so joins are
        # safe while (live seq - window seq) < depth - 2*drain_every;
        # the horizon keeps one extra drain_every of slack under that
        arena_depth = (window_queue_depth + 3) * drain_every + 2
        join_horizon = window_queue_depth * drain_every + 2
        # the event-join worker: the drain thread's only event work
        # becomes swap (cursor sync + async occupancy-bounded copy)
        # + one bounded-queue push; THIS thread finishes the
        # transfer, decodes, joins, and emits — restart-on-death
        # under the serving restart budget, terminal once exhausted
        from ..serving.eventplane import EventJoinWorker

        worker = EventJoinWorker(
            self._event_join, drop_fn=self._event_drop,
            queue_depth=window_queue_depth,
            restart_budget=cfg.serving_restart_budget,
            on_terminal=self._eventworker_incident)
        # the L7 proxy plane (serving/l7plane.py): redirected rows fan
        # out of the event-join worker into the bounded worker pool.
        # Held as a daemon ATTRIBUTE, not a _serving key —
        # _emit_ring_rows (event-worker thread) is contractually
        # barred from touching the session dict, and an atomic
        # attribute read is all the fan-out needs
        from ..serving.l7plane import L7Plane

        l7plane = L7Plane(
            self.proxy,
            workers=cfg.l7_workers,
            queue_depth=cfg.l7_queue_depth,
            restart_budget=cfg.serving_restart_budget,
            on_terminal=self._l7pool_incident,
            request_source=self.l7_request_source,
            dns_resolver=self.l7_dns_resolver)
        self._serving = {
            "drainer": drainer,
            "ring": drainer.fresh(),
            "table_dev": jnp.asarray(table) if len(table) else None,
            "proxy_table": table,  # host copy: demotion rebuilds the
            "ring_capacity": ring_capacity,  # drainer from these
            "trace_sample": trace_sample,
            "drain_every": drain_every,
            "seq": 0,
            "packed": bool(packed),
            "packed_pref": bool(packed),  # survives wide demotion
            "mesh": mesh,
            "mesh_pref": mesh,  # survives sharded demotion
            "n_shards": n_shards,
            "headroom": int(shard_headroom),
            "route_overflow": 0,
            "ladder": FallbackLadder(
                rungs,
                demote_threshold=cfg.serving_demote_threshold,
                promote_after=cfg.serving_promote_after,
                cooldown_s=cfg.serving_promote_cooldown_s,
                k_ladder=k_ladder),
            # superbatch dispatch: the configured K ceiling (the
            # ladder's live K can sit below it after demotions) —
            # also stretches the drain tick's window retention
            "superbatch_k": superbatch_k,
            # packed re-staging arena for the sharded path; same
            # recycling horizon as the batcher arena (routed/valid/
            # orig buffers ride windows onto the worker too)
            "route_arena": BucketArena(arena_depth),
            # batch_id (wrapped) -> (kind, host rows, (ep, dirn) or
            # None, numeric ids, timestamp); kind "wide" | "packed"
            "window": {},
            # the async event plane: worker + the spans accumulated
            # since the last drain tick (bid -> tuple[TraceSpan];
            # drain-thread-only, snapshotted into each DrainWindow)
            "eventplane": worker,
            "gather": event_gather,
            "join_horizon": join_horizon,
            "spans": {},
            # seq at the last drain tick: serve_batch ticks when
            # drain_every batches have dispatched since; the idle
            # hook ticks whenever ANY have (so windows flush when
            # traffic pauses instead of waiting for a batch that may
            # never come)
            "last_tick": 0,
            "tracer": None,
        }
        l7plane.start()
        self._l7plane = l7plane
        worker.start()
        if ingress:
            from ..core.packets import N_COLS
            from ..serving import ServingRuntime

            tracer = None
            if span_sample:
                from ..obs import SpanTracer

                tracer = SpanTracer(span_sample, seed=cfg.fault_seed)
            self._serving["tracer"] = tracer
            deadline_s = cfg.serving_dispatch_deadline_ms * 1e-3
            runtime = ServingRuntime(
                dispatch=self._serving_dispatch,
                # the K-batch leg: the ladder's CURRENT (mode, K)
                # rung decides the live K (sharded rungs pin K=1 —
                # superbatching is a single-chip dispatch shape)
                dispatch_super=self._serving_dispatch_super,
                superbatch_k=self._serving["ladder"].k,
                on_shed=self._publish_sheds,
                on_recovery_drop=self._publish_recovery_drops,
                queue_depth=cfg.serving_queue_depth,
                bucket_ladder=cfg.serving_bucket_ladder,
                max_wait_us=cfg.serving_max_wait_us,
                overflow_policy=cfg.serving_overflow_policy,
                expected_cols=N_COLS,
                # sharded dispatch flow-routes WIDE rows and re-packs
                # after routing, so the batcher packs only when the
                # bucket goes straight to the single-chip device leg
                pack=bool(packed) and mesh is None,
                # arena slots outlive every window in flight on the
                # event-join worker — the ownership handoff contract
                # in serving/batcher.py, sized above
                arena_depth=arena_depth,
                # fault tolerance: watchdog deadline + restart budget
                # from the serving_* knobs; the consumer-idle tick is
                # DERIVED from the deadline so sub-50ms deadlines are
                # honorable (a loop asleep in a 50ms wait cannot
                # notice churn faster than the wait)
                dispatch_deadline_s=deadline_s,
                restart_budget=cfg.serving_restart_budget,
                restart_backoff_s=cfg.serving_restart_backoff_ms
                * 1e-3,
                idle_wait_s=(min(0.05, deadline_s / 4)
                             if deadline_s > 0 else 0.05),
                # obs plane: span tracer + the batch-scoped
                # jax.profiler capture window.  No gauge_fn: the
                # registry reads the in-flight window live at scrape
                # (an idle-tick copy would disagree with /metrics
                # during sustained load, when the idle tick never
                # fires)
                tracer=tracer,
                # the async event plane owns sampled spans from the
                # dispatch return on: device/join stamp at true
                # window-join time on the worker
                span_sink=self._serving_span_sink,
                # idle-cadence drain tick: flush the pending window
                # when traffic pauses (the worker then joins it off
                # the dispatch path as usual)
                idle_fn=self._serving_event_idle_tick,
                # flight recorder: every watchdog restart (and the
                # terminal transition) is a named incident with an
                # auto-captured sysdump bundle
                on_restart=self._serving_restart_incident,
                profile_dir=cfg.profile_dir,
                profile_batches=cfg.profile_batches)
            self._serving["runtime"] = runtime
            runtime.start()

    def _serving_dispatch(self, hdr: np.ndarray, valid: np.ndarray,
                          n_valid: int, packed_meta=None):
        # thread-affinity: drain, api -- the ServingRuntime dispatch
        # callback; stop()'s final drain also lands here
        """The runtime's device leg: one padded bucket through
        serve_batch (padding masked out of CT/metrics/events).
        ``hdr`` arrives as a batcher arena slot whose recycling
        horizon outlives serve_batch's retain-by-reference window
        join (arena_depth above), so no copy is needed.

        Wide batches keep the legacy 3-arg serve_batch call shape —
        tests (and operators) wrap serve_batch with spies that only
        know (hdr, now, valid).

        The DEGRADED-MODE LADDER wraps the device leg: a dispatch
        failure counts toward the rung's demotion threshold; at the
        threshold the session demotes (sharded -> single-chip ->
        wide, CT carried via snapshot + restore) and the TRIGGERING
        batch retries on the demoted rung — it has not been recorded
        anywhere yet, so nothing double-counts.  Below the threshold
        the failure is CONTAINED (DispatchFailedError): the runtime
        accounts the batch as counted recovery drops and keeps the
        loop alive.  At the ladder floor failures escalate raw —
        burning the runtime's restart budget until terminal.
        Sustained health re-promotes after the cooldown."""
        from ..serving import DispatchFailedError

        s = self._serving
        try:
            info = self._serving_device_leg(hdr, valid, packed_meta)
        except Exception as e:  # noqa: BLE001 — any device-leg fault
            lad = s.get("ladder")
            if lad is None:
                raise
            cause = f"{type(e).__name__}: {e}"
            if not lad.record_failure(cause):
                if lad.at_floor:
                    raise  # not containable: escalate to the watchdog
                raise DispatchFailedError(
                    f"dispatch failed on rung {lad.rung!r} "
                    f"({lad.fail_streak}/{lad.demote_threshold}): "
                    f"{cause}") from e
            self._serving_demote(cause)
            # retry the triggering batch on the demoted rung: a
            # sharded-mode bucket is wide (the batcher never packs
            # under a mesh), and a packed bucket demoting to wide
            # unpacks host-side first
            if packed_meta is not None and not s["packed"]:
                from ..core.packets import unpack_rows_np

                hdr = unpack_rows_np(np.asarray(hdr), *packed_meta)
                packed_meta = None
            info = self._serving_device_leg(hdr, valid, packed_meta)
            if isinstance(info, dict):
                # obs plane: this batch CROSSED the demotion — its
                # sampled spans carry the flag (a trace through a
                # ladder transition is exactly what the span ring
                # exists to explain after the fact)
                info["demoted"] = True
        lad = s.get("ladder")
        if (lad is not None and lad.record_success()
                and s.get("runtime") is not None):
            self._serving_promote()
        return info

    def _serving_device_leg(self, hdr, valid, packed_meta):
        # thread-affinity: drain, api
        if packed_meta is None:
            return self.serve_batch(hdr, valid=valid)
        return self.serve_batch(hdr, valid=valid,
                                packed_meta=packed_meta)

    def _serving_dispatch_super(self, sb):
        # thread-affinity: drain
        """The runtime's K-BATCH device leg (ISSUE 11) with the same
        degraded-mode ladder wrap as :meth:`_serving_dispatch`: a
        failure counts toward the rung's demotion threshold; at the
        threshold the session demotes — shrinking K BEFORE changing
        mode — and the triggering K batches retry ONE BY ONE on the
        demoted rung (nothing recorded yet, so nothing
        double-counts).  Below the threshold the failure is contained
        exactly like a single-batch one."""
        from ..serving import DispatchFailedError

        s = self._serving
        try:
            info = self.serve_superbatch(sb)
        except Exception as e:  # noqa: BLE001 — any device-leg fault
            lad = s.get("ladder")
            if lad is None:
                raise
            cause = f"{type(e).__name__}: {e}"
            if not lad.record_failure(cause):
                if lad.at_floor:
                    raise  # not containable: escalate to the watchdog
                raise DispatchFailedError(
                    f"superbatch dispatch failed on rung "
                    f"{lad.rung!r} k={lad.k} "
                    f"({lad.fail_streak}/{lad.demote_threshold}): "
                    f"{cause}") from e
            self._serving_demote(cause)
            info = self._serving_retry_super_steps(sb)
            info["demoted"] = True
        lad = s.get("ladder")
        if (lad is not None and lad.record_success()
                and s.get("runtime") is not None):
            self._serving_promote()
        return info

    def _serving_retry_super_steps(self, sb) -> dict:
        # thread-affinity: drain
        """Retry a failed superbatch's steps one-by-one through the
        single-batch device leg (the most conservative rung of the K
        ladder) — a packed step unpacks host-side first when the
        demotion also left packed mode."""
        s = self._serving
        bids, total_h2d, mode = [], 0, None
        for k in range(sb.k):
            hdr = sb.hdr[k]
            meta = ((int(sb.eps[k]), int(sb.dirns[k]))
                    if sb.packed else None)
            if meta is not None and not s["packed"]:
                from ..core.packets import unpack_rows_np

                hdr = unpack_rows_np(np.asarray(hdr), *meta)
                meta = None
            info = self._serving_device_leg(hdr, sb.valid[k], meta)
            if isinstance(info, dict):
                bids.append(int(info.get("batch_id", -1)))
                total_h2d += int(info.get("h2d_bytes", 0))
                mode = info.get("mode", mode)
            else:
                bids.append(-1)
        return {"h2d_bytes": total_h2d,
                "mode": mode or ("packed" if s["packed"] else "wide"),
                "bids": bids,
                # K single dispatches actually ran — the dispatch
                # scoreboard must not count the retry as one fused
                # superbatch
                "dispatches": sb.k}

    def _serving_demote(self, cause: str) -> None:
        # thread-affinity: drain, api
        """One rung down (drain-thread context).  sharded -> single:
        drain the per-chip rings, SNAPSHOT the (sharded) CT, rebuild
        the single-device placement, and ct_restore the snapshot so
        established flows survive — the endpoint-regeneration
        discipline applied to the serving plane.  single -> wide:
        stop packing (both the batcher and the per-batch eligibility
        path)."""
        import logging

        s = self._serving
        lad = s["ladder"]
        old, old_k = lad.rung, lad.k
        new = lad.demote()
        from ..obs.flightrec import KIND_DEMOTION

        if new == old:
            # K-ONLY shrink (ISSUE 11): the mode keeps its
            # capability, only the superbatch amortization drops —
            # no ring/CT/placement mechanics, no warm-shape reset
            # (each K is its own executable shape; the smaller K is
            # already warm from before superbatching engaged, or
            # compiles under the cold-shape deadline exemption)
            # hot-path-ok: a LADDER DEMOTION is a rare contained-
            # failure event, never per-batch
            logging.getLogger(__name__).warning(
                "serving ladder shrinks superbatch K %d -> %d on "
                "rung %s: %s", old_k, lad.k, new, cause)
            self.record_incident(KIND_DEMOTION,
                                 {"from": f"{old}@k{old_k}",
                                  "to": f"{new}@k{lad.k}",
                                  "cause": cause})
            runtime = s.get("runtime")
            if runtime is not None:
                runtime.superbatch_k = lad.k
                # the triggering superbatch retries its steps
                # one-by-one: a single-batch shape that never
                # dispatched this session pays its XLA compile
                # during the retry, and the in-flight registration
                # (computed from the SUPERBATCH shape) would not be
                # deadline-exempt — the watchdog would deadline the
                # retry mid-flight and double-account rows whose
                # device effects already landed.  Same discipline
                # as the mode-demotion path below.
                runtime.reset_warm_shapes()
            return
        # hot-path-ok: a LADDER DEMOTION is a rare contained-failure
        # event (>= demote_threshold consecutive dispatch failures) —
        # the warning is part of the incident record, never per-batch
        logging.getLogger(__name__).warning(
            "serving ladder demotes %s -> %s: %s", old, new, cause)
        self.record_incident(KIND_DEMOTION,
                             {"from": old, "to": new, "cause": cause})
        if old == "sharded":
            from ..monitor.ring import AsyncRingDrainer

            # flush what the per-chip rings already hold onto the
            # event plane (the window keeps its own buffer/record
            # references, so rebuilding the drainer below is safe
            # while the worker is still joining it); best effort —
            # the ledger counts anything a wedged swap abandons
            try:
                self._serving_drain_tick(s)
            except Exception:  # noqa: BLE001
                # hot-path-ok: demotion failure path only (see above)
                logging.getLogger(__name__).warning(
                    "sharded ring drain failed during demotion; "
                    "in-flight window events lost (counted)")
            # CT continuity: snapshot (gathers every chip's private
            # shard), unshard, restore into the single-device
            # placement.  A wedged gather falls back to the last
            # periodic snapshot rather than dropping all flows.
            ct, fresh = None, False
            try:
                ct = self.loader.ct_snapshot()
                fresh = True
            except Exception:  # noqa: BLE001
                if self._ct_snap is not None:
                    ct = self._ct_snap["rows"]
                    # hot-path-ok: demotion failure path only
                    logging.getLogger(__name__).warning(
                        "live CT unreadable during demotion; "
                        "restoring the %.1fs-old periodic snapshot",
                        time.time() - self._ct_snap["taken-at"])
            self.loader.serving_unshard()
            if ct is not None:
                if fresh:
                    # a STALE fallback keeps its original taken-at:
                    # re-stamping it would zero the age every
                    # telemetry surface reports and hide how old a
                    # later restore really is
                    self._store_ct_snapshot(ct, trigger="demotion")
                self.loader.ct_restore(ct)
            s["mesh"] = None
            s["n_shards"] = 0
            d = AsyncRingDrainer(s["ring_capacity"],
                                 proxy_ports=s["proxy_table"],
                                 gather=s["gather"],
                                 compile_log=self.loader.compile_log)
            s["drainer"] = d
            s["ring"] = d.fresh()
            s["window"].clear()
        s["packed"] = (new == "single") and s["packed_pref"]
        runtime = s.get("runtime")
        if runtime is not None:
            # single-chip rungs pack in the batcher; wide never does
            runtime.batcher.pack = s["packed"] and s["mesh"] is None
            # a mode demotion enters the new mode at ITS best K
            runtime.superbatch_k = lad.k
            # the demoted mode's executables compile on first
            # dispatch — not a hang
            runtime.reset_warm_shapes()

    def _serving_promote(self) -> None:
        # thread-affinity: drain, api
        """One rung back up after sustained health + cooldown
        (drain-thread context).  wide -> single re-enables packing;
        single -> sharded re-places the live state on the mesh and
        swaps back to per-chip rings.  NOTE: re-sharding scatters CT
        rows by position, not flow hash — flows whose entry lands on
        a different chip than their flow route re-establish on their
        next packet (counted as NEW, never dropped); demotion is the
        direction that must be lossless, and is."""
        import logging

        s = self._serving
        lad = s["ladder"]
        old, old_k = lad.rung, lad.k
        new = lad.promote()
        if new == old:
            # K-ONLY growth: re-arm the superbatch amortization on
            # the same mode — no placement/ring mechanics
            # hot-path-ok: promotions happen at most once per
            # cooldown_s (hysteresis-gated recovery)
            logging.getLogger(__name__).info(
                "serving ladder grows superbatch K %d -> %d on "
                "rung %s", old_k, lad.k, new)
            runtime = s.get("runtime")
            if runtime is not None:
                runtime.superbatch_k = lad.k
            return
        # hot-path-ok: promotions happen at most once per cooldown_s
        # (hysteresis-gated recovery, not steady state)
        logging.getLogger(__name__).info(
            "serving ladder promotes %s -> %s", old, new)
        if new == "sharded":
            from ..monitor.ring import ShardedAsyncRingDrainer
            from ..parallel import make_sharded_ring

            mesh = s["mesh_pref"]
            try:
                self._serving_drain_tick(s)
            except Exception:  # noqa: BLE001
                pass
            self.loader.serving_shard(mesh)
            s["mesh"] = mesh
            s["n_shards"] = int(mesh.devices.size)
            cap = s["ring_capacity"]
            s["drainer"] = ShardedAsyncRingDrainer(
                cap, s["n_shards"],
                fresh_fn=lambda: make_sharded_ring(mesh, cap),
                proxy_ports=s["proxy_table"], gather=s["gather"],
                compile_log=self.loader.compile_log)
            s["ring"] = s["drainer"].fresh()
            s["window"].clear()
            s["packed"] = False
        else:  # -> single
            s["packed"] = s["packed_pref"]
        runtime = s.get("runtime")
        if runtime is not None:
            runtime.batcher.pack = s["packed"] and s["mesh"] is None
            # a mode promotion enters the better mode at its
            # SMALLEST K (the inverse of demote's entry-at-best-K)
            runtime.superbatch_k = lad.k
            runtime.reset_warm_shapes()

    def _publish_recovery_drops(self, rows: Optional[np.ndarray],
                                count: int, reason: int) -> None:
        # thread-affinity: drain, watchdog, api
        """Recovery-plane drops (dead/hung/failed dispatch, dead-loop
        stop sweep) -> metricsmap + decoded monitor DROP events —
        the same double surfacing REASON_ROUTE_OVERFLOW gets, so the
        loss is visible both to counters and to flow consumers."""
        from ..monitor.api import synth_drop_batch

        self.loader.add_host_drops(reason, count)
        if rows is None or not len(rows):
            return
        batch = synth_drop_batch(rows, reason, time.time())
        self.monitor.publish(self._filter_events(batch))

    def _publish_sheds(self, rows: Optional[np.ndarray],
                       count: int) -> None:
        # thread-affinity: drain, api
        """Admission sheds -> monitor DROP events.  ``rows`` is the
        bounded retained subset; ``count`` is exact (the counter in
        serving stats carries the difference when retention capped)."""
        from ..datapath.verdict import REASON_INGRESS_OVERFLOW
        from ..monitor.api import synth_drop_batch

        if rows is None or not len(rows):
            return
        batch = synth_drop_batch(rows, REASON_INGRESS_OVERFLOW,
                                 time.time())
        self.monitor.publish(self._filter_events(batch))

    def _publish_cluster_drops(self, rows: Optional[np.ndarray],
                               count: int) -> None:
        # thread-affinity: router, api
        """Cluster-router sheds -> metricsmap + decoded monitor DROP
        events on THIS node (the flow's owner, or a surviving peer
        when the owner died) — the same double surfacing every other
        host-side drop gets.  ``rows`` is the bounded retained
        subset; ``count`` is exact."""
        from ..datapath.verdict import REASON_CLUSTER_OVERFLOW
        from ..monitor.api import synth_drop_batch

        self.loader.add_host_drops(REASON_CLUSTER_OVERFLOW, count)
        if rows is None or not len(rows):
            return
        batch = synth_drop_batch(rows, REASON_CLUSTER_OVERFLOW,
                                 time.time())
        self.monitor.publish(self._filter_events(batch))

    def submit(self, rows: np.ndarray,
               t: Optional[float] = None) -> int:
        # thread-affinity: any
        """Offer a chunk of header rows to the serving front end
        (requires ``start_serving(ingress=True)``); returns how many
        were admitted.  Never blocks — overflow sheds by the
        configured policy and surfaces as counted DROP events."""
        from ..serving import ServingNotStartedError

        s = self._serving
        runtime = s.get("runtime") if s is not None else None
        if runtime is None:
            raise ServingNotStartedError(
                "call start_serving(ingress=True) first")
        return runtime.submit(rows, t)

    # -- CT snapshots (periodic + on-demotion + on-demand) -------------
    def ct_snapshot_now(self, trigger: str = "manual") -> dict:
        """Take and retain a CT snapshot (dense portable rows).  The
        retained copy rides recovery paths — a demotion or loader
        rebuild whose live CT is unreadable restores from it instead
        of dropping every established flow."""
        rows = self.loader.ct_snapshot()
        return self._store_ct_snapshot(rows, trigger)

    def _store_ct_snapshot(self, rows: np.ndarray,
                           trigger: str) -> dict:
        s = self._serving
        lad = s.get("ladder") if s is not None else None
        self._ct_snap = {
            "rows": np.array(rows, copy=True),
            "taken-at": time.time(),
            "trigger": trigger,
            "mode": lad.rung if lad is not None else "offline",
            "revision": self.repo.revision,
        }
        return self.ct_snapshot_info()

    def ct_snapshot_info(self) -> Optional[dict]:
        """Metadata of the retained CT snapshot (None before the
        first one) — surfaced via serving stats / status /
        prometheus so operators can see how stale a recovery
        restore would be."""
        snap = self._ct_snap
        if snap is None:
            return None
        return {
            "age-seconds": round(time.time() - snap["taken-at"], 3),
            "entries": int(len(snap["rows"])),
            "trigger": snap["trigger"],
            "mode": snap["mode"],
            "revision": snap["revision"],
        }

    def restore_ct_snapshot(self) -> bool:
        """Restore the retained snapshot into the live loader (the
        recovery entry for an operator-driven or rebuild-driven CT
        reload).  False when no snapshot has been taken."""
        if self._ct_snap is None:
            return False
        self.loader.ct_restore(self._ct_snap["rows"])
        return True

    def _node_fault_info(self) -> dict:
        """The serving fault state advertised in the node registry
        (health plane): enough for a peer (or operator sweep) to see
        a degraded/restarting node without scraping its API."""
        out = {}
        s = self._serving
        if s is not None:
            lad = s.get("ladder")
            if lad is not None:
                out["serving-mode"] = lad.rung
                out["serving-degraded"] = lad.degraded
            runtime = s.get("runtime")
            if runtime is not None:
                out["serving-restarts"] = runtime.stats.restarts
        snap = self.ct_snapshot_info()
        if snap is not None:
            out["ct-snapshot-age-seconds"] = snap["age-seconds"]
        return out

    def serving_stats(self) -> dict:
        """GET /serving — front-end telemetry + ring-drain counters +
        the fault-tolerance plane (mode/ladder, restarts, recovery
        drops, CT-snapshot age)."""
        s = self._serving
        if s is None:
            return {"active": False}
        d = s["drainer"]
        out = {"active": True,
               "ring": {"windows": d.windows, "events": d.events,
                        "lost": d.lost, "capacity": s["ring_capacity"]},
               "event-plane": s["eventplane"].stats(),
               "analytics": self.analytics.stats(),
               # the map-pressure block (datapath/pressure.py):
               # cached last sample + state machine — never touches
               # the device at render time
               "pressure": self.pressure.stats(),
               # the SLO block (obs/slo.py): verdict + per-SLO
               # states off the engine's cached last evaluation —
               # a stats render never evaluates
               "slo": self.slo.stats(),
               "history": self.history.stats()}
        if s["n_shards"]:
            out["shards"] = s["n_shards"]
            out["shard-headroom"] = s["headroom"]
            out["route-overflow"] = s["route_overflow"]
        lad = s.get("ladder")
        if lad is not None:
            out["mode"] = lad.rung
            out["ladder"] = lad.to_dict()
        runtime = s.get("runtime")
        if runtime is not None:
            out.update(runtime.snapshot())
        snap = self.ct_snapshot_info()
        if snap is not None:
            out["ct-snapshot"] = snap
        log = getattr(self.loader, "compile_log", None)
        if log is not None:
            out["compile"] = log.summary()
        # live-churn plane (datapath/tables.py): published generation,
        # swap/update latency, delta-compile scoreboard
        tstats = getattr(self.loader, "table_stats", None)
        if tstats is not None:
            out["tables"] = tstats()
        if self._cluster is not None:
            # the Cluster block: tier-level counters only (router,
            # membership, failovers) — cheap by contract, because
            # every member node renders it per scrape
            out["cluster"] = self._cluster.summary()
        l7 = self._l7plane
        if l7 is not None:
            out["l7"] = l7.stats()
        return out

    def debug_traces(self, limit: int = 64) -> dict:
        """``GET /debug/traces``: the sampled span plane (per-stage
        aggregate histograms, recent + slowest completed traces) plus
        the compile-event log — the introspection surfaces an
        operator reaches for when a latency histogram says "slow"
        but not "where"."""
        out = {"enabled": False}
        s = self._serving
        tracer = s.get("tracer") if s is not None else None
        if tracer is not None:
            out = tracer.snapshot(limit=limit)
            out["enabled"] = True
        lad = s.get("ladder") if s is not None else None
        if lad is not None:
            out["mode"] = lad.rung
        log = getattr(self.loader, "compile_log", None)
        if log is not None:
            out["compile"] = log.snapshot()
        runtime = s.get("runtime") if s is not None else None
        if runtime is not None:
            prof = runtime.profile_status()
            if prof is not None:
                out["profile"] = prof
        return out

    def serve_batch(self, hdr: np.ndarray,
                    now: Optional[int] = None,
                    valid: Optional[np.ndarray] = None,
                    packed_meta=None) -> Optional[dict]:
        # thread-affinity: drain, api
        """One serving-path batch: dispatch, retain the host header
        rows for the event join, drain/emit every ``drain_every``
        batches.  ``hdr`` must be HOST memory (the serving path never
        fetches it back).  ``valid`` masks the adaptive batcher's
        padding rows (they touch neither CT, metrics, nor the ring).

        ``packed_meta=(ep, dirn)`` marks ``hdr`` as PACKED [N, 4]
        wire rows (16 B/packet h2d) with the stream-metadata scalars;
        the fused packed step unpacks on device and the event join
        reconstructs wide columns host-side only for the few rows the
        ring kept.  Under ``start_serving(mesh=...)`` the batch is
        flow-routed into per-shard blocks first (wide input only —
        the router needs wide columns; the 16 B format then ships the
        ROUTED rows).  Returns link accounting ({"h2d_bytes",
        "mode"}) for the runtime's telemetry."""
        from ..serving import ServingNotStartedError

        s = self._serving
        if s is None:
            raise ServingNotStartedError("call start_serving() first")
        if now is None:
            now = self._now()
        # drain tick BEFORE the dispatch (not after, as pre-PR5): the
        # window then covers exactly the batches dispatched since the
        # previous tick, every one of which has already handed its
        # sampled spans to _serving_span_sink — so the swap-time
        # snapshot is complete and the worker can stamp device/join
        # at true window-join time with no cross-thread rendezvous
        if s["seq"] - s["last_tick"] >= s["drain_every"]:
            self._serving_drain_tick(s)
        bid = s["seq"] & 0x1FFF  # ring batch field width
        if s["mesh"] is not None:
            if packed_meta is not None:
                raise ValueError(
                    "sharded serving routes wide rows (packing "
                    "happens after flow routing); submit wide "
                    "batches")
            info = self._serve_batch_sharded(s, hdr, now, bid, valid)
        elif packed_meta is not None:
            ep, dirn = packed_meta
            s["ring"], row_map = self.loader.serve_packed(
                s["ring"], hdr, now, bid, ep, dirn,
                trace_sample=s["trace_sample"],
                proxy_ports=s["table_dev"],
                audit=self.config.policy_audit_mode,
                valid=valid)
            self._serving_snapshot_numerics(s, row_map)
            s["window"][bid] = ("packed", np.asarray(hdr),
                                (int(ep), int(dirn)), s["numerics"],
                                time.time())
            info = {"h2d_bytes": hdr.nbytes, "mode": "packed",
                    "batch_id": bid}
        else:
            s["ring"], row_map = self.loader.serve(
                s["ring"], hdr, now, bid,
                trace_sample=s["trace_sample"],
                proxy_ports=s["table_dev"],
                audit=self.config.policy_audit_mode,
                valid=valid)
            self._serving_snapshot_numerics(s, row_map)
            # retained by REFERENCE: callers must not mutate hdr
            # until its window drains (the ingress runtime satisfies
            # this via the batcher arena's recycling horizon)
            s["window"][bid] = ("wide", np.asarray(hdr), None,
                                s["numerics"], time.time())
            info = {"h2d_bytes": hdr.nbytes, "mode": "wide",
                    "batch_id": bid}
        s["seq"] += 1
        return info

    def _serving_snapshot_numerics(self, s, row_map) -> None:
        # thread-affinity: drain, api
        # numeric_array() copies the whole row->numeric table; the map
        # only changes on identity churn, so snapshot per
        # (object, version) — the map object is REUSED and mutated
        # across regenerations (object identity alone would serve
        # stale numerics forever), and the retained REFERENCE keeps
        # the comparison sound if the loader ever swaps in a fresh
        # map (an id() of a collected object can false-match)
        if (s.get("row_map") is not row_map
                or s.get("row_map_version") != row_map.version):
            s["row_map"] = row_map
            s["row_map_version"] = row_map.version
            s["numerics"] = row_map.numeric_array()

    def serve_superbatch(self, sb, now: Optional[int] = None) -> dict:
        # thread-affinity: drain, api
        """K batches in ONE device dispatch (ISSUE 11): ``sb`` is the
        batcher's :class:`~..serving.batcher.SuperBatch` — [K, bucket,
        cols] rows + [K, bucket] valid masks.  Each inner step gets
        its own batch id (``seq + k``, the same 13-bit wrap the ring
        uses) and its own retained window record, so the event-join
        worker decodes a superbatch window exactly like K single
        batches; the drain tick still fires per DISPATCH, which is
        the one-cursor-sync-per-K-batches the amortization buys.
        Returns link accounting plus the per-step ``bids`` the
        runtime's span sink needs."""
        from ..serving import ServingNotStartedError

        s = self._serving
        if s is None:
            raise ServingNotStartedError("call start_serving() first")
        if s["mesh"] is not None:
            # the sharded session's ring is per-chip and its state
            # mesh-placed: feeding them to the single-chip superbatch
            # executable would crash opaquely (or worse) — mirror
            # serve_batch's explicit rejection.  The ladder pins K=1
            # on the sharded rung, so the drain loop never gets here;
            # this guards direct callers (warm-up scripts, operators)
            raise ValueError(
                "superbatch dispatch is a single-chip shape; "
                "sharded serving flow-routes per batch (the ladder "
                "pins K=1 on the sharded rung)")
        if now is None:
            now = self._now()
        if s["seq"] - s["last_tick"] >= s["drain_every"]:
            self._serving_drain_tick(s)
        bid0 = s["seq"] & 0x1FFF
        s["ring"], row_map = self.loader.serve_superbatch(
            s["ring"], sb.hdr, now, bid0, eps=sb.eps, dirns=sb.dirns,
            trace_sample=s["trace_sample"],
            proxy_ports=s["table_dev"],
            audit=self.config.policy_audit_mode,
            valid=sb.valid, packed=sb.packed)
        self._serving_snapshot_numerics(s, row_map)
        ts = time.time()
        kind = "packed" if sb.packed else "wide"
        bids = []
        for k in range(sb.k):
            bid = (s["seq"] + k) & 0x1FFF
            meta = ((int(sb.eps[k]), int(sb.dirns[k]))
                    if sb.packed else None)
            # per-step records retained by REFERENCE (views into the
            # superbatch arena slot, whose per-dispatch recycling
            # horizon spans K times more batches than a single slot)
            s["window"][bid] = (kind, sb.hdr[k], meta, s["numerics"],
                                ts)
            bids.append(bid)
        s["seq"] += sb.k
        return {"h2d_bytes": sb.hdr.nbytes, "mode": f"super-{kind}",
                "batch_id0": bid0, "bids": bids, "k": sb.k}

    def _serve_batch_sharded(self, s, hdr: np.ndarray, now: int,
                             bid: int, valid) -> dict:
        # thread-affinity: drain, api
        """The multi-chip leg: flow-route the bucket into per-shard
        blocks (the RSS analogue), account router overflow as
        REASON_ROUTE_OVERFLOW (metricsmap + synthesized DROP events),
        re-pack eligible routed batches to 16 B/packet, and dispatch
        the sharded serve step (CT private per chip, per-chip rings)."""
        from ..core.packets import (N_COLS, PACKED_COLS,
                                    pack_eligibility, pack_rows)
        from ..datapath.verdict import REASON_ROUTE_OVERFLOW
        from ..monitor.api import synth_drop_batch
        from ..parallel import route_by_flow

        S = s["n_shards"]
        hdr = np.asarray(hdr)
        if valid is None:
            rows = hdr
        else:
            n_valid = int(valid.sum())
            # the batcher always produces prefix-valid buckets (slice
            # = view, no copy); a direct caller may pass an arbitrary
            # mask — honor the holes (fancy-index copy) rather than
            # silently routing masked-out rows
            if valid[:n_valid].all():
                rows = hdr[:n_valid]
            else:
                rows = hdr[valid]
        bucket = max(len(hdr), S)
        # ONE routed shape per ladder rung: block is fixed at
        # headroom * bucket / S across batches of this rung (a
        # data-dependent block would retrace the sharded step every
        # batch); the headroom slack absorbs flow skew — see
        # start_serving.  Routed/valid/orig buffers come from the
        # serving arena (same recycling-horizon contract as the
        # batcher slots), keeping this leg allocation-free too.
        block = s["headroom"] * bucket // S
        arena = s["route_arena"]
        out = (arena.slot(S * block, N_COLS),
               arena.slot(S * block, 0, dtype=bool),
               arena.slot(S * block, 0, dtype=np.int64))
        routed, rvalid, orig, n_ovf = route_by_flow(rows, S, block,
                                                    out=out)
        if n_ovf:
            # a shard's block overflowed (flow skew): the loss is
            # counted where operators look (metricsmap) AND each
            # overflowed packet surfaces as a DROP event, exactly
            # like admission sheds
            s["route_overflow"] += n_ovf
            self.loader.add_route_overflow(n_ovf)
            dropped = np.ones(len(rows), dtype=bool)
            dropped[orig[orig >= 0]] = False
            batch = synth_drop_batch(rows[dropped],
                                     REASON_ROUTE_OVERFLOW,
                                     time.time())
            self.monitor.publish(self._filter_events(batch))
        ship, meta, kind = routed, None, "wide"
        if s["packed"]:
            ok, ep, dirn = pack_eligibility(rows)
            if ok:
                ship = pack_rows(
                    routed, out=s["route_arena"].slot(len(routed),
                                                      PACKED_COLS))
                meta, kind = (ep, dirn), "packed"
        s["ring"], row_map = self.loader.serve_sharded(
            s["ring"], ship, now, bid,
            trace_sample=s["trace_sample"],
            proxy_ports=s["table_dev"],
            audit=self.config.policy_audit_mode,
            valid=rvalid, packed_meta=meta)
        self._serving_snapshot_numerics(s, row_map)
        s["window"][bid] = (kind, ship, meta, s["numerics"],
                            time.time())
        info = {"h2d_bytes": ship.nbytes,
                "mode": f"sharded-{kind}", "batch_id": bid}
        if s.get("tracer") is not None:
            # per-shard span attribution: invert the router's
            # orig-index map into batch_pos -> owning shard (routed
            # position // block); -1 marks a route-overflow drop.
            # Only paid while tracing is armed, O(routed) per batch
            shard_of = np.full(len(rows), -1, dtype=np.int64)
            p = np.flatnonzero(orig >= 0)
            shard_of[orig[p]] = p // block
            info["shard_of"] = shard_of
        return info

    def _serving_drain_tick(self, s) -> None:
        # thread-affinity: drain, api
        """The drain thread's ENTIRE event leg after the async event
        plane (PR 5): block on the 8-byte cursor, start the
        occupancy-bounded async copy (``swap_window``), and push the
        window handle + its join context — the retained batch records
        and the spans accumulated since the last tick — onto the
        worker's bounded queue.  No d2h buffer wait, no decode, no
        wide-column join, no monitor fan-out here; a queue overflow
        drops the window COUNTED (never silently)."""
        from ..serving.eventplane import DrainWindow

        # a first gather compile inside a dispatch is not a hang
        on_cold = getattr(s.get("runtime"), "exempt_inflight", None)
        window, s["ring"] = s["drainer"].swap_window(s["ring"], on_cold)
        s["last_tick"] = s["seq"]
        spans, s["spans"] = s["spans"], {}
        # shallow snapshot: the window keeps the records (arena slot
        # + numerics references) alive on the worker regardless of
        # the pruning below — zero copy, the ownership-horizon shape
        records = dict(s["window"])
        s["eventplane"].submit(DrainWindow(
            window, records, spans, s["n_shards"],
            tracer=s.get("tracer"), seq=s["seq"]))
        # retain headers for the batches filling the next window plus
        # one horizon of slack; in-flight windows hold their own refs.
        # A superbatch advances seq by K in one dispatch, so a window
        # spans up to drain_every + K - 1 batch records — the
        # retention stretches by the configured K ceiling
        live = {(s["seq"] - 1 - i) & 0x1FFF
                for i in range(2 * (s["drain_every"]
                                    + s.get("superbatch_k", 1)))}
        for b in list(s["window"]):
            if b not in live:
                del s["window"][b]

    def _serving_event_idle_tick(self) -> None:
        # thread-affinity: drain
        """ServingRuntime's idle hook (drain-thread context, queue
        empty): if any batch dispatched since the last drain tick,
        tick now — a traffic pause must flush the pending window to
        the event plane instead of letting its events (and sampled
        spans) wait for a drain_every-th batch that may never come.
        The monitor plane drains at its own cadence, as the
        reference's userspace perf-ring reader does."""
        s = self._serving
        if s is None or s["seq"] <= s["last_tick"]:
            return
        try:
            self._serving_drain_tick(s)
        except Exception:  # noqa: BLE001 — an idle-cadence swap
            # failure must not kill the drain loop; the dispatch-path
            # tick keeps the fault-propagation discipline
            # hot-path-ok: failure path of the IDLE tick — the queue
            # is empty by definition when this fires
            logging.getLogger(__name__).warning(
                "idle event-plane drain tick failed", exc_info=True)

    def _serving_span_sink(self, bid: int, spans: tuple) -> bool:
        # thread-affinity: drain, api
        """The runtime hands a dispatched batch's sampled spans to
        the event plane (drain-thread context).  Returns False — the
        runtime falls back to completion-boundary stamping — when the
        worker is terminal, so tracing degrades instead of leaking
        spans into a queue nobody drains."""
        s = self._serving
        if s is None:
            return False
        worker = s.get("eventplane")
        if worker is None or worker.error is not None:
            return False
        cur = s["spans"].get(bid)
        s["spans"][bid] = (cur + spans) if cur else spans
        return True

    def _event_join(self, dw) -> None:
        # thread-affinity: event-worker
        """The worker's join leg (eventplane thread, NEVER the drain
        thread): finish the d2h transfer + decode, join packed rows
        back to wide columns, emit to monitor/hubble consumers, and
        stamp sampled spans at TRUE window-join time — device work is
        provably complete once the window's fetch lands."""
        self._event_check_horizon(dw, self._serving)
        rows, shards, _appended, _lost = dw.ring.fetch()
        t_dev = time.monotonic()
        try:
            # the fetch itself can stall (a slow d2h): the producer
            # may have dispatched past the recycling horizon while it
            # waited, so a window admitted inside the horizon can
            # still reference recycled slots by the time the rows
            # land — re-check before publishing anything
            self._event_check_horizon(dw, self._serving)
            self._emit_ring_rows(rows, shards, dw.records, dw.n_shards)
        except Exception:
            # fetch() already credited the drainer's delivered
            # counters; a refuse/emit failure means the monitor plane
            # got NOTHING, and the worker will count the whole window
            # dropped — roll the credit back so the ring ledger and
            # the event-plane ledger never double-count the same
            # events (single-writer: this thread owns the window)
            d = dw.ring.drainer
            if d is not None:
                d.windows -= 1
                d.events -= dw.appended - dw.lost
                d.lost -= dw.lost
            raise
        if dw.spans and dw.tracer is not None:
            from ..obs.trace import STAGE_DEVICE, STAGE_JOIN

            t_join = time.monotonic()
            flat = [sp for spans in dw.spans.values() for sp in spans]
            for i, sp in enumerate(flat):
                sp.ts[STAGE_DEVICE] = t_dev
                sp.ts[STAGE_JOIN] = t_join
                try:
                    dw.tracer.commit(sp)
                except Exception:  # noqa: BLE001 — the events WERE
                    # delivered above, so a tracer failure must not
                    # recount the window as a drop (the drainer credit
                    # stands); evict the uncommitted remainder so the
                    # span ledger stays exact and join normally
                    dw.tracer.evict(flat[i:])
                    logging.getLogger(__name__).warning(
                        "span commit failed at window join",
                        exc_info=True)
                    break
        # the flow analytics plane drains HERE — on the event-join
        # worker, never the drain thread.  Contained: the window's
        # events were already delivered above, so an analytics fault
        # must not recount the window as a drop
        try:
            self.analytics.drain()
        except Exception:  # noqa: BLE001
            logging.getLogger(__name__).warning(
                "flow-analytics drain failed at window join",
                exc_info=True)

    @staticmethod
    def _event_check_horizon(dw, s) -> None:
        # thread-affinity: event-worker
        """Refuse a window the producer has dispatched past the arena
        recycling horizon (stalled plane): its record references may
        point at RECYCLED slots, so a join would publish corrupted
        events.  Raising makes it a contained, COUNTED drop — never
        silent corruption.  (After stop_serving, ``s`` is None and no
        check is needed: the runtime stops dispatching before the
        worker drains.)"""
        if (s is not None and dw.seq is not None
                and s["seq"] - dw.seq > s.get("join_horizon", 1 << 30)):
            raise RuntimeError(
                f"arena horizon exceeded: window is "
                f"{s['seq'] - dw.seq} batches stale "
                f"(horizon {s['join_horizon']})")

    def _event_drop(self, dw) -> None:
        # thread-affinity: any
        """A window the event plane LOST (queue overflow, contained
        join failure, worker death, stop sweep): its spans are
        counted tracer drops — never left incomplete."""
        if dw.tracer is not None:
            for spans in dw.spans.values():
                dw.tracer.evict(spans)

    def stop_serving(self) -> dict:
        # thread-affinity: api -- `api` covers every control-plane
        # caller (API handlers, CLI shutdown, tests' main thread);
        # what matters is that it is never the drain/worker threads
        """Drain everything in flight and emit it; returns serving
        stats (windows/events/lost per the drainer's accounting, plus
        the front-end snapshot when ingress mode was on).  Idempotent:
        stopping an idle daemon is a no-op returning zero counters."""
        s = self._serving
        if s is None:
            return {"windows": 0, "events": 0, "lost": 0}
        runtime = s.get("runtime")
        front = None
        if runtime is not None:
            # stop the front end FIRST: its drain flushes every queued
            # row through serve_batch before the ring drains below
            front = runtime.stop(drain=True)
        d = s["drainer"]
        # the final window (everything appended since the last tick)
        # rides the event plane like any other, then the worker is
        # drained BEFORE the sweep: every queued window joins, and
        # anything a dead/terminal worker left behind is swept as a
        # COUNTED drop — submitted == joined + dropped holds exactly
        self._serving_drain_tick(s)
        ev = s["eventplane"].stop(drain=True)
        # the worker is drained: aggregate whatever it published
        # (caller-thread context — the drain loop has stopped)
        self.analytics.drain()
        # the L7 plane stops AFTER the event plane: the join worker
        # above was still fanning redirect rows into the pool until
        # its drain completed.  Drain the pool, keep the final stats
        # for post-stop reads (proxy stats / metrics), then detach
        l7 = None
        if self._l7plane is not None:
            l7 = self._l7plane.stop(drain=True)
            self._l7_last = l7
            self._l7plane = None
        if s["mesh"] is not None:
            # leave the loader in the default single-device placement
            # (subsequent step()/process_batch callers expect it)
            self.loader.serving_unshard()
        self._serving = None
        out = {"windows": d.windows, "events": d.events,
               "lost": d.lost, "event-plane": ev}
        if s["n_shards"]:
            out["shards"] = s["n_shards"]
            out["route-overflow"] = s["route_overflow"]
        lad = s.get("ladder")
        if lad is not None and (lad.demotions or lad.promotions):
            out["ladder"] = lad.to_dict()
        if front is not None:
            out["front-end"] = front
        if l7 is not None:
            out["l7"] = l7
        return out

    def _emit_ring_rows(self, rows: np.ndarray,
                        shards: Optional[np.ndarray],
                        records: dict, n_shards: int) -> None:
        # thread-affinity: event-worker
        """Join decoded ring rows back to their retained batch
        records and publish (event-join WORKER context: ``records``
        is the window's swap-time snapshot, so this never touches
        ``self._serving`` — which the drain thread may be mutating,
        or stop_serving may already have cleared)."""
        from ..core.packets import unpack_rows_np
        from ..monitor.api import decode_ring_rows
        from ..monitor.ring import COL_BATCH, COL_PKT_IDX

        if rows is None or not len(rows):
            return
        for b in np.unique(rows[:, COL_BATCH]):
            rec = records.get(int(b))
            if rec is None:
                continue  # header window expired (overrun drain lag)
            kind, hdr, meta, numerics, ts = rec
            m = rows[:, COL_BATCH] == b
            rows_b = rows[m]
            pkt = rows_b[:, COL_PKT_IDX].astype(np.int64)
            if shards is not None:
                # per-chip rings carry shard-LOCAL packet indices;
                # the retained window is the ROUTED tensor, shard s
                # owning rows [s*block, (s+1)*block)
                pkt = shards[m] * (len(hdr) // n_shards) + pkt
            sel = hdr[pkt]
            if kind == "packed":
                # wide columns reconstructed host-side ONLY for the
                # rows the ring compaction kept — the whole point of
                # retaining the 4x smaller packed window
                sel = unpack_rows_np(sel, *meta)
            batch = decode_ring_rows(rows_b, sel, numerics, ts,
                                     aligned=True)
            # redirect fan-out: the L7 plane's bounded submit (never
            # blocks, shed is counted).  Attribute read, not a
            # _serving key — see the contract in the docstring; a
            # racing stop_serving already drained what we submitted
            # or sheds it counted, either way the ledger closes
            l7 = self._l7plane
            if l7 is not None:
                l7.ingest(batch)
            if self.auth_manager is not None:
                # the drained window's logical now is gone; the
                # serving loop stamps batches with _now(), so grants
                # land on the same clock
                self.auth_manager.observe(batch, self._now())
            self.monitor.publish(self._filter_events(batch))

    # -- DNS proxy (pkg/fqdn/dnsproxy) --------------------------------
    def start_dns_proxy(self, resolver, host: str = "127.0.0.1"
                        ) -> Dict[int, tuple]:
        """Spawn a wire-level UDP DNS proxy per DNS redirect port
        (reference: the transparent dnsproxy pods resolve through).
        Allowed answers feed the fqdn cache, so toFQDNs identities
        mint from LIVE traffic.  Returns {proxy_port: (host, port)}
        bind addresses."""
        from ..proxy.dnslistener import DNSProxyListener

        out: Dict[int, tuple] = {}
        for l in self.proxy.listeners():
            port = l["proxy-port"]
            if l.get("dns-rules") and port not in self._dns_listeners:
                self._dns_listeners[port] = DNSProxyListener(
                    self.proxy, port, resolver,
                    observe=self.fqdn.observe, host=host)
            if port in self._dns_listeners:
                out[port] = self._dns_listeners[port].address
        return out

    def stop_dns_proxy(self) -> dict:
        stats = {p: {"queries": l.queries, "refused": l.refused,
                     "errors": l.errors}
                 for p, l in self._dns_listeners.items()}
        for l in self._dns_listeners.values():
            l.close()
        self._dns_listeners.clear()
        return stats

    # -- transparent encryption (pkg/wireguard analogue) --------------
    def seal_batch(self, peer: str, frames: bytes) -> bytes:
        """Seal a packed wire-frame buffer for ``peer`` — the egress
        half of node-to-node transparent encryption (the cilium_wg0
        transmit leg; one AEAD per batch)."""
        if self.encryption is None:
            raise RuntimeError("encryption disabled "
                               "(DaemonConfig.enable_encryption)")
        return self.encryption.channel(peer).seal(frames)

    def ingest_encrypted(self, peer: str, frame: bytes, ep: int = 0,
                         direction: int = 0,
                         now: Optional[int] = None) -> EventBatch:
        """The ingress half: open a sealed batch from ``peer``, parse
        the wire frames through the native packed path, and verdict
        them — decrypt-then-datapath, exactly the wg-device receive
        leg.  Raises encryption.DecryptError on tamper/replay."""
        if self.encryption is None:
            raise RuntimeError("encryption disabled "
                               "(DaemonConfig.enable_encryption)")
        wire = self.encryption.channel(peer).open(frame)
        from .. import native

        got = native.parse_frames_packed(wire)
        if got is None:
            got = native.parse_frames_packed_py(wire)
        rows, n, _skipped = got
        import jax.numpy as jnp

        from ..core.packets import unpack_hdr

        hdr = np.asarray(unpack_hdr(jnp.asarray(rows[:n]),
                                    jnp.uint32(ep),
                                    jnp.uint32(direction)))
        return self.process_batch(hdr, now=now)

    def socklb_entries(self, limit: int = 1000) -> list:
        """Decode the socket-LB flow cache for GET /map/lb
        (`cilium-tpu bpf lb list`).  ``socklb_stage_jit`` DONATES the
        table every batch, so a snapshot raced by process_batch can
        find its buffer deleted — retry on the replacement table
        rather than serializing the API against the hot path."""
        from ..service.socklb import socklb_entries_from_snapshot

        for _ in range(4):
            tbl = self._socklb
            if tbl is None:
                return []
            try:
                snap = np.asarray(tbl.table)
            except RuntimeError:  # donated mid-read
                continue
            return socklb_entries_from_snapshot(snap, self._now(),
                                                limit)
        return []

    # -- ipcache API (the k8s-watcher/clustermesh-facing entry) --------
    def upsert_ipcache(self, cidr: str, numeric_id: int,
                       source: str = "k8s") -> None:
        """Map a prefix to an identity; patches the device LPM in
        place when possible, else falls back to regeneration."""
        self.ipcache.upsert(cidr, numeric_id, source=source)
        if self.endpoints.patch_ipcache(cidr, numeric_id):
            return
        self.endpoints.regenerate()

    def delete_ipcache(self, cidr: str) -> None:
        self.ipcache.delete(cidr)
        if self.loader.delete_ipcache(cidr):
            return
        self.endpoints.regenerate()

    # -- runtime config mutation (PATCH /config) -----------------------
    # the mutable subset of DaemonConfig; everything else (backend,
    # capacities) is construction-time (reference: option.DaemonConfig
    # runtime-mutable options like MonitorAggregation/PolicyEnforcement)
    @staticmethod
    def _cast_aggregation(raw) -> str:
        v = str(raw)
        if v not in ("none", "medium"):
            raise ValueError(f"monitor-aggregation must be none|medium,"
                             f" got {v!r}")
        return v

    _MUTABLE_CONFIG = {
        "ct-gc-interval": ("ct_gc_interval", float),
        "fqdn-gc-interval": ("fqdn_gc_interval", float),
        "health-probe-interval": ("health_probe_interval", float),
        "anomaly-threshold": ("anomaly_threshold", float),
        "monitor-aggregation": ("monitor_aggregation",
                                _cast_aggregation.__func__),
    }

    def patch_config(self, body: Dict[str, object]) -> Dict[str, object]:
        """Apply runtime-mutable option changes; returns what changed.
        Unknown or immutable keys raise (reference: PATCH /config
        rejects non-mutable options)."""
        # validate + cast EVERYTHING first: a bad key must not leave
        # earlier keys half-applied behind a 400
        staged: Dict[str, tuple] = {}
        for key, raw in body.items():
            spec = self._MUTABLE_CONFIG.get(key)
            if spec is None:
                raise ValueError(f"option {key!r} is not runtime-"
                                 "mutable (or unknown)")
            attr, cast = spec
            staged[key] = (attr, cast(raw))
        changed: Dict[str, object] = {}
        for key, (attr, value) in staged.items():
            setattr(self.config, attr, value)
            changed[key] = value
        if not changed:
            return changed
        # re-arm controllers whose cadence changed
        if self._started:
            if "ct-gc-interval" in changed:
                # serialized against the monitor's state transitions
                # (monitor lock): a LIVE pressure episode keeps the
                # accelerated cadence — the monitor only accelerates
                # on the OK->PRESSURE edge, so an unsynchronized
                # reset here would silently cancel the response for
                # the rest of the episode; the new normal cadence
                # applies once the episode exits
                self.pressure.resync(self.config.ct_gc_interval,
                                     self._ct_gc_schedule)
            if "fqdn-gc-interval" in changed:
                self.controllers.update(
                    "fqdn-gc", self.fqdn.gc,
                    self.config.fqdn_gc_interval)
            if ("health-probe-interval" in changed
                    and self.health is not None):
                def _health_sweep():
                    self.node_registry.heartbeat(self.config.node_name)
                    self.health.probe_all()

                self.controllers.update(
                    "health-probe", _health_sweep,
                    self.config.health_probe_interval)
        if "anomaly-threshold" in changed and self.anomaly is not None:
            self.anomaly.threshold = self.config.anomaly_threshold
        return changed

    # -- status --------------------------------------------------------
    def status(self) -> dict:
        import jax

        m = self.loader.metrics()
        mesh = self.clustermesh.status()
        devices = jax.devices()
        return {
            "version": VERSION,
            "node": self.config.node_name,
            "backend": self.config.backend,
            # the device the tpu backend's loader actually runs on: a
            # host without a chip runs it on XLA:CPU
            "platform": devices[0].platform,
            "device-kind": devices[0].device_kind,
            "device-count": len(devices),
            "uptime-seconds": round(time.time() - self._boot_time, 1),
            "policy-revision": self.repo.revision,
            "endpoints": {
                "total": len(self.endpoints.list()),
                "by-state": self._eps_by_state(),
            },
            "identities": len(self.allocator.all_identities()),
            "ipcache-entries": len(self.ipcache.entries()),
            "fqdn-entries": len(self.fqdn.entries()),
            "l7-requests": self.proxy.requests_total,
            "regenerations": self.endpoints.regenerations,
            "forwarded": int(m[0].sum()),
            "dropped": int(m[1:].sum()),
            "monitor-events": self.monitor.published,
            "flows-seen": self.observer.seq,
            # via stats(): the sum happens under the recorder's lock
            # (an unlocked dict iteration races first-of-a-kind
            # incident insertion on worker/watchdog threads)
            "incidents": self.flightrec.stats()["incidents"],
            "flow-aggregation": self.analytics.stats(),
            "map-pressure": self.pressure.stats(),
            "controllers": {
                n: {"success": s.success_count, "failure": s.failure_count,
                    "last-error": s.last_error.splitlines()[-1]
                    if s.last_error else ""}
                for n, s in self.controllers.statuses().items()},
            **({"cluster-health": self.health.to_dict()}
               if self.health is not None else {}),
            **({"serving": {
                k: v for k, v in self._node_fault_info().items()}}
               if (self._serving is not None
                   or self._ct_snap is not None) else {}),
            **({"clustermesh": mesh} if mesh else {}),
            **({"nat": nat_st} if (nat_st := (
                self.loader.nat_status(self._now())
                if self.nat is not None
                and hasattr(self.loader, "nat_status") else None))
               else {}),
            **({"auth": self.auth_manager.status()}
               if self.auth_manager is not None else {}),
            **({"encryption": self.encryption.status()}
               if self.encryption is not None else {}),
        }

    def _eps_by_state(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for ep in self.endpoints.list():
            out[ep.state.value] = out.get(ep.state.value, 0) + 1
        return out

    # -- checkpoint / restore -----------------------------------------
    def checkpoint(self, state_dir: str) -> None:
        """Persist control-plane state + CT snapshot (reference:
        /var/run/cilium/state + pinned maps, SURVEY.md §5)."""
        os.makedirs(state_dir, exist_ok=True)
        ids = [{"id": i.numeric_id,
                "labels": [str(l) for l in i.labels]}
               for i in self.allocator.all_identities()]
        eps = [ep.to_dict() for ep in self.endpoints.list()]
        meta = {
            "version": VERSION,
            "node": self.config.node_name,
            "revision": self.repo.revision,
            "identities": ids,
            "endpoints": eps,
            "ipcache": [
                {"cidr": e.cidr, "identity": e.identity,
                 "source": e.source}
                for e in self.ipcache.entries()
                if e.source not in ("endpoint", "generated")],
            "rules": [rule_to_dict(r) for r in self.repo.rules()],
            # bandwidth limits survive restart (upstream re-derives
            # them from pod annotations; restore-without-k8s must not
            # silently unthrottle endpoints)
            "bandwidth": {str(k): v for k, v in self._bw_limits.items()},
            # egress policies likewise: the restored NAT snapshot's
            # mappings carry their egress IPs, and NEW flows must not
            # silently fall back to node_ip masquerade
            "egress-gateways": {
                name: {"selectors": list(p["selectors"]),
                       "dest_cidrs": list(p["dest_cidrs"]),
                       "egress_ip": p["egress_ip"]}
                for name, p in self._egress_policies.items()},
        }
        # ct.npz first, state.json LAST: state.json is the commit point
        # of the checkpoint pair, so a crash between the two renames
        # can never pair new control-plane state with a stale CT
        # snapshot (stale CT would resurrect established flows admitted
        # under since-revoked policy).  The CT snapshot additionally
        # carries the policy revision it was taken under: the INVERSE
        # crash ordering (new ct.npz + old state.json) is caught at
        # restore time by the revision mismatch and the snapshot is
        # skipped.
        ct = self.loader.ct_snapshot()
        self._store_ct_snapshot(ct, trigger="checkpoint")
        ct_tmp = os.path.join(state_dir, "ct.npz.tmp")
        extra = {}
        nat = getattr(self.loader, "nat_snapshot", lambda: None)()
        if nat is not None:
            # NAT entries pair with the CT snapshot (both carry the
            # post-NAT tuples); riding the same file keeps them atomic
            extra["nat"] = nat
        with open(ct_tmp, "wb") as f:
            np.savez_compressed(
                f, table=ct,
                revision=np.int64(self.repo.revision), **extra)
        os.replace(ct_tmp, os.path.join(state_dir, "ct.npz"))
        tmp = os.path.join(state_dir, "state.json.tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f, indent=1)
        os.replace(tmp, os.path.join(state_dir, "state.json"))

    def restore(self, state_dir: str) -> bool:
        """Reload a checkpoint (the agent-restart path: datapath state
        survives; endpoints re-register and regenerate)."""
        path = os.path.join(state_dir, "state.json")
        if not os.path.exists(path):
            return False
        with open(path) as f:
            meta = json.load(f)
        for rec in meta["identities"]:
            self.allocator.restore_identity(
                rec["id"], LabelSet.parse(*rec["labels"]))
        for rec in meta["ipcache"]:
            self.ipcache.upsert(rec["cidr"], rec["identity"],
                                rec["source"])
        if meta["rules"]:
            self.repo.add_obj(meta["rules"])
        for rec in meta["endpoints"]:
            # RESTORING until the batched regeneration below realizes
            # their policy (reference: the endpoint restore state).
            # Enforcement mode + options round-trip — silently
            # resetting a "never"/"always" endpoint to "default" on
            # restart would change verdicts.
            self.endpoints.add(rec["name"], tuple(rec["ips"]),
                               LabelSet.parse(*rec["labels"]),
                               ep_id=rec["id"],
                               named_ports=rec.get("named-ports"),
                               restoring=True, defer_regen=True,
                               enforcement=rec.get("policy-enforcement",
                                                   "default"),
                               options=rec.get("options"))
        self.endpoints.regenerate()
        for ep_id, bps in (meta.get("bandwidth") or {}).items():
            self.set_bandwidth(int(ep_id), int(bps))
        for name, p in (meta.get("egress-gateways") or {}).items():
            self.add_egress_gateway(name, p["selectors"],
                                    p["dest_cidrs"], p["egress_ip"])
        ct_path = os.path.join(state_dir, "ct.npz")
        if os.path.exists(ct_path):
            try:
                snap = np.load(ct_path)
                # revision stamp: a CT snapshot taken under a different
                # policy revision than state.json records is the torn-
                # checkpoint case (crash between the two renames) —
                # skip it rather than resurrect flows admitted under
                # policy that is absent from the restored ruleset.
                # Pre-stamp snapshots (no "revision" key) restore as
                # before.
                snap_rev = (int(snap["revision"])
                            if "revision" in snap.files else None)
                if snap_rev is not None and snap_rev != meta["revision"]:
                    import logging

                    logging.getLogger(__name__).warning(
                        "CT snapshot revision %s != checkpoint revision "
                        "%s (torn checkpoint); skipping connection "
                        "state", snap_rev, meta["revision"])
                else:
                    self.loader.ct_restore(snap["table"])
                    if "nat" in snap.files and hasattr(
                            self.loader, "nat_restore"):
                        # replies to allocated node ports must keep
                        # reverse-translating across restarts
                        self.loader.nat_restore(snap["nat"])
            except Exception as e:  # corrupt snapshot: identities/
                # rules/endpoints above are already restored; losing
                # live connections is the lesser failure
                import logging

                logging.getLogger(__name__).warning(
                    "CT snapshot restore failed (%s); continuing "
                    "without connection state", e)
        return True
