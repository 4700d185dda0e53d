"""The Loader seam: agent-facing datapath interface + backends.

Reference: upstream cilium ``pkg/datapath`` — the ``Loader`` /
``Datapath`` interfaces that ``daemon`` drives ("compile + attach"
eBPF), with ``pkg/datapath/fake`` proving the seam supports non-eBPF
backends.  BASELINE.md's north-star gates the TPU path behind exactly
this seam: "compile+attach" becomes "compile policy/ipcache tensors +
bind device buffers".

Backends:
- :class:`TPULoader` — device tensors + the fused jit pipeline.
- :class:`InterpreterLoader` — the sequential oracle; runs the whole
  agent without any accelerator (the fake-datapath analogue; also the
  divergence-checking reference).

Policy/ipcache updates swap tensors while KEEPING the live conntrack
table and metric counters — the analogue of cilium replacing pinned
BPF programs while maps persist in bpffs (SURVEY.md §5).
"""

from __future__ import annotations

import abc
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.trace import profile_span
from ..policy.compiler import IdentityRowMap, compile_policy
from ..policy.resolve import EndpointPolicy
from .lpm import compile_lpm
from .verdict import MAX_ENDPOINTS, DatapathState, DevicePolicy

# Jitted DONATING dynamic_update_slice — the table patch paths'
# workhorse.  Two design points, both load-bearing at scale:
#
# - ``.at[idx].set`` lowers to XLA SCATTER (measured ~20x slower than
#   a slice update on CPU); patches are row/block-contiguous by
#   construction, so DUS is always expressible.
# - ``donate_argnums=0``: the update aliases the live buffer IN
#   PLACE, so a patch costs O(row), not a full-tensor copy — the r05
#   audit's verdict tensor is GBs at production scale, and copying
#   it per identity churn op would make "incremental" a lie.
#   Donation is safe under the SAME discipline the serve step's own
#   ``donate_argnums=0`` relies on: device-stream ordering sequences
#   the in-place write after every already-enqueued dispatch's
#   reads, and the caller (``_publish_tables``) swaps the state
#   reference in the same locked region with nothing fallible in
#   between, so no dispatch can ever be handed the consumed handle.
#
# Lazy: CPU-only tools import this module without jax.
_dus_jit = None


def _dus(arr, upd, starts):
    global _dus_jit
    if _dus_jit is None:
        import jax

        _dus_jit = jax.jit(
            lambda a, u, s: jax.lax.dynamic_update_slice(a, u, s),
            donate_argnums=0)
    return _dus_jit(arr, upd, tuple(starts))


# Jitted CT-occupancy reduction (the map-pressure sample's one piece
# of device math).  MODULE-level like _dus: one executable per
# (capacity, placement) per process, shared across loaders, so the
# periodic pressure sample never pays — or worse, races a serving
# dispatch's compile-log window with — a fresh XLA compile after the
# first warm call (Daemon.start() / serving_shard warm it).
_occ_jit = None


def _ct_occupied(fp):
    """Occupied CT slots (live + expired-but-unswept): fp != 0 —
    the per-slot key fingerprint's free marker doubles as the
    occupancy bitmap, so the sample reduces 4 B/slot instead of
    loading the 68 B rows."""
    global _occ_jit
    if _occ_jit is None:
        import jax
        import jax.numpy as jnp

        _occ_jit = jax.jit(
            lambda f: jnp.sum(f != 0, dtype=jnp.uint32))
    return _occ_jit(fp)


class Loader(abc.ABC):
    """What the agent needs from a datapath (pkg/datapath.Loader)."""

    @abc.abstractmethod
    def attach(self, policies: Sequence[EndpointPolicy],
               ipcache: Dict[str, int], ep_policy: Dict[int, int],
               row_map: IdentityRowMap) -> None:
        """Full (re)compile + swap — endpoint regeneration's final step.

        ``ipcache`` maps cidr -> NUMERIC identity; ``ep_policy`` maps
        endpoint id -> row index into ``policies``."""

    @abc.abstractmethod
    def step(self, hdr: np.ndarray, now: int, pre_drop=None,
             pre_drop_reason=None, lb_drop=None, audit=False):
        """Verdict one batch.

        Returns ``(out, row_map)``: the out tensor [N, N_OUT] plus the
        IdentityRowMap snapshot that produced it.  The snapshot is
        taken under the same lock as the device step so a concurrent
        ``attach`` can never make the caller decode OUT_ID_ROW values
        through the wrong row table.  ``pre_drop`` ([N] bool) is the
        SNAT stage's exhaustion mask from :meth:`masquerade`."""

    @abc.abstractmethod
    def gc(self, now: int) -> int:
        """Expire CT entries; returns eviction count."""

    # -- mutual authentication (pkg/auth authmap analogue) ------------
    @abc.abstractmethod
    def auth_upsert(self, ep_id: int, remote_id: int,
                    expires: int) -> bool:
        """Grant (subject endpoint's identity, remote identity) until
        ``expires``.  Entries are identity-granular: endpoints sharing
        a policy row (same labels) share the grant, exactly upstream's
        {local identity, remote identity} authmap key."""

    @abc.abstractmethod
    def auth_entries(self) -> list:
        """Live grants for `cilium-tpu bpf auth list`."""

    @abc.abstractmethod
    def auth_gc(self, now: int) -> int:
        """Drop expired grants; returns eviction count."""

    @abc.abstractmethod
    def metrics(self) -> np.ndarray:
        """[N_REASONS, 2] per-reason/direction packet counters."""

    @abc.abstractmethod
    def ct_snapshot(self) -> np.ndarray:
        """CT table contents for checkpoint / `bpf ct list`."""

    @abc.abstractmethod
    def ct_restore(self, table: np.ndarray) -> None:
        """Reload a CT snapshot (agent restart keeps connections)."""

    # -- incremental updates (SURVEY.md §7 hard part #3) --------------
    # Identity churn must NOT cost a full compile_policy + upload; the
    # default False sends callers down the full-attach path, backends
    # that can patch in place override.

    def patch_identity(self, kind: str, numeric_id: int,
                       policies) -> bool:
        """Patch one identity's verdict row in place (peer sets in
        ``policies`` must already reflect the change — see
        policy.incremental.update_contributions).  Returns False when
        a full attach is required instead."""
        return False

    def patch_ipcache(self, cidr: str, numeric_id: int) -> bool:
        """Patch one ipcache prefix -> identity mapping in place."""
        return False

    def delete_ipcache(self, cidr: str) -> bool:
        """Remove one ipcache prefix in place (fqdn TTL expiry)."""
        return False

    # -- map pressure (ISSUE 12: pkg/maps ctmap pressure analogue;
    # ISSUE 19 widened the sample beyond CT: LPM/ipcache prefix
    # occupancy and policy-table row occupancy ride the same
    # snapshot, feeding cilium_lpm_occupancy /
    # cilium_policy_map_occupancy and the map-headroom SLO) ----------
    def map_pressure(self, now: int) -> dict:
        """Point-in-time map-pressure snapshot: CT occupancy +
        cumulative insert drops, NAT pool failures, LPM/ipcache and
        policy-table occupancy.  Backends override; the default
        reports an unmeasurable world (the monitor then keys on the
        counters alone)."""
        return {"ct": {"capacity": 0, "occupied": 0,
                       "occupancy": None, "insert-drops": 0},
                "nat": {"capacity": None, "failures": 0},
                "lpm": {"capacity": 0, "entries": 0,
                        "occupancy": None},
                "policy": {"capacity": 0, "rows": 0,
                           "occupancy": None}}


class TPULoader(Loader):
    """The real datapath: device tensors + fused jit pipeline.

    TABLE GENERATION DISCIPLINE (ISSUE 10; datapath/tables.py): the
    published policy/ipcache tables are versioned behind a
    double-buffered slot pair with a monotonic generation tag.  Every
    mutation — full/delta ``attach``, ``patch_identity``,
    ``patch_ipcache``, ``delete_ipcache``, ``auth_upsert`` — is a
    BUILDER: it assembles the successor tables holding only the
    builder lock (host compile + ``.at[].set`` device work happen off
    the dispatch path) and publishes through ``_publish_tables``,
    which takes the dispatch lock ONLY for the generation flip.  The
    attrs below are the published tables + their host mirrors; the
    static CTA009 checker (analysis/generation.py) flags any write to
    them outside a ``# table-swap-ok`` method, so a shortcut that
    mutates a live table in place cannot land silently.
    """
    # active-tables: state, tensors, _lpm_tensors, _lpm_entries,
    # active-tables: _epp, _policies

    def __init__(self, ct_capacity: int = 1 << 20,
                 delta_compile: bool = True,
                 swap_warn_ms: float = 0.0,
                 nat_capacity: Optional[int] = None):
        import jax.numpy as jnp  # deferred so CPU-only tools can import

        from ..infra.lockdebug import make_lock
        from .tables import TableVersioner

        self._jnp = jnp
        self.ct_capacity = ct_capacity
        # SNAT port-pool size (service/nat.py NATTable); None = the
        # NAT_DEFAULT_CAPACITY.  Small pools are the nat_exhaustion
        # scenario's pressure shape
        self.nat_capacity = nat_capacity
        self.state: Optional[DatapathState] = None
        self.nat_state = None  # NATTable, created on first masquerade
        self.row_map: Optional[IdentityRowMap] = None
        self.attach_count = 0
        # mutual-auth grants, host-authoritative: (ep_id, remote
        # numeric identity) -> expires.  The device [n_pol, n_rows]
        # tensor is a projection rebuilt on every attach (rows and
        # policy indices shift; the dict keys are stable)
        self._auth: Dict[Tuple[int, int], int] = {}
        self._epp = None  # ep -> policy row, mirrors the device table
        # attach() runs on API/regeneration threads while the serve
        # loop is in step(); every state swap must be atomic or a
        # concurrent step would resurrect the pre-attach tensors.
        # make_lock: plain Lock normally, order-checked DebugLock
        # under CILIUM_TPU_LOCKDEBUG=1 (SURVEY §5 race detection)
        #
        # Lock discipline for the hot path: ALL host-side staging
        # (np.ascontiguousarray + the h2d jnp.asarray/device_put)
        # happens BEFORE the lock is taken; the lock covers only the
        # async dispatch + state swap, so attach/auth/API calls never
        # stall behind a host->device copy, and host assembly of
        # batch N+1 overlaps device execution of batch N.
        self._lock = make_lock("datapath-loader")
        # guarded-by: datapath-loader: state
        # (the runtime lockdebug name resolves to _lock in the static
        # checker's alias map too — one identity, both worlds)
        #
        # Table versioning (datapath/tables.py): the slot pair +
        # generation tag + the BUILDER lock serializing every table
        # mutation.  Lock order: table-builder BEFORE datapath-loader
        # (builders publish under the dispatch lock while holding the
        # build lock; nothing acquires them the other way around).
        self.tables = TableVersioner(warn_ms=swap_warn_ms)
        # delta attach (policy.incremental.delta_compile): repaint
        # only fingerprint-changed policies.  _policy_fps is the
        # previous attach's fingerprints (None until the first one)
        self.delta_compile = bool(delta_compile)
        self._policy_fps: Optional[list] = None
        # DUS executable warm set (see _warm_dus) and the
        # incomplete-swap flag the disaster-recovery path keys on
        # (see _building / _heal_incomplete_swap)
        self._dus_warm: set = set()
        self._swap_incomplete = False
        # host-drop counts awaiting a free dispatch lock (see
        # add_host_drops: the watchdog must never block on _lock)
        self._host_drops: Dict[int, int] = {}
        self._host_drops_lock = make_lock("loader-host-drops")
        # guarded-by: loader-host-drops: _host_drops
        # multi-chip serving (parallel/mesh.py): serving_shard()
        # installs the mesh and re-places state (CT sharded per chip,
        # tables replicated); sharded serve steps are cached per
        # (packed, trace_sample, audit) so one serving session
        # compiles exactly one executable per ladder rung and mode
        self._serving_mesh = None
        self._sharded_steps: Dict[tuple, object] = {}
        # compile introspection (obs/compile_log.py): every XLA
        # retrace on the serving path is recorded with shape/mode and
        # the one-executable-per-(rung, mode) invariant asserted at
        # runtime — the jit-cache sizes are sampled around each
        # dispatch (two dict-len reads; noise against the dispatch)
        from ..obs.compile_log import CompileLog

        self.compile_log = CompileLog()

    def _serving_cache_size(self, mode: str) -> int:
        """Executable count backing one serving mode RIGHT NOW."""
        from ..monitor.ring import (serve_step_jit,
                                    serve_step_packed_jit,
                                    serve_superbatch_jit,
                                    serve_superbatch_packed_jit)

        if mode == "wide":
            fn = serve_step_jit
        elif mode == "packed":
            fn = serve_step_packed_jit
        elif mode == "super-wide":
            fn = serve_superbatch_jit
        elif mode == "super-packed":
            fn = serve_superbatch_packed_jit
        else:  # sharded steps are per-(packed, sample, audit) jits
            return sum(
                getattr(f, "_cache_size", lambda: 1)()
                for f in self._sharded_steps.values())
        size = getattr(fn, "_cache_size", None)
        return size() if size is not None else 0

    def _record_compile(self, mode: str, shape, ring_cap: int,
                        statics: tuple, before: int, after: int,
                        elapsed_s: float) -> None:
        """Key the invariant on everything that LEGITIMATELY selects
        a distinct executable — shape, ring capacity, static args,
        and the attach generation (a policy-world change retraces by
        design) — so a growth on an already-seen key is a genuine
        retrace (e.g. the P(axis) vs P(axis, None) sharding-spelling
        trap), not a config change."""
        self.compile_log.record_dispatch(
            mode, tuple(shape), before, after, elapsed_s,
            key_extra=(int(ring_cap),) + tuple(statics)
            + (self.attach_count,))

    def _stage(self, batch_id: int, rows, valid, scalars=(),
               put=None) -> tuple:
        # thread-affinity: drain, api
        """h2d staging of one serving batch, before the lock (lock
        discipline in __init__), in the profiler span ``h2d``: host
        ``rows`` and ``valid`` onto the device by ``put`` (default
        ``jnp.asarray``) and each of ``scalars`` as a u32.  Returns
        ``(rows, valid, *scalars)``."""
        jnp = self._jnp
        put = put or jnp.asarray
        with profile_span("h2d", batch_id):
            if isinstance(rows, np.ndarray):
                rows = put(np.ascontiguousarray(rows))
            if isinstance(valid, np.ndarray):
                valid = put(valid)
            return (rows, valid) + tuple(jnp.uint32(x) for x in scalars)

    def _dispatch(self, mode: str, batch_id: int, step, args: tuple,
                  kw: dict, statics: tuple):
        # thread-affinity: drain, api
        # table-swap-ok: dispatch-result swap — CT/metrics advance,
        # policy+ipcache references carried unchanged
        """One serving dispatch: ``step(state, *args, **kw)`` ->
        ``(state', ring')`` under the lock, which the agent's
        controllers also take (profiler spans ``lock_wait`` for the
        acquisition, ``dispatch`` for the async jit call and the state
        swap).  A grown jit cache is recorded as a compile of
        ``args[1]``'s shape.  Returns ``(ring', row_map)``."""
        with profile_span("lock_wait", batch_id):
            self._lock.acquire()
        try:
            with profile_span("dispatch", batch_id):
                ring, row_map, before, after, t0 = self._dispatch_held(
                    mode, step, args, kw)
        finally:
            self._lock.release()
        if after > before:
            self._record_compile(mode, args[1].shape, ring.buf.shape[0],
                                 statics, before, after,
                                 time.monotonic() - t0)
        return ring, row_map

    def _dispatch_held(self, mode: str, step, args: tuple, kw: dict):
        # holds: datapath-loader
        # table-swap-ok: dispatch-result swap (see _dispatch)
        before = self._serving_cache_size(mode)
        t0 = time.monotonic()
        self.state, ring = step(self.state, *args, **kw)
        return (ring, self.row_map, before,
                self._serving_cache_size(mode), t0)

    def _rekeep_serving_placement(self) -> None:
        # holds: datapath-loader
        # table-swap-ok: placement-only re-put of the CURRENT state
        # (no table content changes; sharded serving must not see
        # fresh leaves land single-device)
        """Call (under the lock) after ANY state swap that introduces
        fresh arrays: during sharded serving the swap must not
        silently unshard the CT or leave new tensors single-device —
        the next sharded step would either recompile or, worse, run
        against an implicitly resharded CT.  No-op outside sharded
        serving; device_put is a no-op on already-placed leaves."""
        if self._serving_mesh is None:
            return
        from ..parallel.mesh import shard_state

        self.state = shard_state(self.state, self._serving_mesh)

    @contextmanager
    def _building(self):
        """tables.building() plus disaster recovery: a builder that
        dies INSIDE the locked publish window — after a donating
        device_patch consumed live buffers, or after the state swap
        but before the flip (placement failure) — re-uploads the
        published content from the host mirrors, which the builder's
        own rollback has just restored.  Serving dispatches therefore
        never see a consumed handle or an unflipped half-publish;
        the publish-or-nothing contract survives even failures inside
        the lock."""
        with self.tables.building() as b:
            try:
                yield b
            except BaseException:
                self._heal_incomplete_swap()
                raise

    def _warm_dus(self, arr, upd, starts) -> None:
        """Pre-compile the donating DUS executable for this (array,
        update) shape pair OFF the dispatch lock: the first call per
        shape pays an XLA trace+compile (tens of ms) that must never
        run inside the locked publish window.  ``arr`` may be a
        consumed handle — only its shape/dtype metadata is read; the
        warm call donates a throwaway zeros array.  One-time per
        shape pair (shapes change only on capacity growth)."""
        key = (tuple(arr.shape), str(arr.dtype),
               tuple(upd.shape), str(upd.dtype))
        if key in self._dus_warm:
            return
        _dus(self._jnp.zeros(arr.shape, arr.dtype), upd,
             tuple(0 for _ in starts))
        self._dus_warm.add(key)

    def _project_auth(self, epp, row_map, n_pol: int,
                      n_rows: int) -> np.ndarray:
        """The host-authoritative auth grants projected onto the
        device [n_pol, n_rows] table — ONE definition shared by the
        full attach and disaster recovery, so a republished-from-
        mirrors world can never carry different grant rules than a
        normal attach (patch_identity's single-COLUMN re-projection
        mirrors the same bounds/merge rules for one numeric).
        ``row_map`` is explicit: attach projects through its ARGUMENT
        map (self.row_map is still the previous one pre-publish)."""
        auth_np = np.zeros((n_pol, n_rows), dtype=np.uint32)
        with self._lock:  # _auth shares the dispatch lock
            auth_items = list(self._auth.items())
        for (ep, rem), exp in auth_items:
            pr = (epp[ep] if epp is not None
                  and 0 <= ep < MAX_ENDPOINTS else -1)
            r = row_map.row(rem) if row_map is not None else 0
            if pr >= 0 and 0 < r < auth_np.shape[1]:
                auth_np[pr, r] = max(auth_np[pr, r], exp)
        return auth_np

    def _heal_incomplete_swap(self) -> None:
        # table-swap-ok: disaster recovery — re-uploads the PUBLISHED
        # content from the host mirrors after a failure inside the
        # locked publish window; no generation bump (content is
        # exactly as published)
        """No-op unless a publish died mid-window (the
        ``_swap_incomplete`` flag).  Rebuilds the device tables from
        the host mirrors — pre-patch by the rollback contract — so
        the datapath serves exactly the published generation again,
        whatever a partial donating chain or placement failure left
        behind."""
        if not self._swap_incomplete:
            return
        from .lpm import DeviceLPM

        tensors = getattr(self, "tensors", None)
        if tensors is None or self._published_state() is None:
            self._swap_incomplete = False
            return
        epp = self._epp
        policy = DevicePolicy.from_tensors(
            tensors, epp,
            auth=self._project_auth(epp, self.row_map,
                                    tensors.verdict.shape[0],
                                    tensors.verdict.shape[2]))
        lpm = DeviceLPM.from_tensors(self._lpm_tensors)
        with self._lock:
            self.state = DatapathState(
                policy=policy, ipcache=lpm,
                ct=self.state.ct, metrics=self.state.metrics)
            self._rekeep_serving_placement()
            self._swap_incomplete = False

    def _published_state(self) -> Optional[DatapathState]:
        # thread-affinity: any
        """Locked point read of the published state.  Builders (under
        the build lock) use it to capture the ACTIVE policy/ipcache:
        those fields are stable until the builder itself publishes —
        every publisher serializes on the build lock — while ct/
        metrics keep advancing under dispatches (the publish flip
        re-reads them under the dispatch lock)."""
        with self._lock:
            return self.state

    def _publish_tables(self, build, policy=None, lpm=None,
                        device_patch=None, row_map=None,
                        mirrors=None, attach: bool = False) -> int:
        # table-swap-ok: THE swap helper — the only site that exposes
        # a new table generation to dispatches.  The dispatch lock is
        # held for the pointer swap + generation flip, plus — for
        # derived-array patches — the ``device_patch`` enqueue:
        # every dispatch DONATES the whole state (donate_argnums=0),
        # so device arrays derived from the live tables must be
        # re-derived from the CURRENT state under the lock (a handle
        # captured off-lock dies at the next dispatch).  The patch
        # itself is an async ``.at[].set`` enqueue — microseconds of
        # lock hold; the device copy overlaps later dispatches.
        # Mirrors are painted after the flip (build lock still
        # held), so a crash anywhere earlier leaves the published
        # generation AND its host mirrors untouched.
        # every caller is inside tables.building() (the build
        # lock lives on self.tables); the dispatch lock is taken here
        from ..infra import faults
        from .conntrack import CTTable

        with self._lock:
            # the mid-swap crash site: fires at the last instant
            # before the flip, with the dispatch lock held — a raise
            # here must still publish NOTHING (chaos-gate regression)
            faults.check(faults.SITE_CHURN_SWAP)
            t_lock = time.monotonic()
            if row_map is not None:
                self.row_map = row_map
            # from here to the flip, a failure leaves live state
            # possibly consumed or half-swapped: flag it so the
            # builder wrapper (_building) heals from the mirrors
            self._swap_incomplete = True
            if device_patch is not None:
                p2, l2 = device_patch(self.state)
                policy = p2 if p2 is not None else policy
                lpm = l2 if l2 is not None else lpm
            if policy is None:
                policy = self.state.policy
            if lpm is None:
                lpm = self.state.ipcache
            if self.state is None:  # keep live CT + counters otherwise
                self.state = DatapathState.create(
                    policy=policy, ipcache=lpm,
                    ct=CTTable.create(self.ct_capacity))
            else:
                self.state = DatapathState(
                    policy=policy, ipcache=lpm,
                    ct=self.state.ct, metrics=self.state.metrics)
            self._rekeep_serving_placement()
            if attach:
                self.attach_count += 1
            # the slot records the PLACED arrays (sharded serving
            # re-places fresh leaves above), so a recycled slot can
            # never hand back unplaced tensors
            gen = self.tables.flip(build, self.state.policy,
                                   self.state.ipcache, t_lock)
            self._swap_incomplete = False
        if mirrors is not None:
            mirrors()
        return gen

    def table_stats(self) -> dict:
        # thread-affinity: any
        """The ``tables`` stats block: generation, swap/update
        latency, delta-compile scoreboard (serving stats -> GET
        /serving -> CLI -> registry)."""
        return self.tables.snapshot()

    def attach(self, policies, ipcache, ep_policy, row_map) -> None:
        # table-swap-ok: full/delta (re)compile builder — device
        # arrays assembled off the dispatch path, published through
        # _publish_tables, host mirrors swapped post-flip
        """Full (re)compile + swap.  When the previous attach's
        per-policy fingerprints are available and the tensor shapes
        still fit, only the policies whose fingerprints changed are
        repainted (``policy.incremental.delta_compile``) — rule and
        selector churn then costs O(changed policies), not O(world),
        and the serving executables never retrace (shapes are
        byte-stable, which the compile log's one-executable guard
        asserts at runtime)."""
        from ..infra import faults
        from ..policy.compiler import policy_fingerprint
        from ..policy.incremental import delta_compile
        from .lpm import DeviceLPM

        jnp = self._jnp
        with self._building() as build:
            policies = list(policies)
            fps = [policy_fingerprint(p) for p in policies]
            published = self._published_state()
            plan = None
            if (self.delta_compile and published is not None
                    and row_map is self.row_map):
                plan = delta_compile(getattr(self, "tensors", None),
                                     policies, row_map,
                                     self._policy_fps, fps)
            # -1 = lxcmap-miss sentinel: a packet with an unregistered
            # endpoint id DROPS (REASON_NO_ENDPOINT) instead of being
            # judged under endpoint 0's policy (reference: bpf_lxc
            # drops on endpoint lookup failure)
            epp = np.full(MAX_ENDPOINTS, -1, dtype=np.int32)
            for ep_id, pol_row in ep_policy.items():
                if not 0 <= ep_id < MAX_ENDPOINTS:
                    # on-device gathers clamp out-of-range ids to the
                    # last row, silently diverging from the oracle —
                    # reject here
                    raise ValueError(
                        f"endpoint id {ep_id} out of range "
                        f"[0, {MAX_ENDPOINTS})")
                epp[ep_id] = pol_row
            tensors = None
            if plan is None:
                # compile first: it may GROW the row map's capacity,
                # which sizes the auth projection below
                tensors = compile_policy(policies, row_map)
                n_rows = tensors.verdict.shape[2]
            else:
                n_rows = self.tensors.verdict.shape[2]
            auth_np = self._project_auth(epp, row_map,
                                         len(policies), n_rows)
            policy, device_patch = None, None
            if plan is None:
                policy = DevicePolicy.from_tensors(tensors, epp,
                                                   auth=auth_np)
            else:
                # delta: ship only the changed policies' slices (and
                # the class maps when the global partition moved).
                # h2d uploads are staged HERE (fresh arrays, immune
                # to dispatch donation); the ``.at[].set`` against
                # the live verdict tensor is deferred to the publish
                # step — dispatches donate the state, so the live
                # arrays must be re-derived under the dispatch lock
                slices_dev = {pi: jnp.asarray(plan.slices[pi][None])
                              for pi in plan.changed}
                pc_dev = cm_dev = None
                if plan.class_structure_changed:
                    pc_dev = jnp.asarray(plan.struct.port_class)
                    cm_dev = jnp.asarray(plan.struct.class_map)
                epp_dev = jnp.asarray(epp)
                auth_dev = jnp.asarray(auth_np)
                for sl in slices_dev.values():  # compile off-lock
                    # every slice: the _dus_warm set dedups same-
                    # shape updates, so this stays O(changed) cheap
                    # and never bets the lock-hold budget on an
                    # all-slices-same-shape assumption
                    self._warm_dus(published.policy.verdict, sl,
                                   (0, 0, 0, 0))

                def device_patch(state):
                    pol = state.policy
                    verdict = pol.verdict
                    for pi, sl in slices_dev.items():
                        verdict = _dus(verdict, sl, (pi, 0, 0, 0))
                    return DevicePolicy(
                        proto_table=pol.proto_table,
                        port_class=(pc_dev if pc_dev is not None
                                    else pol.port_class),
                        class_map=(cm_dev if cm_dev is not None
                                   else pol.class_map),
                        verdict=verdict,
                        ep_policy=epp_dev,
                        auth=auth_dev), None
            # the LPM recompiles every attach (the ipcache map is an
            # arbitrary diff; /32 churn goes through patch_ipcache,
            # never here) — milliseconds, and never a policy compile
            lpm = compile_lpm({c: row_map.row(i)
                               for c, i in ipcache.items()})
            device_lpm = DeviceLPM.from_tensors(lpm)
            faults.check(faults.SITE_CHURN_BUILD)

            def mirrors():
                self._epp = epp
                self._policies = policies
                self._policy_fps = fps
                self._lpm_entries = dict(ipcache)  # cidr -> numeric
                self._lpm_tensors = lpm  # host mirror for patches
                if plan is None:
                    self.tensors = tensors
                else:
                    for pi in plan.changed:
                        self.tensors.verdict[pi] = plan.slices[pi]
                    self.tensors = plan.apply_structure(self.tensors)

            self._publish_tables(build, policy=policy,
                                 lpm=device_lpm,
                                 device_patch=device_patch,
                                 row_map=row_map, mirrors=mirrors,
                                 attach=True)
            # scoreboard bumps only AFTER the publish: a fault-
            # aborted attach counts as a failed build, never as a
            # completed (full or delta) attach
            if plan is None:
                self.tables.full_attaches += 1
                self.tables.policies_recompiled += len(policies)
            else:
                self.tables.delta_attaches += 1
                self.tables.policies_recompiled += len(plan.changed)

    def auth_upsert(self, ep_id: int, remote_id: int,
                    expires: int) -> bool:
        # table-swap-ok: auth-plane builder — the device grant cell
        # is built off the dispatch path and published via
        # _publish_tables (the host-authoritative dict write keeps
        # the dispatch lock it shares with auth_gc/auth_entries)
        jnp = self._jnp
        with self._building() as build:
            with self._lock:
                self._auth[(int(ep_id), int(remote_id))] = int(expires)
            published = self._published_state()
            if published is None or self._epp is None:
                return False
            pr = (self._epp[ep_id]
                  if 0 <= ep_id < MAX_ENDPOINTS else -1)
            r = self.row_map.row(remote_id) if self.row_map else 0
            # shape validation against the active policy (shape
            # metadata survives dispatch donation; the ARRAYS are
            # re-derived under the dispatch lock below)
            if pr < 0 or not 0 < r < published.policy.auth.shape[1]:
                # unknown endpoint/identity row: the grant stays
                # host-side and lands at the next attach
                return False
            exp_dev = jnp.full((1, 1), expires, jnp.uint32)
            self._warm_dus(published.policy.auth, exp_dev, (0, 0))

            def device_patch(state):
                pol = state.policy
                return DevicePolicy(
                    proto_table=pol.proto_table,
                    port_class=pol.port_class,
                    class_map=pol.class_map,
                    verdict=pol.verdict,
                    ep_policy=pol.ep_policy,
                    auth=_dus(pol.auth, exp_dev,
                              (int(pr), int(r)))), None

            self._publish_tables(build, device_patch=device_patch)
        return True

    def auth_entries(self) -> list:
        with self._lock:
            return [{"endpoint": ep, "remote_identity": rem,
                     "expires": exp}
                    for (ep, rem), exp in sorted(self._auth.items())]

    def auth_gc(self, now: int) -> int:
        with self._lock:
            dead = [k for k, exp in self._auth.items() if exp <= now]
            for k in dead:
                del self._auth[k]
        return len(dead)

    def step(self, hdr, now: int, pre_drop=None,
             pre_drop_reason=None, lb_drop=None, audit=False):
        # table-swap-ok: dispatch-result swap — CT/metrics advance,
        # policy+ipcache references carried unchanged
        """``hdr`` may be a numpy array OR an already-on-device jax
        array (the LB stage hands its output over without a host
        round trip).  ``pre_drop`` is the SNAT stage's exhaustion
        mask (rows drop with REASON_NAT_EXHAUSTED);
        ``pre_drop_reason`` carries per-row REASON codes (bandwidth
        manager)."""
        from .verdict import datapath_step_jit

        jnp = self._jnp
        # host staging OUT from under the lock (see __init__ lock
        # discipline): the lock protects dispatch + state swap only,
        # never an h2d copy
        if isinstance(hdr, np.ndarray):
            hdr = jnp.asarray(np.ascontiguousarray(hdr))
        if isinstance(pre_drop, np.ndarray):
            pre_drop = jnp.asarray(pre_drop)
        if isinstance(pre_drop_reason, np.ndarray):
            pre_drop_reason = jnp.asarray(pre_drop_reason)
        if isinstance(lb_drop, np.ndarray):
            lb_drop = jnp.asarray(lb_drop)
        now = jnp.uint32(now)
        with self._lock:
            out, self.state = datapath_step_jit(
                self.state, hdr, now, pre_drop=pre_drop,
                pre_drop_reason=pre_drop_reason, lb_drop=lb_drop,
                audit=audit)
            row_map = self.row_map
        return np.asarray(out), row_map

    def serve(self, ring, hdr, now: int, batch_id: int,
              trace_sample: int = 1024, proxy_ports=None,
              audit: bool = False, valid=None):
        # thread-affinity: drain, api
        # table-swap-ok: dispatch-result swap — CT/metrics advance,
        # policy+ipcache references carried unchanged
        """The SERVING-path step: fused datapath + event-ring append
        in one dispatch, NO host fetch (monitor/ring.py serve_step).
        Returns (ring', row_map); events reach the host when the
        caller drains the ring at its own cadence — the perf-ring
        economics, vs :meth:`step`'s fetch-per-batch debug path.

        ``valid`` ([N] bool, optional) masks the adaptive batcher's
        padding rows: masked rows touch neither CT, metrics, nor the
        event ring, so one bucket size stays one compiled shape."""
        from ..infra import faults
        from ..monitor.ring import serve_step_jit

        faults.check(faults.SITE_LOADER_SERVE)
        hdr, valid, now, bid = self._stage(batch_id, hdr, valid,
                                           (now, batch_id))
        return self._dispatch(
            "wide", batch_id, serve_step_jit, (ring, hdr, now, bid),
            dict(trace_sample=trace_sample, valid=valid,
                 proxy_ports=proxy_ports, audit=audit),
            (int(trace_sample), bool(audit), proxy_ports is not None,
             valid is not None))

    def serve_packed(self, ring, packed, now: int, batch_id: int,
                     ep: int, dirn: int, trace_sample: int = 1024,
                     proxy_ports=None, audit: bool = False,
                     valid=None):
        # thread-affinity: drain, api
        # table-swap-ok: dispatch-result swap — CT/metrics advance,
        # policy+ipcache references carried unchanged
        """The packed serving fast path: [N, 4] uint32 rows —
        16 B/packet on the h2d link instead of :meth:`serve`'s 64 B —
        with on-device unpack + datapath + event-ring append fused in
        ONE dispatch (monitor/ring.py serve_step_packed).  ``ep`` /
        ``dirn`` are per-batch stream metadata scalars;  ``valid``
        masks the adaptive batcher's padding rows exactly like the
        wide path, so each bucket size stays one compiled shape."""
        from ..infra import faults
        from ..monitor.ring import serve_step_packed_jit

        faults.check(faults.SITE_LOADER_SERVE_PACKED)
        packed, valid, now, bid, ep, dirn = self._stage(
            batch_id, packed, valid, (now, batch_id, ep, dirn))
        return self._dispatch(
            "packed", batch_id, serve_step_packed_jit,
            (ring, packed, now, bid, ep, dirn),
            dict(trace_sample=trace_sample, valid=valid,
                 proxy_ports=proxy_ports, audit=audit),
            (int(trace_sample), bool(audit), proxy_ports is not None,
             valid is not None))

    def serve_superbatch(self, ring, hdr, now: int, batch_id0: int,
                         eps=None, dirns=None,
                         trace_sample: int = 1024,
                         proxy_ports=None, audit: bool = False,
                         valid=None, packed: bool = False):
        # thread-affinity: drain, api
        # table-swap-ok: dispatch-result swap — CT/metrics advance,
        # policy+ipcache references carried unchanged
        """The K-batch superbatch dispatch (ISSUE 11): ``hdr`` is
        [K, bucket, 4] packed rows (``packed=True``, with ``eps``/
        ``dirns`` [K] per-step stream scalars) or [K, bucket, N_COLS]
        wide rows; ``valid`` [K, bucket] masks padding rows AND whole
        empty trailing steps.  One lock window, one h2d staging copy,
        one jit call for K batches — the Python per-dispatch cost the
        drain loop pays is amortized K-fold
        (monitor/ring.py serve_superbatch*).

        Generation pinning: the scan captures ONE DatapathState, so
        the whole superbatch serves a single table generation — a
        concurrent publish flips wholly before or wholly after this
        dispatch (re-proven at K>1 by the churn chaos gate)."""
        from ..infra import faults
        from ..monitor.ring import (serve_superbatch_jit,
                                    serve_superbatch_packed_jit)

        faults.check(faults.SITE_LOADER_SERVE_SUPER)
        per_step = (eps, dirns) if packed else ()
        hdr, valid, now, bid0, *per_step = self._stage(
            batch_id0, hdr, valid, (now, batch_id0) + per_step)
        # hdr.shape is (K, bucket, cols): K rides the shape, so the
        # one-executable invariant keys on (rung, mode, K)
        return self._dispatch(
            "super-packed" if packed else "super-wide", batch_id0,
            serve_superbatch_packed_jit if packed
            else serve_superbatch_jit,
            (ring, hdr, now, bid0, *per_step),
            dict(trace_sample=trace_sample, valid=valid,
                 proxy_ports=proxy_ports, audit=audit),
            (int(trace_sample), bool(audit), proxy_ports is not None))

    # -- multi-chip serving (parallel/mesh.py) ------------------------
    def serving_shard(self, mesh) -> None:
        # thread-affinity: drain, api
        # table-swap-ok: placement-only swap (mesh enter) — table
        # contents unchanged, every leaf re-placed for the mesh
        """Enter sharded-serving mode: place the live state for the
        mesh (CT private per chip, policy/ipcache/metrics replicated)
        and route subsequent :meth:`serve_sharded` dispatches through
        per-shard serve steps.  attach()/gc()/ct_restore() keep the
        placement across swaps until :meth:`serving_unshard`."""
        from ..parallel.mesh import shard_state

        with self._lock:
            self._serving_mesh = mesh
            self._sharded_steps = {}
            self.state = shard_state(self.state, mesh)
            # warm the map-pressure occupancy executable for the NEW
            # placement NOW (start_serving runs before tests/benches
            # freeze compile counts): a first pressure sample landing
            # mid-dispatch would otherwise charge its compile to the
            # serving executables' one-per-(rung, mode) window
            _ct_occupied(self.state.ct.fp)

    def serving_unshard(self) -> None:
        # thread-affinity: drain, api
        # table-swap-ok: placement-only swap (mesh exit) — table
        # contents unchanged, gathered back to single-device
        """Leave sharded-serving mode: gather state back to the
        default single-device placement (host round trip — cold path,
        stop_serving only)."""
        import jax

        jnp = self._jnp
        with self._lock:
            if self._serving_mesh is None:
                return
            self._serving_mesh = None
            self._sharded_steps = {}
            self.state = jax.tree.map(
                lambda x: jnp.asarray(np.asarray(x)), self.state)
            _ct_occupied(self.state.ct.fp)  # re-warm single-device

    def serve_sharded(self, ring, hdr, now: int, batch_id: int,
                      trace_sample: int = 1024, proxy_ports=None,
                      audit: bool = False, valid=None,
                      packed_meta=None):
        # thread-affinity: drain, api
        # table-swap-ok: dispatch-result swap — CT/metrics advance,
        # policy+ipcache references carried unchanged
        """One flow-routed batch through the multi-chip serve step.

        ``hdr`` is the ``route_by_flow`` output — wide
        [n_shards*block, N_COLS], or packed [n_shards*block, 4] with
        ``packed_meta=(ep, dirn)`` for the 16 B/packet link format —
        and ``ring`` a :func:`parallel.mesh.make_sharded_ring` pair
        (per-chip private rings).  Each chip runs datapath + ring
        append on its own block; counters psum to global totals."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..infra import faults
        from ..parallel.mesh import make_sharded_serve_step

        # the shard-unavailable failure mode: a chip dropping off the
        # mesh surfaces as the sharded dispatch raising — exactly
        # where the degraded-mode ladder catches it
        faults.check(faults.SITE_LOADER_SERVE_SHARDED)
        mesh = self._serving_mesh
        assert mesh is not None, "serving_shard(mesh) first"
        packed = packed_meta is not None
        # explicit per-chip placement of the batch OUTSIDE the lock:
        # the h2d copy lands each shard's block on its own chip.
        # P("data") spelling matters for the compile cache — see
        # parallel.mesh.make_sharded_ring; valid reuses row_sh, since
        # sharding-spelling identity is load-bearing there too
        row_sh = NamedSharding(mesh, P("data"))
        hdr, valid, now, bid, *meta = self._stage(
            batch_id, hdr, valid, (now, batch_id) + tuple(packed_meta or ()),
            put=lambda x: jax.device_put(x, row_sh))
        if proxy_ports is None:
            proxy_ports = self._jnp.zeros((0,), self._jnp.uint32)
        key = (packed, int(trace_sample), bool(audit))

        def step(state, *args):
            # runs under the lock, which serving_shard() takes to
            # reset the per-mesh step cache
            fn = self._sharded_steps.get(key)
            if fn is None:
                fn = self._sharded_steps[key] = make_sharded_serve_step(
                    mesh, packed=packed, trace_sample=trace_sample,
                    audit=audit)
            return fn(state, *args)

        return self._dispatch(
            "sharded-packed" if packed else "sharded", batch_id, step,
            (ring, hdr, now, bid, valid, proxy_ports, *meta), {},
            key + (valid is not None,))

    def add_route_overflow(self, n: int) -> None:
        # thread-affinity: any
        """Account host-side flow-router overflow in the device
        metricsmap (REASON_ROUTE_OVERFLOW) — the RSS-queue-overflow
        counter; sharding-preserving (.at on the replicated array)."""
        from .verdict import REASON_ROUTE_OVERFLOW

        self.add_host_drops(REASON_ROUTE_OVERFLOW, n)

    def add_host_drops(self, reason: int, n: int) -> None:
        # thread-affinity: any
        """Account host-side drops under ``reason`` in the device
        metricsmap — the serving recovery plane's counterpart of
        :meth:`add_route_overflow`: batches lost to a dead/hung
        dispatch (REASON_DISPATCH_TIMEOUT / REASON_RECOVERY_DROP)
        must show up where operators look, exactly like datapath
        drops.

        NEVER BLOCKS on the dispatch lock: the caller may be the
        serving WATCHDOG accounting a dispatch that is hung INSIDE
        that very lock — waiting here would deadlock recovery
        against the wedge it is recovering from.  When the lock is
        busy the count lands in a host-side pending buffer that
        :meth:`metrics` folds into every read and later calls flush
        opportunistically, so totals are exact either way."""
        if n == 0:
            return
        r = int(reason)
        with self._host_drops_lock:
            self._host_drops[r] = self._host_drops.get(r, 0) + int(n)
        self._flush_host_drops()

    def _flush_host_drops(self) -> None:
        # holds: datapath-loader -- acquired NON-BLOCKING at entry
        # (the early return when busy); every state touch sits inside
        # the acquire/release window the try/finally pins
        # table-swap-ok: metrics-only swap — host-drop counters
        # folded into the metricsmap, tables carried unchanged
        """Move pending host-drop counts into the device metricsmap
        if the dispatch lock is free RIGHT NOW (non-blocking)."""
        from ..parallel.mesh import add_host_drops

        if not self._lock.acquire(blocking=False):
            return
        try:
            with self._host_drops_lock:
                pending = self._host_drops
                if not pending:
                    return
                self._host_drops = {}
            for reason, n in pending.items():
                self.state = add_host_drops(self.state, reason, n)
        finally:
            self._lock.release()

    def masquerade(self, nat, hdr, now: int):
        """CT-aware egress SNAT with port allocation (service/nat.py
        snat_egress); returns (rewritten device hdr, exhaustion drop
        mask) — the mask feeds ``step(pre_drop=...)``.  The NAT table
        lives with the loader like the CT table does (the pkg/maps/nat
        analogue)."""
        from ..service.nat import NATTable, snat_egress_jit

        jnp = self._jnp
        if isinstance(hdr, np.ndarray):
            hdr = jnp.asarray(np.ascontiguousarray(hdr))
        # dispatch INSIDE the lock: step() donates state.ct
        # (donate_argnums=0), so reading it here must not race a
        # concurrent step that would invalidate the buffer between
        # capture and dispatch
        with self._lock:
            if self.nat_state is None:
                self.nat_state = (NATTable.create(self.nat_capacity)
                                  if self.nat_capacity
                                  else NATTable.create())
            hdr, self.nat_state, dropped = snat_egress_jit(
                self.nat_state, nat, self.state.ct, hdr,
                jnp.uint32(now))
            return hdr, dropped

    def reverse_nat(self, nat, hdr, now: int):
        """Ingress reverse translation (post-verdict delivery rewrite:
        replies to allocated node ports restore the original pod
        destination)."""
        from ..service.nat import NATTable, snat_reverse_jit

        jnp = self._jnp
        if isinstance(hdr, np.ndarray):
            hdr = jnp.asarray(np.ascontiguousarray(hdr))
        with self._lock:
            if self.nat_state is None:
                self.nat_state = (NATTable.create(self.nat_capacity)
                                  if self.nat_capacity
                                  else NATTable.create())
            hdr, self.nat_state = snat_reverse_jit(
                self.nat_state, nat, hdr, jnp.uint32(now))
            return hdr

    # -- incremental patching (no recompile, no full upload) ----------
    def patch_identity(self, kind: str, numeric_id: int,
                       policies) -> bool:
        # table-swap-ok: identity-row builder — the patched verdict/
        # auth arrays are built off the dispatch path and published
        # via _publish_tables; the host mirror row is painted only
        # AFTER the flip, so a mid-build crash (churn.* fault sites)
        # leaves both the published generation and the mirror intact
        with self._building() as build:
            published = self._published_state()
            if published is None or self.row_map is None:
                return False
            if len(policies) != self.tensors.verdict.shape[0]:
                return False  # policy list changed shape: full attach
            if kind == "remove" and self.row_map.row(numeric_id) == 0:
                return True  # identity never had a row; nothing to patch
            fresh_row = self.row_map.row(numeric_id) == 0
            row = self.row_map.add(numeric_id)
            if row >= self.tensors.verdict.shape[2]:
                if fresh_row:
                    self.row_map.remove(numeric_id)
                return False  # row capacity grew past the tensor
            try:
                return self._patch_identity_build(
                    build, kind, numeric_id, policies, published,
                    row)
            except BaseException:
                # failed build: the published generation and every
                # mirror stay untouched — including the row map (a
                # freshly-allocated row must not leak per aborted
                # churn op, or chaos-rate faults would fill the
                # verdict tensor's row space)
                if fresh_row:
                    self.row_map.remove(numeric_id)
                raise

    def _patch_identity_build(self, build, kind, numeric_id,
                              policies, published, row) -> bool:
        # table-swap-ok: patch_identity's builder body (split out so
        # the row-map rollback wraps it); publishes via
        # _publish_tables exactly like every other builder.  Called
        # only from patch_identity inside tables.building() (the
        # build lock lives on self.tables)
        from ..infra import faults
        from ..policy.incremental import compose_row
        from .verdict import DevicePolicy

        jnp = self._jnp
        # host compose + h2d staging OFF the dispatch lock
        # (fresh arrays, immune to dispatch donation); the
        # ``.at[].set`` against the live tensors is deferred to
        # the publish step's device_patch (dispatches donate the
        # state, so live arrays re-derive under the lock)
        vals = compose_row(policies, numeric_id, self.tensors)
        # staged as the [n_pol, 2, 1, n_cls] row-slice update the
        # publish-time dynamic_update_slice writes in one pass
        vals_dev = jnp.asarray(vals[:, :, None, :])
        # the auth column must track the row's OCCUPANT: a
        # recycled row would otherwise hand the previous
        # identity's live grant to the newcomer (no-handshake
        # forward).  Re-project this numeric's grants from the
        # host dict; zero on remove.
        auth_col = np.zeros(published.policy.auth.shape[0],
                            dtype=np.uint32)
        if kind == "add" and self._epp is not None:
            with self._lock:  # _auth shares the dispatch lock
                auth_items = list(self._auth.items())
            for (ep, rem), exp in auth_items:
                if rem != numeric_id:
                    continue
                pr = (self._epp[ep]
                      if 0 <= ep < MAX_ENDPOINTS else -1)
                if pr >= 0:
                    auth_col[pr] = max(auth_col[pr], exp)
        auth_dev = jnp.asarray(auth_col[:, None])
        self._warm_dus(published.policy.verdict, vals_dev,
                       (0, 0, 0, 0))
        self._warm_dus(published.policy.auth, auth_dev, (0, 0))
        faults.check(faults.SITE_CHURN_BUILD)

        def device_patch(state):
            pol = state.policy
            return DevicePolicy(
                proto_table=pol.proto_table,
                port_class=pol.port_class,
                class_map=pol.class_map,
                verdict=_dus(pol.verdict, vals_dev,
                             (0, 0, row, 0)),
                ep_policy=pol.ep_policy,
                auth=_dus(pol.auth, auth_dev, (0, row))), None

        def mirrors():
            self.tensors.verdict[:, :, row, :] = vals
            self._policies = list(policies)
            if (kind == "remove" and numeric_id
                    not in self._lpm_entries.values()):
                # row contents are back to defaults and nothing
                # maps to it: recycle (unbounded churn must not
                # grow rows)
                self.row_map.remove(numeric_id)

        self._publish_tables(build, device_patch=device_patch,
                             mirrors=mirrors)
        self.tables.patches += 1
        return True

    def patch_ipcache(self, cidr: str, numeric_id: int) -> bool:
        # table-swap-ok: LPM builder — device patch arrays built off
        # the dispatch path, published via _publish_tables.  The /32
        # fast path must mutate the host mirror BEFORE publishing
        # (lpm_upsert plans and paints in one pass), so a failed
        # build rolls the mirror back via LPMUndo — the published
        # generation and the mirror stay in lockstep either way.
        from ..infra import faults
        from .lpm import DeviceLPM, LPMUndo, lpm_upsert

        jnp = self._jnp
        with self._building() as build:
            published = self._published_state()
            if published is None or self.row_map is None:
                return False
            fresh_row = self.row_map.row(numeric_id) == 0
            row = self.row_map.add(numeric_id)
            if row >= self.tensors.verdict.shape[2]:
                if fresh_row:
                    self.row_map.remove(numeric_id)
                return False
            undo = LPMUndo(self._lpm_tensors, cidr)
            had_entry = cidr in self._lpm_entries
            prev_entry = self._lpm_entries.get(cidr)
            self._lpm_entries[cidr] = numeric_id
            try:
                patches = lpm_upsert(self._lpm_tensors, cidr, row)
                staged_t = None
                new_lpm = device_patch = None
                if patches is None:
                    # padding exhausted / shadowing rebuild: recompile
                    # the LPM alone (never the policy tensors), swap
                    # the mirror object post-flip.  Fresh arrays:
                    # published directly, no live-array derivation
                    staged_t = compile_lpm(
                        {c: self.row_map.row(i)
                         for c, i in self._lpm_entries.items()})
                    new_lpm = DeviceLPM.from_tensors(staged_t)
                else:
                    # stage the payload uploads off-lock; the
                    # ``.at[].set`` against the live LPM re-derives
                    # under the dispatch lock (dispatch donation)
                    staged = [
                        (f, i,
                         jnp.asarray(np.atleast_1d(p)[None]
                                     if f != "l1"
                                     else np.atleast_1d(p)))
                        for f, i, p in patches]
                    for f, _i, pl in staged:  # compile off-lock
                        self._warm_dus(
                            getattr(published.ipcache, f), pl,
                            (0,) if f == "l1" else (0, 0))

                    def device_patch(state):
                        lpm = state.ipcache
                        l1, l2, l3 = lpm.l1, lpm.l2, lpm.l3
                        for field, idx, payload in staged:
                            if field == "l1":
                                l1 = _dus(l1, payload, (idx,))
                            elif field == "l2":
                                l2 = _dus(l2, payload, (idx, 0))
                            else:
                                l3 = _dus(l3, payload, (idx, 0))
                        return None, DeviceLPM(
                            l1=l1, l2=l2, l3=l3, v6_net=lpm.v6_net,
                            v6_mask=lpm.v6_mask,
                            v6_value=lpm.v6_value,
                            v6_plen=lpm.v6_plen, default=lpm.default)
                faults.check(faults.SITE_CHURN_BUILD)

                def mirrors():
                    if staged_t is not None:
                        self._lpm_tensors = staged_t

                self._publish_tables(build, lpm=new_lpm,
                                     device_patch=device_patch,
                                     mirrors=mirrors)
            except BaseException:
                # failed build: the flip never happened, so the host
                # mirror must roll back to exactly the published
                # state (entry map + the upsert's painted cells)
                if had_entry:
                    self._lpm_entries[cidr] = prev_entry
                else:
                    self._lpm_entries.pop(cidr, None)
                undo.restore(self._lpm_tensors)
                if fresh_row:
                    self.row_map.remove(numeric_id)
                raise
            self.tables.patches += 1
        return True

    def delete_ipcache(self, cidr: str) -> bool:
        # table-swap-ok: LPM builder (delete) — same build-off-path /
        # publish-flip / rollback-on-failure structure as
        # patch_ipcache; the /32 fast path paints one mirror cell,
        # restored from a saved copy if the build dies pre-flip
        """Remove one prefix (fqdn TTL expiry).  A /32 is patched in
        place — the slot reverts to the longest remaining covering
        prefix's value, computed from the host entry mirror; anything
        else rebuilds the LPM tensors (never the policy)."""
        import ipaddress

        from ..infra import faults
        from .lpm import DeviceLPM

        jnp = self._jnp
        with self._building() as build:
            published = self._published_state()
            if published is None or self.row_map is None:
                return False
            if cidr not in self._lpm_entries:
                return True  # unknown entry: nothing to do
            prev_entry = self._lpm_entries.pop(cidr)
            net = ipaddress.ip_network(cidr, strict=False)
            saved_row = None  # (blk3, row copy) for rollback
            try:
                in_place = net.version == 4 and net.prefixlen == 32
                if in_place:
                    addr = int(net.network_address)
                    t = self._lpm_tensors
                    hi16, mid8, lo8 = (addr >> 16, (addr >> 8) & 0xFF,
                                       addr & 0xFF)
                    cur1 = int(t.l1[hi16])
                    cur2 = (int(t.l2[-cur1 - 1, mid8]) if cur1 < 0
                            else 0)
                    if cur1 >= 0 or cur2 >= 0:
                        # the /32 was never expanded into an l3 slot
                        # (it came in via a full compile that merged
                        # it, or was shadowed) — too ambiguous to
                        # patch: rebuild
                        in_place = False
                staged_t = new_lpm = device_patch = None
                if in_place:
                    # longest remaining covering v4 prefix -> value
                    best_len, best_num = -1, None
                    for c, num in self._lpm_entries.items():
                        n2 = ipaddress.ip_network(c, strict=False)
                        if n2.version != 4 or n2.prefixlen <= best_len:
                            continue
                        shift = 32 - n2.prefixlen
                        if n2.prefixlen == 0 or (
                                addr >> shift) == (
                                    int(n2.network_address) >> shift):
                            best_len, best_num = n2.prefixlen, num
                    value = (self._lpm_tensors.default
                             if best_num is None
                             else self.row_map.row(best_num))
                    blk3 = -cur2 - 1
                    saved_row = (blk3, t.l3[blk3].copy())
                    t.l3[blk3, lo8] = value
                    # payload staged off-lock; the live-LPM derive
                    # happens under the dispatch lock (donation)
                    row_dev = jnp.asarray(t.l3[blk3][None])
                    self._warm_dus(published.ipcache.l3, row_dev,
                                   (0, 0))

                    def device_patch(state):
                        lpm = state.ipcache
                        return None, DeviceLPM(
                            l1=lpm.l1, l2=lpm.l2,
                            l3=_dus(lpm.l3, row_dev, (blk3, 0)),
                            v6_net=lpm.v6_net, v6_mask=lpm.v6_mask,
                            v6_value=lpm.v6_value,
                            v6_plen=lpm.v6_plen,
                            default=lpm.default)
                else:
                    staged_t = compile_lpm(
                        {c: self.row_map.row(i)
                         for c, i in self._lpm_entries.items()})
                    new_lpm = DeviceLPM.from_tensors(staged_t)
                faults.check(faults.SITE_CHURN_BUILD)

                def mirrors():
                    if staged_t is not None:
                        self._lpm_tensors = staged_t

                self._publish_tables(build, lpm=new_lpm,
                                     device_patch=device_patch,
                                     mirrors=mirrors)
            except BaseException:
                self._lpm_entries[cidr] = prev_entry
                if saved_row is not None:
                    self._lpm_tensors.l3[saved_row[0]] = saved_row[1]
                raise
            self.tables.patches += 1
        return True

    def nat_snapshot(self) -> Optional[np.ndarray]:
        with self._lock:
            if self.nat_state is None:
                return None
            return np.asarray(self.nat_state.table)

    def nat_restore(self, table: np.ndarray) -> None:
        from ..service.nat import NATTable

        table = np.ascontiguousarray(table, dtype=np.uint32)
        with self._lock:
            self.nat_state = NATTable(table=self._jnp.asarray(table),
                                      failed=self._jnp.uint32(0))

    def nat_status(self, now: int) -> Optional[dict]:
        from ..service.nat import NAT_PORT_MIN, nat_live_count

        with self._lock:
            if self.nat_state is None:
                return None
            return {
                "capacity": self.nat_state.capacity,
                "port-min": NAT_PORT_MIN,
                "live": nat_live_count(self.nat_state, now),
                "alloc-failed": int(np.asarray(self.nat_state.failed)),
            }

    def map_pressure(self, now: int) -> dict:
        # thread-affinity: api, offline, cli -- the map-pressure
        # controller / query threads; NEVER the drain thread (the
        # occupancy reduction + scalar fetches block on the device)
        """The map-pressure sample (datapath/pressure.py): occupied
        CT slots via the fingerprint bitmap (one warmed jitted
        reduction, ~4 B/slot), cumulative insert drops
        (``CTTable.dropped`` — restore-time drops included), and
        SNAT pool failures.  Runs under the dispatch lock like gc():
        the state capture must not race a donating dispatch."""
        from .lpm import LPM_NOMINAL_CAPACITY

        with self._lock:
            ct = self.state.ct
            occupied = int(np.asarray(_ct_occupied(ct.fp)))
            drops = int(np.asarray(ct.dropped))
            nat_cap = (self.nat_state.capacity
                       if self.nat_state is not None else None)
            nat_failed = (int(np.asarray(self.nat_state.failed))
                          if self.nat_state is not None else 0)
            # host mirrors only from here down: programmed prefixes
            # and identity-row headroom never touch the device
            lpm_entries = len(self._lpm_entries)
            rows, rows_cap = (self.row_map.row_occupancy()
                              if self.row_map is not None else (0, 0))
        return {
            "ct": {"capacity": self.ct_capacity,
                   "occupied": occupied,
                   "occupancy": round(occupied / self.ct_capacity,
                                      4),
                   "insert-drops": drops},
            "nat": {"capacity": nat_cap, "failures": nat_failed},
            "lpm": {"capacity": LPM_NOMINAL_CAPACITY,
                    "entries": lpm_entries,
                    "occupancy": round(
                        lpm_entries / LPM_NOMINAL_CAPACITY, 6)},
            "policy": {"capacity": rows_cap, "rows": rows,
                       "occupancy": (round(rows / rows_cap, 4)
                                     if rows_cap else None)},
        }

    def gc(self, now: int) -> int:
        # table-swap-ok: CT-only swap (expiry sweep) — tables carried
        # unchanged
        from .conntrack import ct_gc_jit

        with self._lock:
            ct, n = ct_gc_jit(self.state.ct, self._jnp.uint32(now))
            self.state = DatapathState(
                policy=self.state.policy, ipcache=self.state.ipcache,
                ct=ct, metrics=self.state.metrics)
            self._rekeep_serving_placement()
        return int(n)

    def metrics(self) -> np.ndarray:
        with self._lock:
            out = np.array(np.asarray(self.state.metrics))
        # fold in host drops still awaiting a lock-free flush (NOT
        # zeroed here — display-only add keeps flush idempotent)
        with self._host_drops_lock:
            for reason, n in self._host_drops.items():
                out[reason, 0] += n
        return out

    def ct_snapshot(self) -> np.ndarray:
        # thread-affinity: drain, api, watchdog
        """Dense live rows — the canonical (placement-free) snapshot
        format, restorable into any capacity or backend."""
        from .conntrack import ct_rows_from_table

        with self._lock:
            return ct_rows_from_table(np.asarray(self.state.ct.table))

    def ct_restore(self, table: np.ndarray) -> None:
        # thread-affinity: drain, api, offline
        # table-swap-ok: CT-only swap (snapshot restore) — tables
        # carried unchanged
        from .conntrack import (CTTable, ROW_WORDS, ct_fp_from_table,
                                ct_rows_from_table, ct_table_from_rows)

        jnp = self._jnp
        table = np.asarray(table)
        assert table.ndim == 2 and table.shape[1] == ROW_WORDS
        # normalize (accepts dense rows OR a full hashed table — live
        # rows are extracted either way), then re-place with the device
        # hash so probes find every entry at this capacity
        table, n_dropped = ct_table_from_rows(ct_rows_from_table(table),
                                              self.ct_capacity)
        with self._lock:
            self.state = DatapathState(
                policy=self.state.policy, ipcache=self.state.ipcache,
                ct=CTTable(table=jnp.asarray(table),
                           fp=jnp.asarray(ct_fp_from_table(table)),
                           dropped=jnp.uint32(n_dropped)),
                metrics=self.state.metrics)
            self._rekeep_serving_placement()


class InterpreterLoader(Loader):
    """Oracle-backed datapath — no accelerator needed (fake datapath).

    Table updates apply structurally to the oracle (no device slots
    to double-buffer), but the generation tag and swap counters keep
    parity with :class:`TPULoader` so every surface (serving stats,
    registry, CLI) and every backend-agnostic test reads one shape.
    """
    # active-tables: oracle

    def __init__(self, ct_capacity: int = 0,
                 nat_capacity: Optional[int] = None):
        from .tables import TableVersioner
        from .verdict import N_REASONS

        self.oracle = None
        self.nat_state = None  # numpy NAT table (port-pool mirror)
        self.nat_failed = 0
        self.nat_capacity = nat_capacity  # None = default pool
        self.row_map: Optional[IdentityRowMap] = None
        self._metrics = np.zeros((N_REASONS, 2), dtype=np.uint64)
        self.attach_count = 0
        self._auth_display: Dict[Tuple[int, int], int] = {}
        self.tables = TableVersioner()

    def table_stats(self) -> dict:
        # thread-affinity: any
        return self.tables.snapshot()

    def map_pressure(self, now: int) -> dict:
        # thread-affinity: any
        """TPULoader.map_pressure parity.  The oracle CT is an
        unbounded dict (no probe window), so occupancy is None and
        insert drops stay 0 — the pressure monitor then keys on the
        NAT counters alone, which DO mirror the device pool."""
        from .lpm import LPM_NOMINAL_CAPACITY

        live = len(self.oracle.ct) if self.oracle is not None else 0
        lpm_entries = (len(self.oracle.ipcache)
                       + len(self.oracle._exact)
                       if self.oracle is not None else 0)
        rows, rows_cap = (self.row_map.row_occupancy()
                          if self.row_map is not None else (0, 0))
        return {
            "ct": {"capacity": 0, "occupied": live,
                   "occupancy": None, "insert-drops": 0},
            "nat": {"capacity": (self.nat_state.shape[0]
                                 if self.nat_state is not None
                                 else None),
                    "failures": self.nat_failed},
            "lpm": {"capacity": LPM_NOMINAL_CAPACITY,
                    "entries": lpm_entries,
                    "occupancy": round(
                        lpm_entries / LPM_NOMINAL_CAPACITY, 6)},
            "policy": {"capacity": rows_cap, "rows": rows,
                       "occupancy": (round(rows / rows_cap, 4)
                                     if rows_cap else None)},
        }

    def nat_snapshot(self) -> Optional[np.ndarray]:
        return None if self.nat_state is None else self.nat_state.copy()

    def nat_restore(self, table: np.ndarray) -> None:
        self.nat_state = np.ascontiguousarray(table, dtype=np.uint32)

    def nat_status(self, now: int) -> Optional[dict]:
        from ..service.nat import NAT_PORT_MIN, NV_EXPIRES

        if self.nat_state is None:
            return None
        return {
            "capacity": self.nat_state.shape[0],
            "port-min": NAT_PORT_MIN,
            "live": int((self.nat_state[:, NV_EXPIRES] >= now).sum()),
            "alloc-failed": self.nat_failed,
        }

    def attach(self, policies, ipcache, ep_policy, row_map) -> None:
        # table-swap-ok: the oracle world swap (structural apply);
        # generation bumped for TPULoader parity
        from ..testing.oracle import OracleDatapath

        with self.tables.building() as build:
            old_ct = self.oracle.ct if self.oracle is not None else None
            self.row_map = row_map
            # endpoints not listed are lxcmap misses: the oracle drops
            # them (REASON_NO_ENDPOINT), matching the device's -1
            # sentinel
            pol_by_ep = {ep: policies[row]
                         for ep, row in ep_policy.items()}
            old_auth = (self.oracle.auth if self.oracle is not None
                        else None)
            self.oracle = OracleDatapath(pol_by_ep, dict(ipcache))
            if old_ct is not None:
                self.oracle.ct = old_ct
            if old_auth is not None:  # grants survive attach (authmap)
                self.oracle.auth = old_auth
            self.attach_count += 1
            self.tables.full_attaches += 1
            self.tables.note_publish(build)

    def auth_upsert(self, ep_id: int, remote_id: int,
                    expires: int) -> bool:
        # table-swap-ok: auth-grant apply on the oracle (keyed by
        # subject labels); no generation bump — grants are queried
        # live, never snapshot-compiled here
        if self.oracle is None:
            return False
        pol = self.oracle.ep_policies.get(int(ep_id))
        if pol is None:
            return False
        # keyed by SUBJECT LABELS, not endpoint id: label-identical
        # endpoints share grants exactly like the device's shared
        # policy row (upstream: authmap keys the local IDENTITY)
        self.oracle.auth[(pol.subject_labels.sorted_key(),
                          int(remote_id))] = int(expires)
        self._auth_display[(int(ep_id), int(remote_id))] = int(expires)
        return True

    def auth_entries(self) -> list:
        return [{"endpoint": ep, "remote_identity": rem,
                 "expires": exp}
                for (ep, rem), exp in sorted(
                    self._auth_display.items())]

    def auth_gc(self, now: int) -> int:
        # table-swap-ok: auth-grant expiry sweep on the oracle
        if self.oracle is None:
            return 0
        dead = [k for k, exp in self.oracle.auth.items()
                if exp <= now]
        for k in dead:
            del self.oracle.auth[k]
        for k in [k for k, exp in self._auth_display.items()
                  if exp <= now]:
            del self._auth_display[k]
        return len(dead)

    def step(self, hdr: np.ndarray, now: int, pre_drop=None,
             pre_drop_reason=None, lb_drop=None, audit=False):
        from ..core.packets import HeaderBatch, COL_DIR
        from .verdict import N_OUT

        results = self.oracle.step(
            HeaderBatch(np.asarray(hdr)), now, pre_drop=pre_drop,
            pre_drop_reason=(None if pre_drop_reason is None
                             else np.asarray(pre_drop_reason)),
            lb_drop=(None if lb_drop is None
                     else np.asarray(lb_drop)),
            audit=audit)
        out = np.zeros((len(results), N_OUT), dtype=np.uint32)
        for i, r in enumerate(results):
            out[i] = (r.verdict, r.proxy, r.ct,
                      self.row_map.row(r.identity), r.reason, r.event)
            self._metrics[r.reason, int(hdr[i][COL_DIR])] += 1
        return out, self.row_map

    def gc(self, now: int) -> int:
        return self.oracle.gc(now)

    # -- incremental patching -----------------------------------------
    # The oracle evaluates MapState.lookup over the live contribution
    # lists (already updated by update_contributions), so the policy
    # side needs only a row for event decode; ipcache patches edit the
    # oracle's prefix list directly.

    def patch_identity(self, kind: str, numeric_id: int,
                       policies) -> bool:
        # table-swap-ok: row-map-only apply (the oracle evaluates the
        # live contribution lists); generation bumped for parity —
        # including the NO-OP early returns, which must not bump
        # (TPULoader publishes nothing for them either)
        if self.oracle is None or self.row_map is None:
            return False
        if kind == "remove" and self.row_map.row(numeric_id) == 0:
            return True  # identity never had a row; nothing to patch
        with self.tables.building() as build:
            if kind == "remove":
                self.row_map.remove(numeric_id)
            else:
                self.row_map.add(numeric_id)
            self.tables.patches += 1
            self.tables.note_publish(build)
        return True

    def _nat_table(self):
        from ..service.nat import NAT_DEFAULT_CAPACITY, NAT_ROW_WORDS

        if self.nat_state is None:
            self.nat_state = np.zeros(
                (self.nat_capacity or NAT_DEFAULT_CAPACITY,
                 NAT_ROW_WORDS), dtype=np.uint32)
        return self.nat_state

    def masquerade(self, nat, hdr, now: int) -> np.ndarray:
        """Mirror of service.nat.snat_egress over a numpy NAT table +
        the oracle's CT dict.  Same FNV hash, same window, and the
        SAME two-phase order as the device kernel — full-window match
        scan first, then a step-outer/row-inner claim loop (the
        device awards contended slots to the lowest batch row, which
        is exactly what the inner row loop does here) — so allocated
        ports are bit-equal across backends."""
        from ..core.packets import (COL_DIR, COL_DPORT, COL_DST_IP3,
                                    COL_FAMILY, COL_PROTO, COL_SPORT,
                                    COL_SRC_IP3)
        from ..service.nat import (NAT_PORT_MIN, NAT_PROBE, NV_DP,
                                   NV_DST, NV_EXPIRES, NV_SNAT_IP,
                                   NV_SPORT, NV_SRC, _nat_hash_py,
                                   _nat_lifetime_py)
        from ..testing.oracle import OracleDatapath

        hdr = np.array(hdr, dtype=np.uint32)
        dropped = np.zeros(len(hdr), dtype=bool)
        if not nat.enabled:
            return hdr, dropped
        table = self._nat_table()
        P = table.shape[0]
        nets = [(int(n), int(m)) for n, m in
                zip(np.asarray(nat.net), np.asarray(nat.mask))]
        node_ip = int(np.asarray(nat.node_ip))
        egw = list(zip(np.asarray(nat.egw_src).tolist(),
                       np.asarray(nat.egw_net).tolist(),
                       np.asarray(nat.egw_mask).tolist(),
                       np.asarray(nat.egw_ip).tolist()))

        def r_key(s):
            r = table[s]
            return (int(r[NV_SRC]), int(r[NV_SPORT]), int(r[NV_DST]),
                    int(r[NV_DP]))

        claimants = []  # (hdr_row_index, key, h)
        for i in range(len(hdr)):
            row = hdr[i]
            if row[COL_DIR] != 1 or row[COL_FAMILY] != 4:
                continue
            dst = int(row[COL_DST_IP3])
            src0 = int(row[COL_SRC_IP3])
            # egress-gateway policy: first (src, destCIDR) match wins
            # and overrides the non-masquerade exemption
            rewrite_ip = node_ip
            gw = False
            for g_src, g_net, g_mask, g_ip in egw:
                if src0 == g_src and (dst & g_mask) == g_net:
                    rewrite_ip, gw = g_ip, True
                    break
            if not gw and any((dst & m) == n for n, m in nets):
                continue
            rev = OracleDatapath._rev(OracleDatapath._tuple(row))
            e = self.oracle.ct.get(rev)
            if e is not None and e.expires >= now:
                continue  # reply of an inbound connection
            src, sport = src0, int(row[COL_SPORT])
            proto = int(row[COL_PROTO])
            if proto not in (6, 17, 132):
                # portless: port-preserving rewrite only
                row[COL_SRC_IP3] = rewrite_ip
                continue
            dp = (int(row[COL_DPORT]) << 8) | proto
            key = (src, sport, dst, dp)
            h = _nat_hash_py(key)
            # phase 1: full-window scan for a live same-tuple mapping
            hit = None
            for step in range(NAT_PROBE):
                s = (h + step) % P
                if (int(table[s][NV_EXPIRES]) >= now
                        and r_key(s) == key):
                    hit = s
                    break
            if hit is not None:
                # a live mapping keeps its recorded SNAT ip (device
                # parity: policy churn must not flip a flow's ip)
                kept = int(table[hit][NV_SNAT_IP]) or node_ip
                table[hit] = (*key, now + _nat_lifetime_py(proto),
                              kept)
                row[COL_SRC_IP3] = kept
                row[COL_SPORT] = NAT_PORT_MIN + hit
            else:
                row[COL_SRC_IP3] = rewrite_ip
                claimants.append((i, key, h, proto, rewrite_ip))
        # phase 2: lockstep claim rounds (device parity)
        for step in range(NAT_PROBE):
            if not claimants:
                break
            still = []
            for i, key, h, proto, rewrite_ip in claimants:
                s = (h + step) % P
                if (int(table[s][NV_EXPIRES]) < now
                        or r_key(s) == key):
                    table[s] = (*key, now + _nat_lifetime_py(proto),
                                rewrite_ip)
                    hdr[i][COL_SPORT] = NAT_PORT_MIN + s
                else:
                    still.append((i, key, h, proto, rewrite_ip))
            claimants = still
        # leftover claimants: pool exhaustion — DROP (parity with
        # snat_egress's `dropped` mask; reference DROP_NAT_NO_MAPPING)
        self.nat_failed += len(claimants)
        for i, _key, _h, _proto, _rip in claimants:
            dropped[i] = True
        return hdr, dropped

    def reverse_nat(self, nat, hdr, now: int) -> np.ndarray:
        """Sequential mirror of service.nat.snat_reverse."""
        from ..core.packets import (COL_DIR, COL_DPORT, COL_DST_IP3,
                                    COL_FAMILY, COL_PROTO, COL_SPORT,
                                    COL_SRC_IP3)
        from ..service.nat import (NAT_PORT_MIN, NV_DP, NV_DST,
                                   NV_EXPIRES, NV_SNAT_IP, NV_SPORT,
                                   NV_SRC, _nat_lifetime_py)

        hdr = np.array(hdr, dtype=np.uint32)
        if not nat.enabled:
            return hdr
        table = self._nat_table()
        P = table.shape[0]
        node_ip = int(np.asarray(nat.node_ip))
        for i in range(len(hdr)):
            row = hdr[i]
            dport = int(row[COL_DPORT])
            if (row[COL_DIR] != 0 or row[COL_FAMILY] != 4
                    or not NAT_PORT_MIN <= dport < NAT_PORT_MIN + P):
                continue
            s = dport - NAT_PORT_MIN
            r = table[s]
            row_ip = int(r[NV_SNAT_IP]) or node_ip
            if int(row[COL_DST_IP3]) != row_ip:
                continue
            rdp = (int(row[COL_SPORT]) << 8) | int(row[COL_PROTO])
            if (int(r[NV_EXPIRES]) >= now
                    and int(r[NV_DST]) == int(row[COL_SRC_IP3])
                    and int(r[NV_DP]) == rdp):
                row[COL_DST_IP3] = r[NV_SRC]
                row[COL_DPORT] = r[NV_SPORT]
                table[s][NV_EXPIRES] = now + _nat_lifetime_py(
                    int(row[COL_PROTO]))
        return hdr

    def patch_ipcache(self, cidr: str, numeric_id: int) -> bool:
        # table-swap-ok: oracle prefix-list apply; generation bumped
        # for parity
        import ipaddress

        if self.oracle is None:
            return False
        with self.tables.building() as build:
            net = ipaddress.ip_network(cidr, strict=False)
            host_bits = 32 if net.version == 4 else 128
            addr = int(net.network_address)
            if net.prefixlen == host_bits:
                self.oracle._exact[(net.version, addr)] = numeric_id
            else:
                key = (net.version, addr, net.prefixlen)
                self.oracle.ipcache = [
                    e for e in self.oracle.ipcache if e[:3] != key]
                self.oracle.ipcache.append((net.version, addr,
                                            net.prefixlen,
                                            numeric_id))
            self.oracle._lpm_memo.clear()
            self.tables.patches += 1
            self.tables.note_publish(build)
        return True

    def delete_ipcache(self, cidr: str) -> bool:
        # table-swap-ok: oracle prefix-list apply (delete); generation
        # bumped for parity — an UNKNOWN entry is a no-op on both
        # backends and must not bump (TPULoader publishes nothing)
        import ipaddress

        if self.oracle is None:
            return False
        net = ipaddress.ip_network(cidr, strict=False)
        host_bits = 32 if net.version == 4 else 128
        addr = int(net.network_address)
        key = (net.version, addr, net.prefixlen)
        if net.prefixlen == host_bits:
            if (net.version, addr) not in self.oracle._exact:
                return True  # unknown entry: nothing to do
        elif all(e[:3] != key for e in self.oracle.ipcache):
            return True  # unknown entry: nothing to do
        with self.tables.building() as build:
            if net.prefixlen == host_bits:
                self.oracle._exact.pop((net.version, addr), None)
            else:
                self.oracle.ipcache = [
                    e for e in self.oracle.ipcache if e[:3] != key]
            self.oracle._lpm_memo.clear()
            self.tables.patches += 1
            self.tables.note_publish(build)
        return True

    def add_host_drops(self, reason: int, n: int) -> None:
        """Host-side drop accounting (ingress column), mirroring
        :meth:`TPULoader.add_host_drops`."""
        if n:
            self._metrics[int(reason), 0] += int(n)

    def metrics(self) -> np.ndarray:
        return self._metrics.copy()

    def ct_snapshot(self) -> np.ndarray:
        """Oracle CT dict -> dense snapshot rows (the portable format;
        restorable into either backend).  The oracle tracks no per-flow
        packet/byte counters, so those words are zero."""
        from .conntrack import ROW_WORDS, V_EXPIRES, V_PROXY, V_STATE

        rows = np.zeros((len(self.oracle.ct), ROW_WORDS), dtype=np.uint32)
        for i, (key, e) in enumerate(self.oracle.ct.items()):
            src, dst, sport, dport, proto, dirn = key
            rows[i, 0:4] = src
            rows[i, 4:8] = dst
            rows[i, 8] = (sport << 16) | dport
            rows[i, 9] = proto | (dirn << 8)
            rows[i, V_STATE] = e.state
            rows[i, V_EXPIRES] = e.expires
            rows[i, V_PROXY] = e.proxy
        return rows

    def ct_restore(self, table: np.ndarray) -> None:
        # table-swap-ok: CT-only apply (snapshot restore) — policy/
        # ipcache untouched, no generation bump
        """Accepts dense rows or a full hashed table from either
        backend; live rows decode back into the oracle dict."""
        from ..testing.oracle import _CTEntry
        from .conntrack import (V_EXPIRES, V_PROXY, V_STATE,
                                ct_rows_from_table)

        assert self.oracle is not None, "attach() before ct_restore()"
        self.oracle.ct.clear()
        for row in ct_rows_from_table(np.asarray(table)):
            key = (tuple(int(x) for x in row[0:4]),
                   tuple(int(x) for x in row[4:8]),
                   int(row[8]) >> 16, int(row[8]) & 0xFFFF,
                   int(row[9]) & 0xFF, (int(row[9]) >> 8) & 1)
            self.oracle.ct[key] = _CTEntry(state=int(row[V_STATE]),
                                           expires=int(row[V_EXPIRES]),
                                           proxy=int(row[V_PROXY]))
