"""The fused per-packet verdict pipeline — ``bpf_lxc.c`` as one jit fn.

Reference: upstream cilium ``bpf/bpf_lxc.c`` ``handle_xgress``: parse ->
ipcache LPM (``lib/eps.h``) -> ``ct_lookup4`` (``lib/conntrack.h``) ->
``policy_can_access_ingress`` (``lib/policy.h``) -> ``ct_create4`` ->
emit trace/drop/policy-verdict events.  TPU-first redesign: the whole
stack is ONE jitted function over the ``[N, N_COLS]`` header tensor;
every stage is gathers/elementwise so XLA fuses it into a handful of
kernels, and the batch axis shards across chips with ``shard_map``
(tables replicated, packets split).

State (policy tensors, ipcache LPM, conntrack) threads functionally:
``datapath_step(state, hdr, now) -> (out, state')`` where ``out`` is the
per-packet event tensor the monitor layer decodes (the perf-ringbuffer
analogue, returned via outfeed/device->host copy).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.packets import (
    COL_DIR,
    COL_DPORT,
    COL_DST_IP0,
    COL_EP,
    COL_FAMILY,
    COL_PROTO,
    COL_SRC_IP0,
)
from ..policy.compiler import (AUTH_SHIFT, PolicyTensors, PROXY_MASK,
                               PROXY_SHIFT, VERDICT_MASK)
from ..policy.mapstate import (
    VERDICT_ALLOW,
    VERDICT_DEFAULT_DENY,
    VERDICT_DENY,
    VERDICT_REDIRECT,
)
from .conntrack import (
    CT_ESTABLISHED,
    CT_NEW,
    CT_RELATED,
    CT_REPLY,
    CTTable,
    V_PROXY,
    ct_keys_from_headers,
    ct_lookup,
    ct_update,
)
from .lpm import DeviceLPM, LPMTensors, lpm_lookup

# Drop reasons (reference: bpf/lib/drop.h DROP_* codes, renumbered).
REASON_FORWARDED = 0
REASON_POLICY_DENY = 1  # explicit deny rule
REASON_POLICY_DEFAULT_DENY = 2  # no rule allowed it (default deny)
REASON_ROUTE_OVERFLOW = 3  # flow-router shard block overflow (RSS queue)
REASON_NO_ENDPOINT = 4  # unregistered endpoint id (lxcmap miss)
REASON_NAT_EXHAUSTED = 5  # SNAT port pool exhausted (DROP_NAT_NO_MAPPING)
REASON_BANDWIDTH = 6  # egress rate limit (bandwidth manager / EDT)
REASON_NO_SERVICE = 7  # service frontend with no backend (DROP_NO_SERVICE)
REASON_AUTH_REQUIRED = 8  # policy allows, mutual auth missing (pkg/auth)
# admission-queue shed at the serving front door (cilium_tpu/serving):
# the XDP-ring-overflow analogue.  Host-synthesized (the row never
# reached the device), but numbered in this space so every decode
# table — monitor, flow layer, ring wire format (4-bit field) — names
# it like any datapath drop.
REASON_INGRESS_OVERFLOW = 9
# serving fault recovery (host-synthesized, like INGRESS_OVERFLOW):
# the dispatch watchdog deadlined a hung device dispatch and dropped
# its in-flight batch...
REASON_DISPATCH_TIMEOUT = 10
# ...or a dead/failed dispatch's rows (and any rows still queued when
# a dead drain loop stops) were accounted by the recovery supervisor
# instead of silently vanishing — admitted traffic is ALWAYS one of
# completed / shed / recovery-dropped (serving/runtime.py invariant)
REASON_RECOVERY_DROP = 11
# cluster front-end router shed (cilium_tpu/cluster/router.py): a
# node replica's bounded forward queue was full, so the packet never
# reached that node's admission queue.  Host-synthesized like
# INGRESS_OVERFLOW, one level further out — the cluster tier's entry
# in the cluster-wide ledger (submitted == per-node accounted
# + router_overflow + failover_dropped).
REASON_CLUSTER_OVERFLOW = 12
N_REASONS = 13

# Event types in the out tensor (monitor vocabulary).
EV_TRACE = 0  # TraceNotify: forwarded established/reply traffic
EV_VERDICT = 1  # PolicyVerdictNotify: NEW connection decision
EV_DROP = 2  # DropNotify

# Out tensor columns.
OUT_VERDICT = 0  # final VERDICT_* code
OUT_PROXY = 1  # proxy port when redirected
OUT_CT = 2  # CT_* lookup result
OUT_ID_ROW = 3  # remote identity row (host maps to numeric id)
OUT_REASON = 4  # drop reason (REASON_*)
OUT_EVENT = 5  # EV_*
N_OUT = 6

MAX_ENDPOINTS = 4096


@jax.tree_util.register_pytree_node_class
@dataclass
class DevicePolicy:
    """Compiled policy tensors on device + endpoint->policy-row map
    (the policymap + lxcmap of the TPU datapath)."""

    proto_table: jnp.ndarray  # [256] int32
    port_class: jnp.ndarray  # [N_PROTO, 65536] int32 -> GLOBAL class
    class_map: jnp.ndarray  # [n_pol, n_cls_global] int32 -> LOCAL
    verdict: jnp.ndarray  # [n_pol, 2, n_rows, n_local] int32
    ep_policy: jnp.ndarray  # [MAX_ENDPOINTS] int32 endpoint -> policy row
    # [n_pol, n_rows] uint32 mutual-auth expiries (the authmap
    # analogue, pkg/auth: keyed local identity x remote identity —
    # policy rows ARE identity-granular via the distillery)
    auth: jnp.ndarray

    @staticmethod
    def from_tensors(t: PolicyTensors,
                     ep_policy: np.ndarray = None,
                     auth: np.ndarray = None) -> "DevicePolicy":
        if ep_policy is None:
            # default matches TPULoader.attach: every endpoint id is
            # an lxcmap miss until registered (callers that want the
            # all-registered single-policy shape pass explicit zeros)
            ep_policy = np.full(MAX_ENDPOINTS, -1, dtype=np.int32)
        if auth is None:
            auth = np.zeros((t.verdict.shape[0], t.verdict.shape[2]),
                            dtype=np.uint32)
        return DevicePolicy(
            proto_table=jnp.asarray(t.proto_table),
            port_class=jnp.asarray(t.port_class),
            class_map=jnp.asarray(t.class_map),
            verdict=jnp.asarray(t.verdict),
            ep_policy=jnp.asarray(ep_policy),
            auth=jnp.asarray(auth),
        )

    def tree_flatten(self):
        return ((self.proto_table, self.port_class, self.class_map,
                 self.verdict, self.ep_policy, self.auth), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclass
class DatapathState:
    """Full device datapath state — the BPF-maps bundle as a pytree."""

    policy: DevicePolicy
    ipcache: DeviceLPM
    ct: CTTable
    metrics: jnp.ndarray  # [N_REASONS, 2] uint32: [reason, dir] counts

    @staticmethod
    def create(policy: DevicePolicy, ipcache: DeviceLPM,
               ct: CTTable) -> "DatapathState":
        return DatapathState(
            policy=policy, ipcache=ipcache, ct=ct,
            metrics=jnp.zeros((N_REASONS, 2), dtype=jnp.uint32))

    def tree_flatten(self):
        return ((self.policy, self.ipcache, self.ct, self.metrics), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def datapath_step(state: DatapathState, hdr: jnp.ndarray,
                  now: jnp.ndarray, valid: jnp.ndarray = None,
                  pre_drop: jnp.ndarray = None,
                  pre_drop_reason: jnp.ndarray = None,
                  lb_drop: jnp.ndarray = None,
                  audit: bool = False
                  ) -> Tuple[jnp.ndarray, DatapathState]:
    """One batched pass of the full verdict pipeline (see module doc).

    ``valid`` (optional [N] bool) masks padding rows added by the
    multi-chip flow router; masked rows produce output rows but touch
    neither CT state nor metrics.

    ``pre_drop`` (optional [N] bool) marks rows an earlier stage
    already condemned — today the SNAT stage on port-pool exhaustion
    (reference: DROP_NAT_NO_MAPPING; the reference DROPS rather than
    emit a colliding node-side tuple).  Policy/lxcmap verdicts keep
    precedence (upstream order: bpf_lxc judges before host SNAT);
    rows that would otherwise forward drop with
    ``REASON_NAT_EXHAUSTED`` and create no CT entry.

    ``pre_drop_reason`` (optional [N] uint32, 0 = none) is the
    generalized form: rows carry their own REASON_* code (today the
    bandwidth manager's ``REASON_BANDWIDTH``), with the same
    precedence and CT semantics as ``pre_drop``.

    ``audit`` (static): policy-audit-mode (reference:
    --policy-audit-mode): NEW flows the POLICY stage would deny
    (explicit deny, default deny, missing mutual auth) FORWARD and
    create CT state, while the emitted verdict event keeps the
    would-be reason (verdict ALLOW + reason POLICY_*/AUTH_* is the
    audit signature the flow layer renders).  Non-policy drops
    (lxcmap miss, NAT exhaustion, bandwidth, NO_SERVICE) still drop.

    ``lb_drop`` (optional [N] bool) marks LB frontend hits with no
    backend.  Unlike the two channels above this is a PRE-policy
    drop: upstream's LB lookup (bpf/lib/lb.h, bpf_sock) runs before
    the endpoint program ever judges the packet, so these rows report
    ``REASON_NO_SERVICE`` regardless of what policy (or the lxcmap
    gate) would have said, and touch no CT state.

    Each stage runs under its ``jax.named_scope``
    (``obs.trace.DEVICE_STAGES``): op metadata only, the same program."""
    from ..obs.trace import (SCOPE_CT_LOOKUP, SCOPE_CT_UPDATE,
                             SCOPE_IPCACHE, SCOPE_METRICSMAP, SCOPE_POLICY)

    hdr = hdr.astype(jnp.uint32)
    dirn = hdr[:, COL_DIR].astype(jnp.int32)
    fam = hdr[:, COL_FAMILY].astype(jnp.int32)

    # 1. ipcache: remote IP -> identity row (src for ingress, dst for
    #    egress — reference: lookup_ip4_remote_endpoint on the peer).
    with jax.named_scope(SCOPE_IPCACHE):
        src_words = hdr[:, COL_SRC_IP0:COL_SRC_IP0 + 4]
        dst_words = hdr[:, COL_DST_IP0:COL_DST_IP0 + 4]
        remote = jnp.where((dirn == 0)[:, None], src_words, dst_words)
        id_row = lpm_lookup(state.ipcache, remote, fam)

    # 2. conntrack lookup.  RELATED rows (ICMP errors carrying the
    #    embedded original tuple, core/packets.py FLAG_RELATED) probe
    #    the original flow's entry; a hit is CT_RELATED — forwarded
    #    like established traffic, never refreshed, never created.
    from ..core.packets import COL_FLAGS, FLAG_RELATED

    with jax.named_scope(SCOPE_CT_LOOKUP):
        fwd, rev = ct_keys_from_headers(hdr)
        ct_res, slot, is_reply = ct_lookup(state.ct, fwd, rev, now)
        related_hint = (hdr[:, COL_FLAGS] & FLAG_RELATED) != 0
        is_related = related_hint & (ct_res != CT_NEW)

    # 3. policy map lookup (two gathers; all precedence precompiled).
    #    ep_policy row -1 = unregistered endpoint (the lxcmap-miss
    #    sentinel): the reference DROPS when the endpoint lookup fails
    #    (bpf_lxc lxcmap miss) instead of judging under some other
    #    endpoint's policy.
    with jax.named_scope(SCOPE_POLICY):
        ep_col = hdr[:, COL_EP]  # uint32: range-check BEFORE the int32
        pol_row_raw = state.policy.ep_policy[ep_col.astype(jnp.int32)]
        # out-of-range ids would clamp onto the boundary rows in the
        # gather (>= 4096 -> 4095; >= 2^31 -> wraps negative -> 0) and be
        # judged under whatever endpoint lives there — a forged ep id must
        # be a miss, not a clamp
        no_ep = (pol_row_raw < 0) | (ep_col >= MAX_ENDPOINTS)
        pol_row = jnp.maximum(pol_row_raw, 0)
        proto_idx = state.policy.proto_table[
            hdr[:, COL_PROTO].astype(jnp.int32)]
        gcls = state.policy.port_class[
            proto_idx, hdr[:, COL_DPORT].astype(jnp.int32)]
        # global -> per-policy local class (compiler class_map): the
        # verdict tensor's class axis is sized to ONE policy's boundaries,
        # not the union of every policy's (the 17 GB failure mode)
        cls = state.policy.class_map[pol_row, gcls]
        packed = state.policy.verdict[pol_row, dirn, id_row, cls]
        p_verdict = (packed & VERDICT_MASK).astype(jnp.int32)
        p_proxy = ((packed >> PROXY_SHIFT) & PROXY_MASK).astype(jnp.int32)
        p_auth = ((packed >> AUTH_SHIFT) & 1) != 0

        # 4. final verdict: established/reply bypass policy (reference: the
        #    CT fast path — policy applies to NEW connections only).
        is_new = ct_res == CT_NEW
        ct_proxy = state.ct.table[slot, V_PROXY].astype(jnp.int32)
        allowed_new = ((p_verdict == VERDICT_ALLOW)
                       | (p_verdict == VERDICT_REDIRECT))
        # no_ep drops even ESTABLISHED traffic: the endpoint is gone/never
        # existed, so its CT fast path must not forward either
        allowed = (~is_new | allowed_new) & ~no_ep
        # mutual auth (pkg/auth): a NEW flow whose winning allow carries
        # the auth bit forwards only with a live authmap entry; otherwise
        # it drops AUTH_REQUIRED (and the agent's auth manager observes
        # the drop and handshakes).  EST flows ride the CT fast path —
        # upstream judges auth at policy time only.
        auth_exp = state.policy.auth[pol_row, id_row]
        auth_drop = allowed & is_new & p_auth & (auth_exp <= now)
        allowed = allowed & ~auth_drop
        audit_fwd = None
        if audit:
            # policy-audit-mode: would-be policy/auth denials forward
            audit_fwd = is_new & ~allowed & ~no_ep
            allowed = allowed | audit_fwd
        nat_drop = None
        if pre_drop is not None:
            nat_drop = pre_drop & allowed  # policy/no_ep drops win
            allowed = allowed & ~nat_drop
        stage_drop = None
        if pre_drop_reason is not None:
            stage_drop = (pre_drop_reason != 0) & allowed
            allowed = allowed & ~stage_drop
        proxy = jnp.where(is_new, jnp.where(p_verdict == VERDICT_REDIRECT,
                                            p_proxy, 0),
                          ct_proxy)
        # an ICMP error related to a proxied flow is forwarded, not
        # redirected (the proxy speaks the flow's L7, not ICMP)
        proxy = jnp.where(is_related, 0, proxy)
        verdict = jnp.where(
            allowed,
            jnp.where(proxy > 0, VERDICT_REDIRECT, VERDICT_ALLOW),
            jnp.where(no_ep, VERDICT_DENY, p_verdict))
        reason_allowed = (allowed if audit_fwd is None
                          else allowed & ~audit_fwd)
        reason = jnp.where(
            reason_allowed, REASON_FORWARDED,
            jnp.where(no_ep, REASON_NO_ENDPOINT,
                      jnp.where(p_verdict == VERDICT_DENY, REASON_POLICY_DENY,
                                REASON_POLICY_DEFAULT_DENY)))
        # auth_drop rows carry p_verdict == ALLOW, so the base chain
        # mislabels them — override both verdict and reason
        verdict = jnp.where(auth_drop, VERDICT_DENY, verdict)
        reason = jnp.where(auth_drop, REASON_AUTH_REQUIRED, reason)
        proxy = jnp.where(auth_drop, 0, proxy)
        if audit_fwd is not None:
            # the ACTION is forward; the reason above keeps the would-be
            # decision (rows a later NAT/bandwidth/LB stage drops get
            # their reason overridden by that stage, as they really drop)
            verdict = jnp.where(audit_fwd & allowed, VERDICT_ALLOW,
                                verdict)
        if nat_drop is not None:
            verdict = jnp.where(nat_drop, VERDICT_DENY, verdict)
            reason = jnp.where(nat_drop, REASON_NAT_EXHAUSTED, reason)
            proxy = jnp.where(nat_drop, 0, proxy)
        if stage_drop is not None:
            verdict = jnp.where(stage_drop, VERDICT_DENY, verdict)
            reason = jnp.where(stage_drop, pre_drop_reason, reason)
            proxy = jnp.where(stage_drop, 0, proxy)
        if lb_drop is not None:
            # pre-policy: wins over policy/no_ep/NAT/bandwidth reasons
            allowed = allowed & ~lb_drop
            verdict = jnp.where(lb_drop, VERDICT_DENY, verdict)
            reason = jnp.where(lb_drop, REASON_NO_SERVICE, reason)
            proxy = jnp.where(lb_drop, 0, proxy)

    # 5. conntrack create/refresh (create only on allowed NEW; related
    #    rows neither create nor refresh — the ICMP error is evidence
    #    about a flow, not flow traffic; no_ep rows touch nothing).
    with jax.named_scope(SCOPE_CT_UPDATE):
        untouched = is_related | no_ep
        if nat_drop is not None:
            untouched = untouched | nat_drop  # dropped rows refresh nothing
        if stage_drop is not None:
            untouched = untouched | stage_drop
        if lb_drop is not None:
            untouched = untouched | lb_drop
        ct = ct_update(state.ct, hdr, fwd,
                       jnp.where(untouched, CT_NEW, ct_res), slot,
                       is_reply,
                       do_create=allowed & is_new & ~related_hint,
                       proxy_port=proxy.astype(jnp.uint32),
                       now=now, valid=valid)

    # 6. metrics (reference: bpf metricsmap per-reason counters).
    with jax.named_scope(SCOPE_METRICSMAP):
        m_reason = reason if valid is None else jnp.where(valid, reason,
                                                         N_REASONS)
        metrics = state.metrics.at[m_reason, dirn].add(1, mode="drop")

    event = jnp.where(~allowed, EV_DROP,
                      jnp.where(is_new, EV_VERDICT, EV_TRACE))
    out = jnp.stack([
        verdict.astype(jnp.uint32),
        proxy.astype(jnp.uint32),
        jnp.where(is_related, CT_RELATED, ct_res).astype(jnp.uint32),
        id_row.astype(jnp.uint32),
        reason.astype(jnp.uint32),
        event.astype(jnp.uint32),
    ], axis=1)
    return out, DatapathState(policy=state.policy, ipcache=state.ipcache,
                              ct=ct, metrics=metrics)


def apply_masquerade(ct: CTTable, nat, hdr: jnp.ndarray,
                     now: jnp.ndarray) -> jnp.ndarray:
    """CONNTRACK-AWARE egress masquerade: egress-to-world sources
    rewrite to the node IP UNLESS the row's reverse CT entry exists —
    that row replies to a connection a remote originated INTO us and
    must keep its source (reference: the bpf masquerade path consults
    CT before SNAT).  Runs as its own stage before datapath_step so
    event decode sees the post-NAT rows; the CT entry of a
    masqueraded flow carries the post-NAT tuple (reverse-translation
    anchor)."""
    from ..core.packets import COL_DST_IP3, COL_SRC_IP3
    from .conntrack import _probe, ct_keys_from_headers

    hdr = hdr.astype(jnp.uint32)
    if not nat.enabled:  # static pytree aux: baked in at trace time
        return hdr
    dst = hdr[:, COL_DST_IP3]
    internal = jnp.any(
        (dst[:, None] & nat.mask[None, :]) == nat.net[None, :], axis=1)
    egress = hdr[:, COL_DIR] == 1
    v4 = hdr[:, COL_FAMILY] == 4
    _fwd, rev = ct_keys_from_headers(hdr)
    r_found, _slot = _probe(ct.table, rev, now)
    masq = egress & v4 & ~internal & ~r_found
    new_src = jnp.where(masq, nat.node_ip, hdr[:, COL_SRC_IP3])
    return hdr.at[:, COL_SRC_IP3].set(new_src)


apply_masquerade_jit = jax.jit(apply_masquerade)

datapath_step_jit = jax.jit(datapath_step, donate_argnums=0,
                            static_argnames=("audit",))


def datapath_step_packed(state: DatapathState, packed: jnp.ndarray,
                         now: jnp.ndarray, ep, dirn,
                         valid: jnp.ndarray = None,
                         audit: bool = False
                         ) -> Tuple[jnp.ndarray, DatapathState]:
    """The ingest fast path: packed IPv4 rows (16 B/packet on the h2d
    link — see core/packets.py PACKED_*) unpack on device and run the
    same fused pipeline.  ``ep``/``dirn`` are per-stream scalars, like
    the per-endpoint tc hook in the reference."""
    from ..core.packets import unpack_hdr
    from ..obs.trace import SCOPE_UNPACK

    with jax.named_scope(SCOPE_UNPACK):
        hdr = unpack_hdr(packed, ep, dirn)
    return datapath_step(state, hdr, now, valid=valid, audit=audit)


datapath_step_packed_jit = jax.jit(datapath_step_packed, donate_argnums=0,
                                   static_argnames=("audit",))


def build_state(policy_tensors: PolicyTensors, lpm_tensors: LPMTensors,
                ep_policy: np.ndarray = None,
                ct_capacity: int = 1 << 20,
                ct_shards: int = 1) -> DatapathState:
    """Assemble a fresh device state from host-compiled tensors."""
    return DatapathState.create(
        policy=DevicePolicy.from_tensors(policy_tensors, ep_policy),
        ipcache=DeviceLPM.from_tensors(lpm_tensors),
        ct=CTTable.create(ct_capacity, shards=ct_shards),
    )
