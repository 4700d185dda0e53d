"""Connection tracking: fixed-capacity open-addressing hash in HBM.

Reference: upstream cilium ``bpf/lib/conntrack.h`` (``ct_lookup4/6``,
``ct_create4/6``, TCP state handling, per-proto lifetimes) and
``pkg/maps/ctmap`` (GC).  TPU-first redesign: the kernel's per-packet
hash probe becomes a **batched** probe — every packet in the header
tensor probes concurrently via gathers; inserts use a vectorized
write-then-verify claim (scatter the whole row, re-gather the key,
check who won) instead of a CAS loop, giving lock-free semantics
across the batch.  Key and value words live in ONE row of one table so
an insert is a single scatter — no torn entries between concurrent
claimants of the same slot.

Static shapes: capacity is fixed at construction (power of two); a full
probe window drops new inserts (counted, like the reference's CT map
pressure) rather than reallocating.  Aging is a vectorized sweep
(``ctmap.GC``); expired entries are lookup misses immediately and their
slots are reclaimable by inserts.

Known deliberate divergences from eBPF (documented for the divergence
suite): duplicate tuples in one batch collapse to one entry with
last-writer counters (the kernel, processing serially, would count
both; the accounting delta is bounded by batch size and reconciled at
the flow layer).  Per-flow tx/rx packet and byte counters are uint32
table words and WRAP at 2^32 (the reference ctmap uses u64) — a
deliberate trade: one uint32 row keeps insert a single scatter; flows
past 4 GiB show wrapped accounting in ``bpf ct list`` (the flow layer
aggregates per-batch deltas host-side in uint64 and is unaffected).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.packets import (
    COL_DPORT,
    COL_DST_IP0,
    COL_FLAGS,
    COL_LEN,
    COL_PROTO,
    COL_SPORT,
    COL_SRC_IP0,
    TCP_FIN,
    TCP_RST,
)

# Lookup results (reference: bpf/lib/common.h CT_* codes).
CT_NEW = 0
CT_ESTABLISHED = 1
CT_REPLY = 2
CT_RELATED = 3

# Entry states stored in the table.
ST_FREE = 0
ST_SYN_SENT = 1  # open, no reply seen yet
ST_ESTABLISHED = 2
ST_CLOSING = 3  # FIN/RST seen

# Lifetimes in seconds (reference: bpf CT_CONNECTION_LIFETIME_TCP/
# NONTCP, CT_SYN_TIMEOUT, CT_CLOSE_TIMEOUT defaults).
LIFETIME_TCP = 21600
LIFETIME_NONTCP = 60
LIFETIME_SYN = 60
LIFETIME_CLOSE = 10

KEY_WORDS = 10  # src[4] dst[4] ports proto
N_PROBE = 16  # linear probe window
N_CAND = 4  # full rows fetched per fingerprint-filtered probe
N_CAND_INS = 4  # claim attempts against fingerprint-filtered slots

# value columns (offsets within the combined row, after the key words)
V_STATE = KEY_WORDS + 0
V_EXPIRES = KEY_WORDS + 1
V_TX_PKTS = KEY_WORDS + 2
V_RX_PKTS = KEY_WORDS + 3
V_TX_BYTES = KEY_WORDS + 4
V_RX_BYTES = KEY_WORDS + 5
V_PROXY = KEY_WORDS + 6  # proxy redirect port (reference: proxy_redirect)
ROW_WORDS = KEY_WORDS + 7


@jax.tree_util.register_pytree_node_class
@dataclass
class CTTable:
    """Device CT state (a pytree threading functionally through jit).

    ``fp`` is a per-slot 1-byte key fingerprint (0 = free slot) kept in
    its own HBM array: probes gather the 16-slot fingerprint window
    first (two aligned 64 B blocks per key, :func:`_fp_window`) and
    fetch full 68 B rows only for the few fingerprint-matching
    candidates — a ~3x probe-byte diet over loading the whole
    [N, 16, ROW_WORDS] window.  The fingerprint is a pure function of
    the stored key (``_fp_mix`` of the slot hash), so
    snapshots stay placement-free and restores recompute it."""

    table: jnp.ndarray  # [C, ROW_WORDS] uint32
    fp: jnp.ndarray  # [C] uint32 — key fingerprint per slot, 0 = free
    dropped: jnp.ndarray  # [] uint32 — failed inserts (map pressure)

    @staticmethod
    def create(capacity: int = 1 << 20, shards: int = 1) -> "CTTable":
        """``capacity`` is the GLOBAL entry count; when the table is
        sharded over ``shards`` chips each shard's slice must be a
        power of two (the probe mask is per-shard)."""
        per_shard, rem = divmod(capacity, shards)
        assert rem == 0, "capacity must divide evenly across shards"
        assert per_shard & (per_shard - 1) == 0, \
            "per-shard capacity must be 2^k"
        return CTTable(
            table=jnp.zeros((capacity, ROW_WORDS), dtype=jnp.uint32),
            fp=jnp.zeros((capacity,), dtype=jnp.uint32),
            dropped=jnp.zeros((), dtype=jnp.uint32),
        )

    @property
    def capacity(self) -> int:
        return self.table.shape[0]

    def tree_flatten(self):
        return ((self.table, self.fp, self.dropped), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def ct_keys_from_headers(hdr: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Header tensor [N, N_COLS] -> (forward, reverse) CT key tensors.

    The key carries the hook direction like the reference's
    ``TUPLE_F_OUT``/``TUPLE_F_IN`` (word 9 = proto | dir << 8), so an
    egress-created entry never satisfies an ingress lookup of the same
    5-tuple on another endpoint.  The reverse (reply) key flips both
    the tuple AND the direction bit — a reply to an ingress-created
    flow is seen at the egress hook (reference:
    ``ipv4_ct_tuple_reverse``).  ICMP zeroes the port word so echo
    request/reply share a tuple modulo the swap.
    """
    from ..core.packets import COL_DIR, FLAG_RELATED, normalize_ports

    src = hdr[:, COL_SRC_IP0:COL_SRC_IP0 + 4].astype(jnp.uint32)
    dst = hdr[:, COL_DST_IP0:COL_DST_IP0 + 4].astype(jnp.uint32)
    proto = hdr[:, COL_PROTO].astype(jnp.uint32)
    dirn = hdr[:, COL_DIR].astype(jnp.uint32)
    sport, dport = normalize_ports(jnp, proto, hdr[:, COL_SPORT],
                                   hdr[:, COL_DPORT])
    sport = sport.astype(jnp.uint32)
    dport = dport.astype(jnp.uint32)
    fwd_ports = (sport << 16) | dport
    rev_ports = (dport << 16) | sport
    fwd_pd = proto | (dirn << 8)
    rev_pd = proto | ((1 - dirn) << 8)
    fwd = jnp.concatenate(
        [src, dst, fwd_ports[:, None], fwd_pd[:, None]], axis=1)
    rev = jnp.concatenate(
        [dst, src, rev_ports[:, None], rev_pd[:, None]], axis=1)
    # RELATED rows (ICMP errors) carry the EMBEDDED original tuple; the
    # entry to relate to was created with that SAME tuple under either
    # hook direction, so the "reverse" probe flips only the direction
    # bit instead of swapping the tuple (reference: the kernel looks up
    # the inner tuple for icmp errors)
    related = ((hdr[:, COL_FLAGS] & FLAG_RELATED) != 0)[:, None]
    rev_rel = jnp.concatenate(
        [src, dst, fwd_ports[:, None], rev_pd[:, None]], axis=1)
    rev = jnp.where(related, rev_rel, rev)
    return fwd, rev


def _hash(keys: jnp.ndarray) -> jnp.ndarray:
    """FNV-1a over the key words + murmur3 finalizer:
    [N, KEY_WORDS] uint32 -> [N] uint32.

    The finalizer is load-bearing: word-FNV's low product bits depend
    ONLY on low input bits (low16(h*p) = low16(low16(h)*low16(p))), and
    the ports word packs sport into the HIGH half — without avalanche,
    home slots collapse to |srcs|*|dports| distinct values and probe
    windows chain to overflow at a few percent occupancy."""
    h = jnp.full(keys.shape[0], 0x811C9DC5, dtype=jnp.uint32)
    for w in range(KEY_WORDS):
        h = (h ^ keys[:, w]) * jnp.uint32(0x01000193)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _fp_mix(h):
    """Key hash -> fingerprint byte in 1..255 (0 is the free marker).

    The slot index consumes the LOW bits of ``h``, so the fingerprint
    runs the murmur3 finalizer over it and takes the TOP byte — within
    one probe window (slots that differ only in low bits) fingerprints
    of distinct keys are ~independent, giving a 1/255 false-candidate
    rate per live slot."""
    g = h ^ (h >> 16)
    g = g * jnp.uint32(0x85EBCA6B)
    g = g ^ (g >> 13)
    g = g * jnp.uint32(0xC2B2AE35)
    return (g >> 24) % jnp.uint32(255) + jnp.uint32(1)


def _probe(table: jnp.ndarray, keys: jnp.ndarray, now: jnp.ndarray
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Probe the window for each key: -> (found [N] bool, slot [N] i32).

    Expired entries don't match (an expired entry is a miss; GC frees
    the slot later, and inserts may reclaim it immediately).

    The whole window loads as ONE [N, N_PROBE, ROW_WORDS] gather
    (instead of N_PROBE dependent gathers) so the memory system
    pipelines the probe; first-match selection is an argmax over the
    window axis."""
    c = table.shape[0]
    if c & (c - 1):
        raise ValueError(
            f"CT probe needs 2^k capacity, got {c} — a multi-shard "
            "table must be probed inside shard_map (per-shard slice)")
    h = _hash(keys)
    slots = _slots(h, jnp.arange(N_PROBE)[None, :], c - 1)
    rows = table[slots]  # [N, N_PROBE, ROW_WORDS] — one gather
    live = (rows[:, :, V_STATE] != ST_FREE) & (rows[:, :, V_EXPIRES]
                                               >= now)
    match = live & jnp.all(rows[:, :, :KEY_WORDS]
                           == keys[:, None, :], axis=2)  # [N, N_PROBE]
    found = jnp.any(match, axis=1)
    first = jnp.argmax(match, axis=1)  # first True (0 when none)
    slot = jnp.take_along_axis(slots, first[:, None], axis=1)[:, 0]
    return found, jnp.where(found, slot, 0).astype(jnp.int32)


def _slots(h: jnp.ndarray, pos: jnp.ndarray, mask: int) -> jnp.ndarray:
    """Window positions -> table slots: ``(h + pos) & mask`` as int32.

    ``h`` is [N] and ``pos`` a position, [N] or [N, k]: plain
    arithmetic, so a candidate's slot costs no gather."""
    pos = jnp.asarray(pos).astype(jnp.uint32)
    if pos.ndim == 2:
        h = h[:, None]
    return ((h + pos) & mask).astype(jnp.int32)


def _fp_window(fp: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """Each key's fingerprint window: [N] key hashes -> [N, N_PROBE],
    position j holding ``fp[(h + j) & mask]``.

    On the TPU a gather costs about the same per index whatever the
    bytes per index, so the window is fetched as whole aligned blocks:
    ``fp`` viewed as [C / N_PROBE, N_PROBE], the home slot's block and
    the next one (wrapping at the table's end) in ONE gather of 2 rows
    per key, then shifted to the home slot by a barrel shift of static
    rolls, one per bit of ``h & (N_PROBE - 1)``.  A per-key
    ``dynamic_slice`` would lower to a serial ``while`` on the TPU.  A
    table smaller than one block gathers per slot."""
    c = fp.shape[0]
    mask = c - 1
    if c < N_PROBE:
        return fp[_slots(h, jnp.arange(N_PROBE)[None, :], mask)]
    n_blocks = c // N_PROBE
    block = (h & mask) // N_PROBE
    blocks = jnp.stack([block, (block + 1) % n_blocks], axis=1)
    win = fp.reshape(n_blocks, N_PROBE)[blocks.astype(jnp.int32)]
    win = win.reshape(-1, 2 * N_PROBE)
    off = h % N_PROBE
    bit = N_PROBE // 2
    while bit:
        win = jnp.where(((off & bit) != 0)[:, None],
                        jnp.roll(win, -bit, axis=1), win)
        bit //= 2
    return win[:, :N_PROBE]


def _first_k(mask: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """First ``k`` True positions per row of [N, N_PROBE] ``mask`` in
    window order: -> (positions [N, k] int32, valid [N, k] bool)."""
    steps = jnp.arange(N_PROBE, dtype=jnp.int32)
    rank = jnp.where(mask, steps[None, :], N_PROBE)
    order = jnp.sort(rank, axis=1)[:, :k]
    return jnp.minimum(order, N_PROBE - 1), order < N_PROBE


def _probe_fp(table: jnp.ndarray, fp: jnp.ndarray, keys: jnp.ndarray,
              now: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fingerprint-filtered probe: -> (found, slot, overflow).

    Gathers the 16-slot fingerprint window (:func:`_fp_window`), then
    full rows for only the first ``N_CAND`` fingerprint matches.  Exactness:
    a miss with more than ``N_CAND`` fingerprint matches in the window
    is flagged ``overflow`` — the true entry could hide past the
    candidate budget (P ~ (occupancy/255)^N_CAND per probe), and the
    caller reruns the full-window probe under ``lax.cond``.  Stale
    fingerprints of expired-but-unswept entries only cost a candidate
    slot; the liveness check on the gathered row rejects them."""
    h = _hash(keys)
    fmatch = _fp_window(fp, h) == _fp_mix(h)[:, None]  # [N, N_PROBE]
    pos, cand_valid = _first_k(fmatch, N_CAND)
    cand_slots = _slots(h, pos, fp.shape[0] - 1)  # [N, N_CAND]
    rows = table[cand_slots]  # [N, N_CAND, ROW_WORDS]
    live = (rows[:, :, V_STATE] != ST_FREE) & (rows[:, :, V_EXPIRES]
                                               >= now)
    match = cand_valid & live & jnp.all(
        rows[:, :, :KEY_WORDS] == keys[:, None, :], axis=2)
    found = jnp.any(match, axis=1)
    first = jnp.argmax(match, axis=1)
    slot = jnp.take_along_axis(cand_slots, first[:, None], axis=1)[:, 0]
    overflow = ~found & (jnp.sum(fmatch, axis=1) > N_CAND)
    return found, jnp.where(found, slot, 0).astype(jnp.int32), overflow


def ct_lookup(ct: CTTable, fwd: jnp.ndarray, rev: jnp.ndarray,
              now: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched ``ct_lookup4`` equivalent.

    Returns (result [N] int32 in CT_*, slot [N] int32, is_reply [N]
    bool).  ``slot`` is valid only where result != CT_NEW.

    Fast path: fingerprint-filtered probes (:func:`_probe_fp`).  If
    ANY packet's fingerprint candidates overflowed without a match,
    the whole batch reruns the exact full-window probe — semantics are
    bit-identical to the unfiltered probe, the filter is purely a
    memory-traffic optimization.
    """
    f_found, f_slot, f_ovf = _probe_fp(ct.table, ct.fp, fwd, now)
    r_found, r_slot, r_ovf = _probe_fp(ct.table, ct.fp, rev, now)

    def _full(_):
        ff, fs = _probe(ct.table, fwd, now)
        rf, rs = _probe(ct.table, rev, now)
        return ff, fs, rf, rs

    def _fast(_):
        return f_found, f_slot, r_found, r_slot

    f_found, f_slot, r_found, r_slot = jax.lax.cond(
        jnp.any(f_ovf | r_ovf), _full, _fast, None)
    is_reply = ~f_found & r_found
    slot = jnp.where(f_found, f_slot, r_slot)
    result = jnp.where(f_found, CT_ESTABLISHED,
                       jnp.where(is_reply, CT_REPLY, CT_NEW))
    return result.astype(jnp.int32), slot, is_reply


def ct_update(ct: CTTable, hdr: jnp.ndarray, fwd: jnp.ndarray,
              result: jnp.ndarray, slot: jnp.ndarray,
              is_reply: jnp.ndarray, do_create: jnp.ndarray,
              proxy_port: jnp.ndarray, now: jnp.ndarray,
              valid: jnp.ndarray = None) -> CTTable:
    """Refresh hit entries, apply the TCP state machine, insert NEW.

    ``do_create`` marks NEW packets whose policy verdict allowed them
    (reference: ``ct_create4`` is called on the allow path only).
    ``valid`` masks out padding rows (batch routing pads shards to a
    common size); invalid rows touch nothing.
    """
    proto = hdr[:, COL_PROTO].astype(jnp.uint32)
    flags = hdr[:, COL_FLAGS].astype(jnp.uint32)
    length = hdr[:, COL_LEN].astype(jnp.uint32)
    is_tcp = proto == 6
    closing = is_tcp & ((flags & (TCP_FIN | TCP_RST)) != 0)

    table = ct.table
    capacity = ct.capacity

    # --- refresh existing entries (hits) -------------------------------
    # State transitions are MONOTONE upgrades (SYN_SENT < ESTABLISHED <
    # CLOSING, no downgrades), so concurrent refreshes of one slot by
    # several packets of the same flow in one batch combine with
    # scatter-max — matching the oracle's sequential result regardless
    # of intra-batch order.  Expiry is then recomputed from the POST-max
    # state so the lifetime matches the winning state.
    hit = result != CT_NEW
    if valid is not None:
        hit = hit & valid
    hslot = jnp.where(hit, slot, 0)
    old_state = table[hslot, V_STATE]
    # reply seen -> ESTABLISHED; FIN/RST -> CLOSING
    new_state = jnp.where(is_reply & (old_state == ST_SYN_SENT),
                          ST_ESTABLISHED, old_state)
    new_state = jnp.where(closing, ST_CLOSING, new_state)
    upd_rows = jnp.where(hit, hslot, capacity)  # OOB rows dropped
    table = table.at[upd_rows, V_STATE].max(
        new_state.astype(jnp.uint32), mode="drop")
    final_state = table[hslot, V_STATE]
    lifetime = jnp.where(
        final_state == ST_CLOSING, LIFETIME_CLOSE,
        jnp.where(is_tcp,
                  jnp.where(final_state >= ST_ESTABLISHED, LIFETIME_TCP,
                            LIFETIME_SYN),
                  LIFETIME_NONTCP)).astype(jnp.uint32)
    table = table.at[upd_rows, V_EXPIRES].set(now + lifetime, mode="drop")
    pkt_col = jnp.where(is_reply, V_RX_PKTS, V_TX_PKTS)
    byte_col = jnp.where(is_reply, V_RX_BYTES, V_TX_BYTES)
    table = table.at[upd_rows, pkt_col].add(1, mode="drop")
    table = table.at[upd_rows, byte_col].add(length, mode="drop")

    # --- insert NEW entries (write-then-verify claim) ------------------
    pending = do_create & (result == CT_NEW)
    if valid is not None:
        pending = pending & valid
    init_state = jnp.where(is_tcp, ST_SYN_SENT, ST_ESTABLISHED)
    init_life = jnp.where(is_tcp, LIFETIME_SYN, LIFETIME_NONTCP)
    new_row = jnp.concatenate([
        fwd,
        jnp.stack([
            init_state.astype(jnp.uint32),
            now + init_life.astype(jnp.uint32),
            jnp.ones_like(length),  # tx_pkts
            jnp.zeros_like(length),
            length,  # tx_bytes
            jnp.zeros_like(length),
            proxy_port.astype(jnp.uint32),
        ], axis=1),
    ], axis=1)  # [N, ROW_WORDS]

    fp = ct.fp
    mask = capacity - 1
    h = _hash(fwd)
    win_fp = _fp_window(fp, h)
    key_fp = _fp_mix(h)

    def _claim(table, fp, pending, s, also_try=None):
        stored = table[s]
        claimable = ((stored[:, V_STATE] == ST_FREE)
                     | (stored[:, V_EXPIRES] < now)
                     | jnp.all(stored[:, :KEY_WORDS] == fwd, axis=1))
        trying = pending & claimable
        if also_try is not None:
            trying = trying & also_try
        rows = jnp.where(trying, s, capacity)
        table = table.at[rows].set(new_row, mode="drop")
        # gather whole rows, then slice: a partial-row gather
        # (table[s, :KEY_WORDS]) lowers to a serial loop on the TPU
        won = trying & jnp.all(table[s][:, :KEY_WORDS] == fwd, axis=1)
        fp = fp.at[jnp.where(won, s, capacity)].set(key_fp, mode="drop")
        return table, fp, pending & ~won

    # fast path: claim among fingerprint-filtered candidates only —
    # free slots (fp 0) and same-fingerprint slots (own key re-claim,
    # expired twins).  Probe-byte diet: N_CAND_INS row gathers instead
    # of N_PROBE.
    cand_mask = (win_fp == 0) | (win_fp == key_fp[:, None])
    pos, cand_valid = _first_k(cand_mask, N_CAND_INS)
    for k in range(N_CAND_INS):
        table, fp, pending = _claim(table, fp, pending,
                                    _slots(h, pos[:, k], mask),
                                    cand_valid[:, k])

    # exact fallback: a still-pending insert might claim an
    # expired-other-key slot the fingerprint can't identify, or lost
    # every candidate to same-window racers — rerun the full-window
    # loop for the batch (rare: needs >= N_CAND_INS contenders or an
    # exhausted window, so steady state never pays it)
    def _full(args):
        table, fp, pending = args
        for step in range(N_PROBE):
            table, fp, pending = _claim(table, fp, pending,
                                        _slots(h, step, mask))
        return table, fp, pending

    table, fp, pending = jax.lax.cond(
        jnp.any(pending), _full, lambda a: a, (table, fp, pending))

    dropped = ct.dropped + jnp.sum(pending).astype(jnp.uint32)
    return CTTable(table=table, fp=fp, dropped=dropped)


def ct_gc(ct: CTTable, now: jnp.ndarray) -> Tuple[CTTable, jnp.ndarray]:
    """Age out expired entries (reference: pkg/maps/ctmap.GC interval
    sweep).  Returns (table, n_evicted)."""
    live = ct.table[:, V_STATE] != ST_FREE
    expired = live & (ct.table[:, V_EXPIRES] < now)
    n = jnp.sum(expired).astype(jnp.uint32)
    state = jnp.where(expired, ST_FREE, ct.table[:, V_STATE])
    table = ct.table.at[:, V_STATE].set(state.astype(jnp.uint32))
    fp = jnp.where(expired, jnp.uint32(0), ct.fp)
    return CTTable(table=table, fp=fp, dropped=ct.dropped), n


@partial(jax.jit, donate_argnums=0)
def ct_gc_jit(ct: CTTable, now: jnp.ndarray) -> Tuple[CTTable, jnp.ndarray]:
    return ct_gc(ct, now)


# Jitted entry points: each eager scatter/gather costs a separate XLA
# compile, so callers outside the fused datapath_step use these.
ct_lookup_jit = jax.jit(ct_lookup)
ct_update_jit = jax.jit(ct_update, donate_argnums=0)
ct_keys_jit = jax.jit(ct_keys_from_headers)


def ct_live_count(ct: CTTable) -> int:
    return int(np.asarray(jnp.sum(ct.table[:, V_STATE] != ST_FREE)))


_STATE_NAMES = {ST_SYN_SENT: "SYN_SENT", ST_ESTABLISHED: "ESTABLISHED",
                ST_CLOSING: "CLOSING"}


def _hash_np(keys: np.ndarray) -> np.ndarray:
    """Host-side hash identical to :func:`_hash` (for re-placement)."""
    keys = keys.astype(np.uint32)
    h = np.full(keys.shape[0], 0x811C9DC5, dtype=np.uint32)
    with np.errstate(over="ignore"):
        for w in range(KEY_WORDS):
            h = (h ^ keys[:, w]) * np.uint32(0x01000193)
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(0x85EBCA6B)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def _fp_mix_np(h: np.ndarray) -> np.ndarray:
    """Host mirror of :func:`_fp_mix`."""
    with np.errstate(over="ignore"):
        g = h ^ (h >> np.uint32(16))
        g = g * np.uint32(0x85EBCA6B)
        g = g ^ (g >> np.uint32(13))
        g = g * np.uint32(0xC2B2AE35)
    return (g >> np.uint32(24)) % np.uint32(255) + np.uint32(1)


def ct_fp_from_table(table: np.ndarray) -> np.ndarray:
    """Recompute the per-slot fingerprint array from a placed table.

    The fingerprint is derived state (a pure function of each live
    slot's key), so restores rebuild it instead of persisting it."""
    table = np.asarray(table, dtype=np.uint32)
    fp = np.zeros(table.shape[0], dtype=np.uint32)
    live = table[:, V_STATE] != ST_FREE
    if live.any():
        fp[live] = _fp_mix_np(_hash_np(table[live, :KEY_WORDS]))
    return fp


def ct_rows_from_table(table: np.ndarray) -> np.ndarray:
    """Live rows of a (hashed) CT table -> dense [n, ROW_WORDS] array.

    The dense form is the portable snapshot format: it carries no slot
    placement, so it can be restored into a table of ANY capacity (or
    into the interpreter backend's dict)."""
    table = np.asarray(table)
    return table[table[:, V_STATE] != ST_FREE].copy()


def ct_table_from_rows(rows: np.ndarray,
                       capacity: int) -> Tuple[np.ndarray, int]:
    """Rebuild a hashed CT table from dense snapshot rows.

    Re-places every entry with the same FNV hash + linear probe the
    device uses, so a snapshot taken at one capacity (or from the
    interpreter oracle) restores correctly into another.  Returns
    ``(table, n_dropped)``: entries that cannot be placed within the
    probe window are dropped and counted — seed ``CTTable.dropped``
    with the count so restore-time map pressure shows in metrics like
    live-insert pressure does."""
    assert capacity & (capacity - 1) == 0, "capacity must be 2^k"
    table = np.zeros((capacity, ROW_WORDS), dtype=np.uint32)
    rows = np.asarray(rows, dtype=np.uint32)
    if rows.size == 0:
        return table, 0
    mask = np.uint32(capacity - 1)
    hs = _hash_np(rows[:, :KEY_WORDS])
    # vectorized placement: per probe step, every still-pending row
    # bids for its slot; the first bidder (original row order) of each
    # free slot wins — restart restores of ~1M flows stay sub-second
    pending = np.arange(len(rows))
    for step in range(N_PROBE):
        if not len(pending):
            break
        slots = (hs[pending] + np.uint32(step)) & mask
        free = table[slots, V_STATE] == ST_FREE
        order = np.argsort(slots, kind="stable")
        s_sorted = slots[order]
        first = np.ones(len(order), dtype=bool)
        first[1:] = s_sorted[1:] != s_sorted[:-1]
        win = np.zeros(len(pending), dtype=bool)
        win[order] = first
        place = free & win
        table[slots[place]] = rows[pending[place]]
        pending = pending[~place]
    return table, len(pending)


def ct_entries_from_snapshot(table: np.ndarray,
                             limit: int = 1000) -> list:
    """Decode live CT rows for display (`cilium bpf ct list`)."""
    from ..core.packets import words_to_ip

    table = np.asarray(table)
    live = np.nonzero(table[:, V_STATE] != ST_FREE)[0][:limit]
    out = []
    for i in live:
        row = table[i]
        proto = int(row[9]) & 0xFF
        dirn = (int(row[9]) >> 8) & 1
        fam = 4 if not row[0:3].any() else 6
        out.append({
            "src": words_to_ip(row[0:4], fam),
            "dst": words_to_ip(row[4:8], fam),
            "sport": int(row[8]) >> 16,
            "dport": int(row[8]) & 0xFFFF,
            "proto": proto,
            "dir": "ingress" if dirn == 0 else "egress",
            "state": _STATE_NAMES.get(int(row[V_STATE]),
                                      str(int(row[V_STATE]))),
            "expires": int(row[V_EXPIRES]),
            "tx_packets": int(row[V_TX_PKTS]),
            "rx_packets": int(row[V_RX_PKTS]),
            "tx_bytes": int(row[V_TX_BYTES]),
            "rx_bytes": int(row[V_RX_BYTES]),
            "proxy_port": int(row[V_PROXY]),
        })
    return out
