"""Fingerprint-filtered CT probe: exactness vs the full-window probe.

The fingerprint array is a memory-traffic optimization only — every
test here asserts bit-identical semantics with the unfiltered probe,
including the adversarial cases that force the ``lax.cond`` fallbacks
(candidate overflow on lookup, expired-other-key reclaim on insert).
Reference behavior under test: ``bpf/lib/conntrack.h`` ct_lookup/
ct_create probe loop (SURVEY.md §2a:90).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from cilium_tpu.core import TCP_ACK, TCP_SYN, make_batch
from cilium_tpu.datapath import CTTable
from cilium_tpu.datapath.conntrack import (
    CT_ESTABLISHED,
    CT_NEW,
    CT_REPLY,
    KEY_WORDS,
    LIFETIME_SYN,
    N_CAND,
    N_CAND_INS,
    N_PROBE,
    ST_FREE,
    V_EXPIRES,
    V_STATE,
    _fp_mix,
    _first_k,
    _fp_mix_np,
    _fp_window,
    _hash,
    _hash_np,
    _probe,
    _probe_fp,
    _slots,
    ct_fp_from_table,
    ct_gc,
    ct_keys_jit,
    ct_live_count,
    ct_lookup_jit,
    ct_update_jit,
)


def _flows(n, seed=0):
    rng = np.random.default_rng(seed)
    rows = [dict(src=f"10.{rng.integers(0, 200)}.{i // 250}.{i % 250 + 1}",
                 dst="10.200.0.1", sport=int(rng.integers(1024, 60000)),
                 dport=443, proto=6, flags=TCP_SYN) for i in range(n)]
    return make_batch(rows)


def _seed_table(n=512, cap=1 << 12, now=100):
    ct = CTTable.create(cap)
    hdr = jnp.asarray(_flows(n).data)
    fwd, rev = ct_keys_jit(hdr)
    res, slot, rep = ct_lookup_jit(ct, fwd, rev, jnp.uint32(now))
    ct = ct_update_jit(ct, hdr, fwd, res, slot, rep,
                       do_create=jnp.ones(n, bool),
                       proxy_port=jnp.zeros(n, jnp.uint32),
                       now=jnp.uint32(now))
    return ct, hdr, fwd, rev


class TestFingerprintProbe:
    def test_fp_probe_matches_full_probe_on_hits_and_misses(self):
        ct, hdr, fwd, rev = _seed_table()
        now = jnp.uint32(101)
        for keys in (fwd, rev):
            f0, s0 = _probe(ct.table, keys, now)
            f1, s1, ovf = _probe_fp(ct.table, ct.fp, keys, now)
            assert not bool(jnp.any(ovf))
            np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
            np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))

    def test_live_slots_carry_key_fingerprint(self):
        ct, *_ = _seed_table()
        table = np.asarray(ct.table)
        fp = np.asarray(ct.fp)
        live = table[:, V_STATE] != ST_FREE
        expect = ct_fp_from_table(table)
        np.testing.assert_array_equal(fp[live], expect[live])
        assert (fp[~live] == 0).all()

    def test_candidate_overflow_falls_back_exact(self):
        # the trap: the true entry sits at window position N_CAND+1
        # while every slot's fingerprint matches the key — the first
        # N_CAND candidates are all false positives, so the filtered
        # probe alone would MISS; the overflow flag must fire and
        # ct_lookup's cond fallback must still find the entry
        from cilium_tpu.datapath.conntrack import ROW_WORDS

        cap = 64
        hdr = jnp.asarray(_flows(1).data)
        fwd, rev = ct_keys_jit(hdr)
        key = np.asarray(fwd)[0]
        pos = N_CAND + 1
        slot = int((_hash_np(key[None, :])[0] + pos) % cap)
        table = np.zeros((cap, ROW_WORDS), dtype=np.uint32)
        table[slot, :KEY_WORDS] = key
        table[slot, V_STATE] = 2  # ST_ESTABLISHED
        table[slot, V_EXPIRES] = 10_000
        key_fp = _fp_mix_np(_hash_np(key[None, :]))[0]
        ct = CTTable(table=jnp.asarray(table),
                     fp=jnp.full((cap,), key_fp, dtype=jnp.uint32),
                     dropped=jnp.zeros((), jnp.uint32))
        now = jnp.uint32(100)
        f1, s1, ovf = _probe_fp(ct.table, ct.fp, fwd, now)
        assert bool(ovf[0]) and not bool(f1[0])  # the trap is sprung...
        res, got_slot, rep = ct_lookup_jit(ct, fwd, rev, now)
        assert int(res[0]) == CT_ESTABLISHED  # ...and the cond saves it
        assert int(got_slot[0]) == slot

    def test_insert_reclaims_expired_other_key_slots(self):
        # fill a single-window table with flows, expire them all, and
        # insert fresh keys WITHOUT a GC sweep: the fingerprint filter
        # can't see expired-other-key slots, so the claim must ride the
        # full-loop fallback — old probe semantics (expired slots are
        # immediately claimable) preserved
        cap = 16  # one probe window == the whole table
        ct = CTTable.create(cap)
        old = jnp.asarray(_flows(8, seed=1).data)
        fwd, rev = ct_keys_jit(old)
        now = jnp.uint32(100)
        res, slot, rep = ct_lookup_jit(ct, fwd, rev, now)
        ct = ct_update_jit(ct, old, fwd, res, slot, rep,
                           do_create=jnp.ones(8, bool),
                           proxy_port=jnp.zeros(8, jnp.uint32), now=now)
        n_old = ct_live_count(ct)
        assert n_old > 0
        later = jnp.uint32(100 + LIFETIME_SYN + 1)  # all expired, unswept
        assert int(np.asarray(ct.fp != 0).sum()) == n_old  # stale fps
        new = jnp.asarray(_flows(4, seed=2).data)
        nfwd, nrev = ct_keys_jit(new)
        res, slot, rep = ct_lookup_jit(ct, nfwd, nrev, later)
        assert (np.asarray(res) == CT_NEW).all()
        ct = ct_update_jit(ct, new, nfwd, res, slot, rep,
                           do_create=jnp.ones(4, bool),
                           proxy_port=jnp.zeros(4, jnp.uint32), now=later)
        assert int(np.asarray(ct.dropped)) == 0
        res2, _s, _r = ct_lookup_jit(ct, nfwd, nrev, later)
        assert (np.asarray(res2) == CT_ESTABLISHED).all()
        # reclaimed slots' fingerprints now belong to the new keys
        table = np.asarray(ct.table)
        live = table[:, V_STATE] != ST_FREE
        np.testing.assert_array_equal(
            np.asarray(ct.fp)[live], ct_fp_from_table(table)[live])

    def test_gc_clears_fingerprints(self):
        ct, hdr, fwd, rev = _seed_table(n=64, cap=1 << 10)
        later = jnp.uint32(100 + LIFETIME_SYN + 1)
        ct2, n = ct_gc(ct, later)
        assert int(np.asarray(n)) > 0
        fp = np.asarray(ct2.fp)
        state = np.asarray(ct2.table[:, V_STATE])
        assert (fp[state == ST_FREE] == 0).all()

    def test_probe_equivalence_fuzz_through_lifecycle(self):
        """Randomized gate: across batches of inserts, refreshes,
        expiries, and GC sweeps, the fingerprint probe must equal the
        full-window probe on EVERY key, hit or miss."""
        # small: forces collisions + window pressure, ~60% occupancy
        # at peak
        _lifecycle_fuzz(cap=1 << 10, n_flows=600, batch=128)

    def test_probe_equivalence_fuzz_wrapping_windows(self):
        # two windows' worth of slots: half the home slots lie in the
        # last 15, so their windows wrap past the table's end
        _lifecycle_fuzz(cap=2 * N_PROBE, n_flows=40, batch=16)

    def test_host_fp_mix_mirrors_device(self):
        keys = np.asarray(_seed_table(n=32)[2])
        h_dev = np.asarray(_fp_mix(_hash(jnp.asarray(keys))))
        h_np = _fp_mix_np(_hash_np(keys))
        np.testing.assert_array_equal(h_dev, h_np)
        assert h_np.min() >= 1 and h_np.max() <= 255


def _lifecycle_fuzz(cap, n_flows, batch):
    rng = np.random.default_rng(42)
    ct = CTTable.create(cap)
    now = 100
    universe = _flows(n_flows, seed=7)
    afwd, arev = ct_keys_jit(jnp.asarray(universe.data))
    for step in range(12):
        pick = rng.choice(n_flows, batch, replace=False)
        hdr = jnp.asarray(universe.data[pick])
        fwd, rev = ct_keys_jit(hdr)
        t = jnp.uint32(now)
        res, slot, rep = ct_lookup_jit(ct, fwd, rev, t)
        ct = ct_update_jit(ct, hdr, fwd, res, slot, rep,
                           do_create=jnp.ones(batch, bool),
                           proxy_port=jnp.zeros(batch, jnp.uint32),
                           now=t)
        table = np.asarray(ct.table)
        live = table[:, V_STATE] != ST_FREE
        np.testing.assert_array_equal(np.asarray(ct.fp)[live],
                                      ct_fp_from_table(table)[live])
        # equivalence sweep over the WHOLE universe
        for keys in (afwd, arev):
            f0, s0 = _probe(ct.table, keys, t)
            f1, s1, ovf = _probe_fp(ct.table, ct.fp, keys, t)
            # where no overflow, results must match exactly
            clean = ~np.asarray(ovf)
            np.testing.assert_array_equal(np.asarray(f0)[clean],
                                          np.asarray(f1)[clean])
            np.testing.assert_array_equal(
                np.asarray(s0)[clean & np.asarray(f0)],
                np.asarray(s1)[clean & np.asarray(f0)])
        now += rng.integers(1, 40)  # let lifetimes expire mid-run
        if step % 4 == 3:
            ct, _n = ct_gc(ct, jnp.uint32(now))


def _hashes(cap, n, rng):
    """``n`` random key hashes, a quarter of them homed in the table's
    last 15 slots (their windows wrap past the end)."""
    h = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    tail = n // 4
    h[:tail] = ((h[:tail] & np.uint32(~(cap - 1) & 0xFFFFFFFF))
                | np.uint32(cap - 1)
                - rng.integers(0, min(15, cap), tail).astype(np.uint32))
    return h


def _window_slots(h, cap):
    steps = np.arange(N_PROBE, dtype=np.uint64)
    return ((h[:, None].astype(np.uint64) + steps)
            & np.uint64(cap - 1)).astype(np.int32)


@pytest.mark.parametrize("cap", [8, 16, 32, 1 << 10, 1 << 19])
def test_block_window_equals_per_slot_window(cap):
    """The block-gathered window is ``fp[slots]`` bit for bit, at the
    table's end too; below one block the window gathers per slot."""
    rng = np.random.default_rng(cap)
    fp = rng.integers(0, 256, cap, dtype=np.uint32)
    h = _hashes(cap, 4096, rng)
    got = np.asarray(_fp_window(jnp.asarray(fp), jnp.asarray(h)))
    np.testing.assert_array_equal(got, fp[_window_slots(h, cap)])


@pytest.mark.parametrize("cap", [16, 32, 1 << 10, 1 << 19])
def test_candidate_slots_by_arithmetic(cap):
    """``(h + pos) & mask`` is the slot the window held at ``pos``, for
    the positions ``_first_k`` picks, as a block and per column."""
    rng = np.random.default_rng(cap + 1)
    h = _hashes(cap, 4096, rng)
    slots = _window_slots(h, cap)
    cand = rng.random((len(h), N_PROBE)) < 0.3
    for k in (N_CAND, N_CAND_INS):
        pos, _valid = _first_k(jnp.asarray(cand), k)
        want = np.take_along_axis(slots, np.asarray(pos), axis=1)
        got = _slots(jnp.asarray(h), pos, cap - 1)
        np.testing.assert_array_equal(np.asarray(got), want)
        for j in range(k):
            np.testing.assert_array_equal(
                np.asarray(_slots(jnp.asarray(h), pos[:, j], cap - 1)),
                want[:, j])
