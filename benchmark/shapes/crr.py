"""``crr``: the netperf TCP_CRR shape, with longer connections.

``open_conns`` connection slots each carry one connection of
``packets_per_conn`` ingress packets (SYN, ACK data..., FIN|ACK) after
another, every connection a fresh 5-tuple.  Position ``p`` belongs to
slot ``perm[p % open_conns]``, so two packets of one connection are
exactly ``open_conns`` rows apart in the stream, and slots start at
staggered phases so that every cycle opens the same number of
connections.  ``denied_frac`` of the connections (by a hash of the
connection number and the seed) target ``denied_port``.
"""

import numpy as np

from traffic import (BLOCK, COL_DPORT, COL_FLAGS, COL_LEN, COL_SPORT,
                     COL_SRC_IP3, TCP_ACK, TCP_FIN, TCP_SYN, mix64)


class Shape:
    def __init__(self, mix, stream, rng):
        self.mix, self.s = mix, stream
        c, k = int(mix["open_conns"]), int(mix["packets_per_conn"])
        self.slot_perm = rng.permutation(c).astype(np.int64)
        self.phase = rng.permutation(np.arange(c) % k).astype(np.int64)
        self.conn_off = int(rng.integers(0, len(stream.ips)))
        self.deny_cut = np.uint64(int(mix["denied_frac"] * 2.0 ** 64))

    def block(self, b, rng):
        out = self.s.base(BLOCK)
        out[:, COL_LEN] = rng.integers(60, 1500, BLOCK, dtype=np.uint32)
        c = len(self.slot_perm)
        k = int(self.mix["packets_per_conn"])
        p = np.arange(b * BLOCK, (b + 1) * BLOCK, dtype=np.int64)
        slot = self.slot_perm[p % c]
        u = p // c + self.phase[slot]
        pkt, gen = u % k, u // k
        conn = gen * c + slot + self.conn_off
        n = len(self.s.ips)
        out[:, COL_SRC_IP3] = self.s.ips[conn % n]
        out[:, COL_SPORT] = (1024 + (conn // n) % 64000).astype(np.uint32)
        seed = np.uint64(self.s.seed[0] | (self.s.seed[1] << 32))
        denied = mix64(conn.astype(np.uint64) ^ seed) < self.deny_cut
        out[:, COL_DPORT] = np.where(denied, self.mix["denied_port"],
                                     self.mix["port"])
        out[:, COL_FLAGS] = np.where(
            pkt == 0, TCP_SYN,
            np.where(pkt == k - 1, TCP_FIN | TCP_ACK, TCP_ACK))
        return out
