"""``rr``: the netperf TCP_RR shape.

A pool of ``flows`` distinct TCP flows from the clients to the
server; ``denied_frac`` of them (an exact count) target
``denied_port``.  ``setup_rows()`` is one SYN per flow, which opens
the allowed ones; ``reply_rows()`` is the server's SYN|ACK to each
allowed flow, which establishes it for the conntrack's TCP lifetime
however long set-up takes.  The stream is ACK data packets drawn
uniformly from the pool.
"""

import numpy as np

from traffic import (COL_DIR, COL_DPORT, COL_DST_IP3, COL_FLAGS, COL_LEN,
                     COL_SPORT, COL_SRC_IP3, TCP_ACK, TCP_SYN)


class Shape:
    def __init__(self, mix, stream, rng):
        self.mix, self.s = mix, stream
        n, f = len(stream.ips), int(mix["flows"])
        # flow i: client (i % n) permuted, source port from i // n, so
        # all flows are distinct 5-tuples
        order = rng.permutation(f)
        perm = rng.permutation(n)
        self.src = stream.ips[perm[order % n]]
        self.sport = (1024 + order // n).astype(np.uint32)
        denied = np.zeros(f, dtype=bool)
        denied[rng.permutation(f)[:round(f * mix["denied_frac"])]] = True
        self.dport = np.where(denied, mix["denied_port"],
                              mix["port"]).astype(np.uint32)

    def setup_rows(self):
        rng = np.random.default_rng(self.s.seed + [2])
        idx = rng.permutation(len(self.src))
        out = self.s.base(len(idx))
        out[:, COL_SRC_IP3] = self.src[idx]
        out[:, COL_SPORT] = self.sport[idx]
        out[:, COL_DPORT] = self.dport[idx]
        out[:, COL_FLAGS] = TCP_SYN
        out[:, COL_LEN] = rng.integers(60, 1500, len(idx), dtype=np.uint32)
        return out

    def reply_rows(self):
        rng = np.random.default_rng(self.s.seed + [5])
        ok = np.flatnonzero(self.dport == self.mix["port"])
        idx = ok[rng.permutation(len(ok))]
        out = self.s.base(len(idx))
        out[:, COL_SRC_IP3] = self.s.server_ip
        out[:, COL_DST_IP3] = self.src[idx]
        out[:, COL_SPORT] = self.dport[idx]
        out[:, COL_DPORT] = self.sport[idx]
        out[:, COL_FLAGS] = TCP_SYN | TCP_ACK
        out[:, COL_DIR] = 1
        out[:, COL_LEN] = 60
        return out

    def block(self, b, rng):
        from traffic import BLOCK

        out = self.s.base(BLOCK)
        out[:, COL_LEN] = rng.integers(60, 1500, BLOCK, dtype=np.uint32)
        f = rng.integers(0, len(self.src), BLOCK)
        out[:, COL_SRC_IP3] = self.src[f]
        out[:, COL_SPORT] = self.sport[f]
        out[:, COL_DPORT] = self.dport[f]
        out[:, COL_FLAGS] = TCP_ACK
        return out
