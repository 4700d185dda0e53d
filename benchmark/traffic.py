"""The one traffic generator: reads a mix file (``mixes/<name>.json``)
and produces the packet stream from a seed.

Every row is a header row in the program's ingest format, a
``[n, 16]`` uint32 array (IPv4 addresses in the last address word).
The stream is a pure function of (mix, deployment, seed, position):
``rows(start, n)`` gives the same rows for the same arguments, so the
reference regenerates exactly what was offered from the positions the
run recorded.

What the rows are is the mix's ``shape``, a module
``shapes/<shape>.py`` found by name; when they are offered is its
``loop``, a module ``loops/<loop>.py`` (see ``harness.Generator``).
A shape module defines ``Shape(mix, stream, rng)`` with
``block(b, rng)``: the ``BLOCK`` rows of block ``b``, made from
``stream.base(BLOCK)`` and ``rng`` (seeded by the seed and ``b``).
It may define ``setup_rows()`` (offered once in set-up, before the
window) and ``reply_rows()`` (offered after them).

``warm_rows()`` are rows for warming the ladder's shapes that change
no state: SYNs to a port the mix names as denied, from tuples nothing
else uses.
"""

from __future__ import annotations

import json
import os

import numpy as np

import byname

HERE = os.path.dirname(os.path.abspath(__file__))

# the program's ingest row layout (core/packets.py N_COLS = 16)
N_COLS = 16
COL_SRC_IP3, COL_DST_IP3 = 3, 7
COL_SPORT, COL_DPORT, COL_PROTO, COL_FLAGS = 8, 9, 10, 11
COL_LEN, COL_FAMILY, COL_EP, COL_DIR = 12, 13, 14, 15
TCP_FIN, TCP_SYN, TCP_ACK = 0x01, 0x02, 0x10
BLOCK = 1 << 16


def load_mix(name: str, tiny: bool = False, path: str = None) -> dict:
    """``mixes/<name>.json``; ``tiny`` applies its ``tiny`` block."""
    path = path or os.path.join(HERE, "mixes", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    if tiny:
        mix.update(mix.get("tiny", {}))
    return mix


def seed_words(seed: int) -> list:
    seed = int(seed) % (1 << 64)
    return [seed & 0xFFFFFFFF, seed >> 32]


def mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer over uint64."""
    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


class Stream:
    """The packet stream of one mix over one deployment and seed.  The
    server is the deployment's first endpoint."""

    def __init__(self, mix: dict, dep, seed: int):
        self.mix = mix
        self.seed = seed_words(seed)
        self.ips = np.asarray(dep.client_ips, dtype=np.uint32)
        self.endpoints = dep.endpoints
        self.server_ip = int(dep.endpoints[0]["ip"])
        self.server_ep = int(dep.endpoints[0]["id"])
        self._cache = {}
        self.shape = byname.load("shapes", mix["shape"]).Shape(
            mix, self, np.random.default_rng(self.seed + [1]))

    def base(self, n: int) -> np.ndarray:
        """``n`` IPv4 TCP ingress rows to the server, all else zero."""
        out = np.zeros((n, N_COLS), dtype=np.uint32)
        out[:, COL_DST_IP3] = self.server_ip
        out[:, COL_PROTO] = 6
        out[:, COL_FAMILY] = 4
        out[:, COL_EP] = self.server_ep
        return out

    def warm_rows(self, start: int, n: int) -> np.ndarray:
        """``n`` SYNs to the denied port from tuples no other row uses
        (source ports from 50000): every one drops and none touches
        conntrack, so warm-up leaves no state that could age."""
        i = np.arange(start, start + n, dtype=np.int64)
        out = self.base(n)
        out[:, COL_SRC_IP3] = self.ips[i % len(self.ips)]
        out[:, COL_SPORT] = 50000 + (i // len(self.ips)) % 10000
        out[:, COL_DPORT] = self.mix["denied_port"]
        out[:, COL_FLAGS] = TCP_SYN
        out[:, COL_LEN] = 60
        return out

    def _once(self, name: str) -> np.ndarray:
        fn = getattr(self.shape, name, None)
        return fn() if fn else np.zeros((0, N_COLS), dtype=np.uint32)

    def setup_rows(self) -> np.ndarray:
        return self._once("setup_rows")

    def reply_rows(self) -> np.ndarray:
        return self._once("reply_rows")

    def _block(self, b: int) -> np.ndarray:
        hit = self._cache.get(b)
        if hit is not None:
            return hit
        out = self.shape.block(b, np.random.default_rng(self.seed + [3, b]))
        if len(self._cache) >= 4:
            self._cache.pop(min(self._cache))
        self._cache[b] = out
        return out

    def rows(self, start: int, n: int) -> np.ndarray:
        """Stream positions ``[start, start + n)``."""
        parts = []
        while n > 0:
            b, off = divmod(start, BLOCK)
            take = min(n, BLOCK - off)
            parts.append(self._block(b)[off:off + take])
            start += take
            n -= take
        return parts[0] if len(parts) == 1 else np.concatenate(parts)
