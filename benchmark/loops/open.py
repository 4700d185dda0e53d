"""``open``: Poisson arrivals of ``chunk_rows``-row chunks at
``rate_rows_per_s``, whatever the system does.  A chunk is offered at
its due time, or as soon after it as the generator can; its due time
is what ``submit`` is given as the arrival stamp."""

import time

import numpy as np

from traffic import seed_words


class Arrivals:
    """Due times, in seconds after the loop starts."""

    def __init__(self, mix: dict, seed: int, salt: int = 0):
        self.mean = mix["chunk_rows"] / float(mix["rate_rows_per_s"])
        self._rng = np.random.default_rng(seed_words(seed) + [4, salt])
        self._t = 0.0
        self._buf = np.zeros(0)
        self._i = 0

    def next(self) -> float:
        if self._i >= len(self._buf):
            self._buf = self._t + np.cumsum(
                self._rng.exponential(self.mean, 4096))
            self._t = float(self._buf[-1])
            self._i = 0
        self._i += 1
        return float(self._buf[self._i - 1])


def run(gen) -> None:
    arr = Arrivals(gen.mix, gen.s.seed, gen.salt)
    chunk = int(gen.mix["chunk_rows"])
    t0 = time.monotonic()
    while not gen.stopping():
        due = t0 + arr.next()
        delay = due - time.monotonic()
        if delay > 0:
            gen.idle(delay)
        gen.offer(chunk, due)
