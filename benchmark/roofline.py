"""Peak table lookup and the bytes a verdict must touch.

The byte count follows from the verdict's semantics, not from how the
program implements them, so a rewrite of the conntrack probe cannot
make it stale.  Per row the verdict must at least:

- read the header row as shipped to the device: 64 B (16 words);
- read the source's ipcache entry: 8 B (a /32 key and its identity);
- read one policy verdict word: 4 B;
- read the row's conntrack entry: 68 B (a 40 B key, 28 B of state,
  expiry, counters and proxy port);
- write that entry back: 28 B of value words on a hit, the whole
  68 B on an insert;
- add to one per-reason counter: 4 B.

Each event the ring keeps adds an 8 B append.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

ROW_BYTES = 64 + 8 + 4 + 68 + 28 + 4
INSERT_EXTRA_BYTES = 68 - 28
EVENT_BYTES = 8


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def verdict_bytes(rows: int, inserts: int, events: int) -> int:
    """Least HBM bytes that ``rows`` verdicts with ``inserts`` new
    conntrack entries and ``events`` kept events must move."""
    return rows * ROW_BYTES + inserts * INSERT_EXTRA_BYTES \
        + events * EVENT_BYTES
