"""The bytes a breadth-first search needs, whatever implements it.

Four terms, from the counts a check reports: each frontier row read
once, one 8-byte probe of the fingerprint table per generated
successor, one 8-byte insert per new state, and one row appended to the
row log per new state.  Every unique state is a frontier row once and a
new state once.  The same work is counted whether dedup sorts or not.
"""

from __future__ import annotations

WORD_BYTES = 4
FINGERPRINT_BYTES = 8


def bfs_bytes(unique: int, generated: int, state_width: int) -> int:
    row = state_width * WORD_BYTES
    return (
        unique * row  # frontier rows read
        + generated * FINGERPRINT_BYTES  # probes
        + unique * FINGERPRINT_BYTES  # inserts
        + unique * row  # rows appended
    )
