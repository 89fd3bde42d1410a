"""What the work counters share across families: how the engine packs
prefills into calls, and which steps a window holds.  The needed
operations and bytes of a family's served steps are its own
(``models/<family>.py``, class ``Work``)."""
from __future__ import annotations

from typing import Dict, List, Tuple


def prefill_calls(lengths: List[int], bucket: int, max_batch: int
                  ) -> List[Tuple[int, int]]:
    """(batch, padded length) of the prefill calls that serve prompts of
    ``lengths`` admitted in one step: grouped by padded length, in groups
    of at most ``max_batch`` (the engine's packing)."""
    by_len: Dict[int, int] = {}
    for n in lengths:
        lpad = -(-n // bucket) * bucket
        by_len[lpad] = by_len.get(lpad, 0) + 1
    calls = []
    for lpad in sorted(by_len):
        count = by_len[lpad]
        while count > 0:
            calls.append((min(count, max_batch), lpad))
            count -= max_batch
    return calls


def in_window(ticks, t0: float, seconds: float):
    """The steps that started inside the measured window."""
    return [t for t in ticks if t0 <= t.start < t0 + seconds]
