"""Operations and bytes that the traffic needs of ``route_accumulate``.

Counted from the traffic, whatever implements it: every real tuple has to
be read once (8 B) and costs at least one operation.  The state cells a
tuple updates are not counted, because a skewed stream may touch far
fewer distinct cells than it has tuples; nor are the launch shapes, the
masked padding or the tuples x bins compares of today's kernel.  So the
count is a lower bound of any correct kernel's work: a faster kernel
reads a higher share, and none can read above 100%.
"""
from __future__ import annotations

TUPLE_BYTES = 8

# what the kernel's device ops are called in a profiler trace
NAME_PATTERNS = ("route_accumulate",)


def work(rows):
    """``(ops, bytes)`` needed by the flushes of telemetry ``rows``."""
    tuples = sum(int(r["tuples"]) for r in rows)
    return tuples, TUPLE_BYTES * tuples
