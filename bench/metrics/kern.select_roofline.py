"""Fused unembed+select kernel: least time for its calls (real V, rows as
given) over their device time."""
from bench.readers import select_roofline


def read(view):
    return select_roofline(view)
