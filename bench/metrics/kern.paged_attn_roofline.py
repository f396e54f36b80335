"""Paged decode-attention kernel: least time for the live lanes' contexts
of each whole traced step over the kernel's device time in it."""
from bench.readers import paged_attn_roofline


def read(view):
    return paged_attn_roofline(view)
