"""Whole-step share of the chip's bf16 peak."""
from bench.readers import step_mfu


def read(view):
    return step_mfu(view)
