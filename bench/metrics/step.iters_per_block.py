"""Refinement iterations per block (GenerationOutput.steps over the
blocks of the requests finished in the window)."""
from bench.readers import iters_per_block


def read(view):
    return iters_per_block(view)
