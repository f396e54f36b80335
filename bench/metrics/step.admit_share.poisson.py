"""Device time of the admission program (prompt prefill) over the
traced window."""
from bench.readers import program_share


def read(view):
    return program_share(view, "_admit")
