"""Share of the traced window in which no operation ran on the device."""
from bench.readers import idle_share


def read(view):
    return idle_share(view)
