"""Median time a request waited for a lane (GenerationOutput.queue_s),
requests finished in the window."""
from bench.readers import finished_in_window


def read(view):
    waits = sorted(r.output.queue_s for r in finished_in_window(view))
    if not waits:
        return None
    n = len(waits)
    return 1e3 * (waits[n // 2] if n % 2 else
                  (waits[n // 2 - 1] + waits[n // 2]) / 2)
