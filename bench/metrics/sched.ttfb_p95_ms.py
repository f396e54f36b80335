"""95th percentile, over the requests due in the window, of the time from
scheduled send to first block, read as ``ttfb_p95_ms`` is; per layer in
the cells whose host stalls leave that tail too unsteady to bound."""
from bench.harness import end_to_end


def read(view):
    return end_to_end(view.reqs, view.ws, view.we,
                      view.cell.serve["block_size"]).get("ttfb_p95_ms")
