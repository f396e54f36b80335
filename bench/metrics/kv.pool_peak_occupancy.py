"""Peak share of the KV page pool in use (engine.page_pool_stats)."""


def read(view):
    if not view.pool.get("n_pages"):
        return None
    return 100.0 * view.pool["peak_occupancy"]
