"""KV manager: mean over the window's pumps of the share of the page pool
in use after the pump (``EngineCore.free_pages``)."""


def read(run):
    pumps = [p for p in run.pumps if p.in_window]
    if not pumps:
        return None
    return 100.0 * sum(1 - p.free_pages / run.pool_pages
                       for p in pumps) / len(pumps)
