"""Requests the serve engine answered in a coalesced (batched) dispatch,
as a share of the requests submitted in the window, from the engine's
own counters (``ServeEngine.stats()``). Moves ``queries_per_s``."""


def read(ctx):
    total = ctx.counters.get("requests", 0)
    if not total:
        return None
    return 100.0 * ctx.counters.get("coalesced_requests", 0) / total
