"""Share of the window's id lookups the cache tier served from its device
cache: 1 - rows fetched from the cold tier / ids looked up, from the
program's cumulative counters ``lookups`` and ``fetched``
(``CachedBackend.stats``; ``harness.system.counters`` names them
``cache_lookups``, ``cache_fetched``) after minus before the window."""


def read(ctx):
    looked = (ctx.counters1.get("cache_lookups", 0.0)
              - ctx.counters0.get("cache_lookups", 0.0))
    if looked <= 0:
        return None
    fetched = ctx.counters1["cache_fetched"] - ctx.counters0["cache_fetched"]
    return 100.0 * (1.0 - fetched / looked)
