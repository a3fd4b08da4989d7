"""Rows the cache tier evicted a step in the window: occupied slots it
gave to a missed id, from the program's cumulative counter ``evictions``
(``CachedBackend.stats``; ``harness.system.counters`` names it
``cache_evictions``) after minus before the window, over the window's
steps.  Set-up fills the cache, so every miss in the window evicts."""


def read(ctx):
    if "cache_evictions" not in ctx.counters1 or not ctx.steps:
        return None
    return (ctx.counters1["cache_evictions"]
            - ctx.counters0["cache_evictions"]) / ctx.steps
