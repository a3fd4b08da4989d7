"""The fused embedding-bag kernel's share of its roofline: the least time
of all its calls in the window (counts.bag; bound by HBM bytes at these
shapes) over the device time of its events.  Kernel: the Pallas call the
trace names ``embedding_bag_pallas`` (training's forward, under vmap, and
the online scoring's forward; its backward is XLA's)."""

from harness import counts

KERNEL = "embedding_bag_pallas"


def _is_kernel(text):
    return KERNEL in text.split(" ", 1)[0] and "custom-call(" in text


def read(ctx):
    if ctx.trace is None or not ctx.steps or "n_fields" not in ctx.cfg:
        return None
    found = ctx.trace.ops_matching(_is_kernel)
    spent = [s for s, _ in found]
    if not any(spent):
        return None
    calls = found[0][1] / ctx.steps          # per step: training, scoring
    B, nnz = ctx.batch, int(ctx.cfg["nnz_per_instance"])
    dim, bags = int(ctx.cfg["embed_dim"]), ctx.batch * int(ctx.cfg["n_fields"])
    least = 0.0
    for d in ctx.distinct:
        t, _ = counts.least_time(*counts.bag(d["sparse"], B * nnz, bags, dim),
                                 ctx.peak)
        least += t * calls
    return 100.0 * least / (sum(spent) / len(spent))
