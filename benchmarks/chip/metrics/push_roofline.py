"""The fused sparse-AdaGrad push kernel's share of its roofline: the least
time of one push per table and step (counts.push over the batch's distinct
ids of that table) over the device time of its events.  Kernel: the Pallas
call the trace names ``sparse_adagrad_apply_pallas`` (the cache tier's
push is the same kernel over the cache rows)."""

from harness import counts

KERNEL = "sparse_adagrad_apply_pallas"


def _is_kernel(text):
    return KERNEL in text.split(" ", 1)[0] and "custom-call(" in text


def read(ctx):
    if ctx.trace is None or not ctx.steps:
        return None
    spent = [s for s, _ in ctx.trace.ops_matching(_is_kernel)]
    if not any(spent):
        return None
    dim = int(ctx.cfg["embed_dim"])
    least = 0.0
    for d in ctx.distinct:
        for rows in d.values():
            least += counts.least_time(*counts.push(rows, dim), ctx.peak)[0]
    return 100.0 * least / (sum(spent) / len(spent))
