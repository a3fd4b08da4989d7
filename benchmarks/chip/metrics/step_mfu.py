"""The whole step's share of the chips' peak: model FLOPs per instance
(forward and backward of the trained batch plus the forward of the scored
batch, from shapes, nothing recomputed counted) times instances per second
of the traced window, over chips times the bf16 peak."""

from harness import counts


def read(ctx):
    if not ctx.instances:
        return None
    rate = ctx.instances / ctx.window_s
    per = counts.step_flops_per_instance(ctx.model, ctx.cfg)
    return 100.0 * per * rate / (ctx.chips * ctx.peak["flops_bf16"])
