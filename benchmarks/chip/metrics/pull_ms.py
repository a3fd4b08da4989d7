"""Device time per step of the program ``jit__pull`` (XLA module name as
the trace shows it, before the hash), averaged over the chips."""

PROGRAM = "jit__pull"


def read(ctx):
    if ctx.trace is None or not ctx.steps:
        return None
    per_chip = ctx.trace.module_s(PROGRAM)
    if not any(per_chip):
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / ctx.steps
