"""Device time per step of the train programs, local and merge steps
alike (both are the XLA module ``jit_train`` in the trace, named before
the hash), averaged over the chips."""

PROGRAM = "jit_train"


def read(ctx):
    if ctx.trace is None or not ctx.steps:
        return None
    per_chip = ctx.trace.module_s(PROGRAM)
    if not any(per_chip):
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / ctx.steps
