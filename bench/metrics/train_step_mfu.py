"""The whole training step's share of the chip's peak: ``flops.py``'s
true-shape operations per step (forward plus twice the forward for the
backward, recomputation not counted) times the traced window's steps per
second, over the peak of every chip the cell uses."""


def read(ctx):
    if ctx.get("kind") != "train" or "trace" not in ctx or not ctx["steps"]:
        return None
    f = ctx["counts"]["step_flops"] * ctx["steps_per_s"]
    return 100.0 * f / (ctx["peak"]["flops_per_s"] * ctx["chips"])
