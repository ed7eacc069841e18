"""Share of the traced window in which an all-reduce (the guard's per-step
``pmin`` in ``DistributedDDTrainer``'s guarded chunk, under the
``dd-comm-agree`` scope, and the chunk's health reduction) ran on a chip
with no other operation running there, averaged over the chips.  Finds
nothing, and returns None, where no all-reduce ran."""
from bench import trace

NAMES = ("[all-reduce",)


def read(ctx):
    if ctx.get("kind") != "train" or "trace" not in ctx:
        return None
    s = ctx["summary"]
    match = lambda n: any(k in n for k in NAMES)  # noqa: E731
    if trace.op_ns(ctx["trace"], s["t0"], s["t1"], match) == 0:
        return None
    exp = trace.exposed_ns(ctx["trace"], s["t0"], s["t1"], match)
    vals = [exp[d] for d in s["devices"]]
    return 100.0 * sum(vals) / len(vals) / 1e9 / s["window_s"]
