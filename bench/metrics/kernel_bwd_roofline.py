"""Share of its roofline that the fused reverse-sweep kernel
(``kernels/pinn_mlp.py`` ``_kernel2_bwd``) reaches in the traced window.

Kernel time is the summed device time of the trace's operations that are
this kernel: on a v5e the custom call ``%pinn2-bwd-fused.N
[tpu_custom_call]``, named by the ``pinn2-bwd-fused`` scope that
``kernels/ops.py`` opens around it (the Pallas call sets no name).  The
least time is ``flops.py``'s true-shape operations and bytes
of one launch, times the steps in the window, at the chip's peak.  Finds
nothing, and returns None, where the kernel is not on the timed path.
"""
from bench import flops, trace

KERNEL = "kernel_bwd"


def is_kernel(name: str) -> bool:
    return "[tpu_custom_call]" in name and "pinn2-bwd-fused" in name


def read(ctx):
    if ctx.get("kind") != "train" or "trace" not in ctx:
        return None
    s = ctx["summary"]
    ns = trace.op_ns(ctx["trace"], s["t0"], s["t1"],
                     is_kernel)
    if ns == 0:
        return None
    c = ctx["counts"][KERNEL]
    least, _bound = flops.least_seconds(c["flops"] * ctx["steps"],
                                        c["bytes"] * ctx["steps"], ctx["peak"])
    return 100.0 * least / (ns / 1e9)
