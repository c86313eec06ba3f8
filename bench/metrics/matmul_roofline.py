"""The matmuls' share of their roofline: the matmul operations one chip
executes per step (recomputation and padding included, from the step's
jaxpr) over the matmul ops' device time per step, over the chip's bf16
peak.  Matmuls at these shapes are bound by compute, not by HBM."""


def read(ctx):
    s = ctx["reduction"].class_s["matmul"]
    if s <= 0:
        return None
    per_step = s / ctx["steps"]
    return (100.0 * ctx["matmul_flops_per_step_per_chip"] / per_step
            / ctx["peaks"]["bf16_flops"])
