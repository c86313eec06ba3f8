"""Model FLOP/s utilization of the whole step: the model's forward and
backward matmul operations per step (``bench/flops.py``, recomputation not
counted) times steps over the window, over chips times the bf16 peak."""


def read(ctx):
    flops = ctx["model_flops_per_step"] * ctx["steps"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops"]
    return 100.0 * flops / ctx["window_s"] / peak
