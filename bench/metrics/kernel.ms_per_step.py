"""Device time per step of the Pallas kernels (the trace's ops classed
``kernel`` by the run's compiled HLO: a ``tpu_custom_call``, bare or
fused).  In the training cells these are the DPS weight and gradient tree
passes' fused quantize kernel, where the program has it."""


def read(ctx):
    s = ctx["reduction"].class_s["kernel"]
    return 1e3 * s / ctx["steps"] if s > 0 else None
