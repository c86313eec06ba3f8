"""Device time per step of every op that is neither a matmul, a collective
nor a Pallas kernel: the DPS quantize-and-stats passes, the optimizer,
norms, softmax, the SSD's elementwise terms."""


def read(ctx):
    s = ctx["reduction"].class_s["other"]
    return 1e3 * s / ctx["steps"] if s > 0 else None
