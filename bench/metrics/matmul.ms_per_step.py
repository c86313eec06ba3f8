"""Device time per step of the operations that are, or fuse, a dot or a
convolution (the trace's ops, classed by the run's compiled HLO)."""


def read(ctx):
    s = ctx["reduction"].class_s["matmul"]
    return 1e3 * s / ctx["steps"] if s > 0 else None
