"""Device time per step of the SSD chunked scan: every op, matmul-class or
other, in the program's ``ssm.scan`` scope, its remat recomputation and
backward included (``bench/ssm_scan.py``)."""

from bench import ssm_scan


def read(ctx):
    return ssm_scan.ms_per_step(ctx)
