"""Share of the SSD chunked scan's device time spent in Pallas kernels: the
ops of scope ``ssm.scan`` classed ``kernel`` (a ``tpu_custom_call``) over
every op of that scope (``bench/ssm_scan.py``), in %.  It says whether the
program ran the scan's intra-chunk form as its kernels; None where the
scan has no kernel op or no scope."""

from bench import scopes, ssm_scan
from bench import trace_reduce as tr


def read(ctx):
    total = ssm_scan.seconds(ctx)
    if total <= 0:
        return None
    h = scopes._harness_locals(ctx)
    ops, spans = tr.read_xplane(h["trace_dir"])
    classes = tr.hlo_classes(h["hlo"])
    kernels = {name: scope for name, scope in
               scopes.hlo_scopes(h["hlo"], (ssm_scan.SCOPE,)).items()
               if classes.get(name) == "kernel"}
    s = ssm_scan.scan_seconds(ops, spans, classes, kernels, h["platform"])
    return 100.0 * s / total if s > 0 else None
