"""The SSD chunked scan's share of its roofline: the least time its
operations and bytes need on the chip, counted from the cell's shapes,
over ``ssm.scan_ms_per_step`` (``bench/ssm_scan.py``)."""

from bench import ssm_scan


def read(ctx):
    return ssm_scan.roofline_pct(ctx)
