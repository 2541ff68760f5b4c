"""Reader ``ssm_shares``: the roofline of the state-space scan a model kind
marks ``ssm_scan`` in the trace (``kind.marks``) and counts the work of
(``kind.ssm_scan_work``), through ``device_trace.roofline``.  Nothing where
the kind marks no such part or no traced op carries the mark."""

from . import device_trace


def ssm_scan_roofline(ctx):
    return device_trace.roofline(ctx, "ssm_scan")
