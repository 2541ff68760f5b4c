"""Reader ``kernel_shares``: the roofline of the expert layers a model kind
marks ``moe`` in the trace (``kind.marks``) and counts the work of
(``kind.moe_work``).  Nothing where the kind marks no such part or no traced
op carries the mark.  (Attention, whatever its masks and head counts, is
marked ``attention`` and read by ``device_trace.attention_roofline``.)"""

from . import device_trace


def moe_roofline(ctx):
    return device_trace.roofline(ctx, "moe")
