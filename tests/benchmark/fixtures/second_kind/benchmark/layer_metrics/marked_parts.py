"""Reader ``marked_parts`` (tests only): rooflines of the parts a model kind
marks in the trace, one line a part."""

from . import device_trace


def mix_roofline(ctx):
    return device_trace.roofline(ctx, "mix")
