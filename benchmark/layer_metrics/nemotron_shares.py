"""Reader ``nemotron_shares``: the rooflines of the two parts a model kind
with a latent mixture of experts and Mamba-2 heads narrower than a lane
tile marks in the trace (``kind.marks``) and counts the work of:
``latent_experts`` (the held experts' passes in the latent width and their
way back) and ``narrow_scan`` (the state-space scan over those heads).
Nothing where the kind marks no such part or no traced op carries the
mark."""

from . import device_trace


def latent_experts_roofline(ctx):
    return device_trace.roofline(ctx, "latent_experts")


def narrow_scan_roofline(ctx):
    return device_trace.roofline(ctx, "narrow_scan")
