"""Reader ``glm_shares``: the rooflines of the three parts a model kind with
latent attention under a learned key selection marks in the trace
(``kind.marks``) and counts the work of: ``sparse_attention`` (the selected
attention), ``indexer`` (the scores and the top-k that select) and
``held_experts`` (the router, this chip's share of the routed experts and
the shared one).  Nothing where the kind marks no such part or no traced op
carries the mark."""

from . import device_trace


def sparse_attention_roofline(ctx):
    return device_trace.roofline(ctx, "sparse_attention")


def indexer_roofline(ctx):
    return device_trace.roofline(ctx, "indexer")


def held_experts_roofline(ctx):
    return device_trace.roofline(ctx, "held_experts")
