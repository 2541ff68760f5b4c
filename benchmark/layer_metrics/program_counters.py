"""Reader ``program_counters``: per-layer metrics from the program's own
metrics registry, read when the reader runs.  A program that has no such
metric (the parent of the PR that brought it) reads as nothing."""

from __future__ import annotations

from typing import Optional


def weights_upload_s(ctx) -> Optional[float]:
    """Sum of the ``nnstpu_weights_upload_seconds`` histogram: host to
    device, ``device_put`` to ready, of every model a backend opened."""
    del ctx
    from nnstreamer_tpu.obs.metrics import REGISTRY

    hist = REGISTRY.get("nnstpu_weights_upload_seconds")
    if hist is None:
        return None
    total = sum(child.sum for _, child in hist.children())
    return total if total > 0 else None
