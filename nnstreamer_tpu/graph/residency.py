"""Device-residency topology walk, shared by hot-path elements.

A frame's tensors are jax Arrays (device-resident) on any segment of the
graph between XLA-backed filters, provided every element in between passes
payloads through untouched.  Elements use this walk at configure time to
pick their per-frame strategy:

- ``tensor_filter`` — prewarm the shaped entry vs the flat host-wire twin
  upstream; start async device→host copies for host consumers downstream
  (``tensor_filter.c:316-436``'s map/invoke/unmap discipline, re-cast for
  an accelerator with an async wire).
- ``tensor_unbatch`` — host consumers get ONE device→host copy + numpy row
  views; device consumers get a single jitted split (never N eager slice
  ops per round — measured 0.7 ms/round of pure dispatch overhead).
"""

from __future__ import annotations

from .node import Node


def _passthrough_types():
    from ..elements.batch import TensorBatch, TensorUnbatch
    from ..elements.demux import TensorDemux
    from ..elements.mux import TensorMux
    from ..elements.queue import Queue
    from ..elements.tee import Tee
    from ..elements.upload import TensorUpload

    return (Queue, Tee, TensorBatch, TensorUnbatch, TensorDemux, TensorMux,
            TensorUpload)


def hop_plumbing(pad, direction: str, transparent, max_hops: int = 4):
    """Follow a chain of 1-in/1-out nodes of the given ``transparent`` types
    starting at ``pad`` (a peer pad); returns the first pad whose node is
    not transparent (or None when the chain ends/branches).  The single
    graph-walk primitive behind residency detection, fusion hopping, and
    the upload element's wire-rule discovery — one place to update when a
    new spec-transparent element is added."""
    up = direction == "up"
    hops = 0
    while pad is not None and isinstance(pad.node, transparent) and hops < max_hops:
        node = pad.node
        pads = node.sink_pads if up else node.src_pads
        if len(pads) != 1:
            break
        pad = next(iter(pads.values())).peer
        hops += 1
    return pad


def downstream_filter_node(node: Node, max_hops: int = 4):
    """The first backend-carrying node downstream of ``node``, hopping
    over queue/upload plumbing (None when the chain ends, branches, or
    lands on a non-filter).  The node (not just its backend) is what the
    warmup planner needs: ``TensorFilter.warm_spec`` owns the fused-
    wrapper rebuild discipline a bucket compile must follow."""
    from ..elements.queue import Queue
    from ..elements.upload import TensorUpload

    pads = node.src_pads
    if len(pads) != 1:
        return None
    pad = hop_plumbing(
        next(iter(pads.values())).peer, "down", (Queue, TensorUpload),
        max_hops,
    )
    if pad is None or getattr(pad.node, "backend", None) is None:
        return None
    return pad.node


def downstream_backend(node: Node, max_hops: int = 4):
    """The first filter backend downstream of ``node``, hopping over
    queue/upload plumbing (None when the chain ends, branches, or lands on
    a non-filter).  Shared by ``tensor_upload`` (wire-rule/sharding
    discovery) and the batch elements (the consumer's mesh width).
    """
    filt = downstream_filter_node(node, max_hops)
    return getattr(filt, "backend", None) if filt is not None else None


def consumer_mesh_devices(node: Node, max_hops: int = 4) -> int:
    """Device count of the dispatch mesh the downstream filter backend will
    shard over (1 = unsharded dispatch).  The device-mesh placement mode:
    conf ``[mesh]`` / ``NNSTPU_MESH=dp:8`` (auto-detected from
    ``jax.devices()``; CPU-testable via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``) turns the jax
    backend's dispatch into a batch-axis ``NamedSharding`` over all chips,
    and this walk hands that geometry to the batch elements and the query
    server so they size buckets in per-shard multiples — one dynbatch
    invoke then spreads ndev× the batch at roughly single-chip latency."""
    backend = downstream_backend(node, max_hops)
    get = getattr(backend, "mesh_devices", None)
    if not callable(get):
        return 1
    try:
        return max(1, int(get()))
    except Exception:  # noqa: BLE001 — a sick backend must not kill config
        return 1


def dispatch_mesh():
    """The process-wide dispatch mesh (None = mesh mode off).  Re-exported
    from ``parallel.mesh`` so graph-layer callers have one placement
    import; see :func:`consumer_mesh_devices` for the negotiation-time
    walk."""
    from ..parallel.mesh import dispatch_mesh as _dm

    return _dm()


def chain_device_resident(node: Node, direction: str, max_hops: int = 4) -> bool:
    """Walk the up- or downstream chain a few hops from ``node``: a
    device_resident filter with only residency-*preserving* elements between
    means frames on that side are jax Arrays.  Only elements that pass
    device payloads through untouched qualify (queue/tee/batch/unbatch/
    demux/mux/upload); anything else (converter, host transforms, decoders,
    sinks) emits or consumes host numpy and stops the walk."""
    up = direction == "up"
    pads = node.sink_pads if up else node.src_pads
    if len(pads) != 1:
        return False
    pad = hop_plumbing(
        next(iter(pads.values())).peer, direction, _passthrough_types(), max_hops
    )
    if pad is None:
        return False
    backend = getattr(pad.node, "backend", None)
    if backend is None:
        return False
    return bool(getattr(backend, "device_resident", False))
