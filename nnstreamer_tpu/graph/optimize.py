"""Graph optimization: fuse adjacent transforms into XLA-backed filters.

The north-star requirement: ``tensor_transform``'s
arithmetic/typecast/transpose ops fuse into the model's XLA graph.  The
reference accelerates transforms with hand-written Orc SIMD
(``tensor_transform.c:330-405``); the TPU-native answer is compiler-grade —
rewrite ``transform* → filter(jax) → transform*`` chains into a single
filter whose backend compiles ``post∘model∘pre`` as ONE XLA program:

- elementwise pre-ops (typecast/normalize) run on-device fused into the
  model's first layers, so only the raw (e.g. uint8) frame crosses
  host→device — ¼ the transfer of pre-normalized float32;
- post-transforms fuse into the model's tail the same way.

Called automatically from ``Pipeline.start`` (disable with
``pipeline.auto_fuse = False``).

Transform fusion is the *adjacent-element* rewrite; whole-segment
compilation (:mod:`.segments`, conf ``[segment] enabled``) builds on the
same wrapper machinery to fold an entire run-to-completion region —
trivial converters and lowerable decoder heads included — into one
device program.  ``_hop_transparent``/``_splice_out`` below are shared
with that planner.
"""

from __future__ import annotations

from typing import List

from .node import Node
from .pipeline import Pipeline


def _is_fusable_transform(node: Node) -> bool:
    from ..elements.transform import TensorTransform

    return (
        isinstance(node, TensorTransform)
        and node.acceleration
        and len(node.sink_pads) == 1
        and len(node.src_pads) == 1
    )


def _is_fusable_filter(node: Node) -> bool:
    from ..backends.jax_backend import JaxBackend
    from ..elements.filter import TensorFilter

    return isinstance(node, TensorFilter) and isinstance(node.backend, JaxBackend)


def _hop_transparent(pad, direction: str):
    """Walk past spec-transparent 1-in/1-out plumbing (queue, tensor_upload)
    so transforms separated from the filter only by thread/wire boundaries
    still fuse: ``transform → upload → queue → filter`` compiles to one XLA
    program fed raw wire bytes.  (Deliberately narrower than the residency
    walk's passthrough set: hopping tee/mux/demux would move a transform
    across a fan point and change other branches' streams.)"""
    from ..elements.queue import Queue
    from ..elements.upload import TensorUpload
    from .residency import hop_plumbing

    return hop_plumbing(pad, direction, (Queue, TensorUpload))


def _splice_out(pipeline: Pipeline, node: Node):
    """Remove a 1-in/1-out node, reconnecting its neighbors.  Returns an
    undo closure restoring the original topology."""
    sink_pad = next(iter(node.sink_pads.values()))
    src_pad = next(iter(node.src_pads.values()))
    up = sink_pad.peer
    down = src_pad.peer
    up.peer = None
    sink_pad.peer = None
    src_pad.peer = None
    if down is not None:
        down.peer = None
        up.link(down)
    del pipeline.nodes[node.name]
    node.pipeline = None

    def undo():
        if down is not None:
            up.peer = None
            down.peer = None
            down.peer = src_pad
            src_pad.peer = down
        up.peer = sink_pad
        sink_pad.peer = up
        pipeline.nodes[node.name] = node
        node.pipeline = pipeline

    return undo


def fuse_transforms(pipeline: Pipeline) -> List:
    """Fold accelerated transforms around jax filters.  Returns a list of
    undo closures — run in reverse to restore the un-fused graph (used by
    ``Pipeline.start`` when a later start step fails, so a failed start
    leaves the user's graph intact)."""
    undos: List = []
    for filt in [n for n in pipeline.nodes.values() if _is_fusable_filter(n)]:
        # upstream chain (immediately preceding transforms, nearest last)
        pre: List[Node] = []
        while True:
            peer = _hop_transparent(filt.sink_pads["sink"].peer, "up")
            if peer is None or not _is_fusable_transform(peer.node):
                break
            tr = peer.node
            undos.append(_splice_out(pipeline, tr))
            pre.insert(0, tr)
        post: List[Node] = []
        while True:
            peer = _hop_transparent(filt.src_pads["src"].peer, "down")
            if peer is None or not _is_fusable_transform(peer.node):
                break
            tr = peer.node
            undos.append(_splice_out(pipeline, tr))
            post.append(tr)
        if pre or post:
            filt.set_fused_transforms(pre, post)

            def undo_install(f=filt):
                f.set_fused_transforms([], [])
                backend = getattr(f, "backend", None)
                if backend is not None and hasattr(backend, "set_wrapper"):
                    backend.set_wrapper(None)

            undos.append(undo_install)
    return undos
