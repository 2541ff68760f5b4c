"""``tensor_mux``: N× single-tensor streams → one multi-tensor frame.

Analog of ``gst/nnstreamer/tensor_mux/gsttensormux.c`` (CollectPads +
time-sync at ``:328-358``): each synchronized collection round emits one
``other/tensors`` frame whose tensor list is the concatenation of every
sink pad's tensors, in pad order.  This is the batching front-door for the
TPU multi-core path (survey §3.3): a mux feeding a batched ``tensor_filter``
turns N camera streams into one sharded XLA invocation.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from ..buffer import Frame
from ..graph.node import NegotiationError
from ..graph.registry import register_element
from ..obs import spans as _spans
from ..spec import NNS_TENSOR_SIZE_LIMIT, TensorsSpec
from .collect import CollectNode


@register_element("tensor_mux")
class TensorMux(CollectNode):
    def configure(self, in_specs: Dict[str, TensorsSpec]) -> Dict[str, TensorsSpec]:
        tensors = []
        rate = None
        for name in self._pad_order_specs(in_specs):
            spec = in_specs[name]
            tensors.extend(spec.tensors)
            if spec.rate is not None:
                rate = spec.rate if rate is None else min(rate, spec.rate)
        if len(tensors) > NNS_TENSOR_SIZE_LIMIT:
            raise NegotiationError(
                f"{self.name}: muxed frame would exceed {NNS_TENSOR_SIZE_LIMIT} tensors"
            )
        return {"src": TensorsSpec(tensors=tuple(tensors), rate=rate)}

    def _pad_order_specs(self, in_specs):
        return sorted(in_specs, key=lambda n: (len(n), n))

    def combine(self, frames: Dict[str, Frame]) -> Optional[Frame]:
        tensors = []
        for name in sorted(frames, key=lambda n: (len(n), n)):
            tensors.extend(frames[name].tensors)
        pts, dur = self.output_timing(frames)
        meta: Dict[str, Any] = {}
        if _spans.enabled:
            # one collection round = one new span, parent-linked to every
            # contributed stream's frame span (their cross-thread flows
            # terminate at this collect point)
            self.coalesce(frames, meta)
        return Frame(tensors=tuple(tensors), pts=pts, duration=dur, meta=meta)
