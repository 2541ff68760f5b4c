"""``tensor_split``: slice one tensor into N tensors along a dimension.

Analog of ``gst/nnstreamer/tensor_split/gsttensorsplit.c``: ``tensorseg``
gives each output's dims (NNS ``d1:d2:d3:d4`` strings, comma-separated,
``gsttensorsplit.c:63-66``); outputs differ from the input only along one
axis, whose per-output sizes define the split offsets.  ``tensorpick``
selects a subset of segments (``:122-131``).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from ..buffer import Frame, WireTensor
from ..graph.node import NegotiationError, Node, Pad
from ..graph.registry import register_element
from ..obs import hooks as _hooks
from ..obs import spans as _spans
from ..spec import TensorSpec, TensorsSpec


@register_element("tensor_split")
class TensorSplit(Node):
    REQUEST_SRC_PADS = True

    def __init__(
        self,
        name: Optional[str] = None,
        tensorseg: str = "",
        tensorpick: str = "",
    ):
        super().__init__(name)
        self.add_sink_pad("sink")
        if not tensorseg:
            raise ValueError("tensor_split requires tensorseg=")
        self.segments: List[TensorSpec] = [
            TensorSpec.from_dims_string(s) for s in str(tensorseg).split(",") if s
        ]
        self.tensorpick: Optional[List[int]] = None
        if tensorpick:
            self.tensorpick = [int(x) for x in str(tensorpick).split(",")]
        self._axis = 0
        self._offsets: List[slice] = []

    def _pad_order(self) -> List[str]:
        return sorted(self.src_pads, key=lambda n: (len(n), n))

    def _selected(self) -> List[int]:
        return self.tensorpick if self.tensorpick is not None else list(
            range(len(self.segments))
        )

    def configure(self, in_specs: Dict[str, TensorsSpec]) -> Dict[str, TensorsSpec]:
        spec = in_specs["sink"]
        if spec.num_tensors != 1:
            raise NegotiationError(f"{self.name}: split input must be single-tensor")
        t = spec.tensors[0]
        rank = t.rank
        segs = []
        for s in self.segments:
            shape = s.shape
            if len(shape) < rank:  # pad squeezed trailing NNS 1s → leading numpy 1s
                shape = (1,) * (rank - len(shape)) + shape
            elif len(shape) > rank:
                raise NegotiationError(f"{self.name}: segment rank > input rank")
            segs.append(TensorSpec(dtype=t.dtype, shape=shape))
        # Find the (single) axis along which segments may differ from input.
        axis = None
        for ax in range(rank):
            total = sum(s.shape[ax] for s in segs)
            if all(
                s.shape[a] == t.shape[a] for s in segs for a in range(rank) if a != ax
            ) and total == t.shape[ax]:
                axis = ax
                break
        if axis is None:
            raise NegotiationError(
                f"{self.name}: tensorseg {self.segments} does not tile input {t}"
            )
        self._axis = axis
        self._offsets = []
        pos = 0
        for s in segs:
            n = s.shape[axis]
            self._offsets.append(slice(pos, pos + n))
            pos += n
        sel = self._selected()
        order = self._pad_order()
        if len(order) > len(sel):
            raise NegotiationError(
                f"{self.name}: more src pads than selected segments"
            )
        return {
            pad_name: TensorsSpec(tensors=(segs[sel[i]],), rate=spec.rate)
            for i, pad_name in enumerate(order)
        }

    def process(self, pad: Pad, frame: Frame):
        del pad
        arr = frame.tensor(0)
        if isinstance(arr, WireTensor):
            # materialize ONCE and slice the cached host array: WireTensor
            # subscripting pays a full device→host copy per __getitem__, so
            # the old per-pad slicing cost N d2h round trips per frame
            arr = np.asarray(arr)
            if _hooks.enabled:
                _hooks.emit("copy", self, arr.nbytes, 1)
        sel = self._selected()
        out = []
        for i, pad_name in enumerate(self._pad_order()):
            sl = [slice(None)] * arr.ndim
            sl[self._axis] = self._offsets[sel[i]]
            out.append(
                (pad_name, Frame.of(arr[tuple(sl)], pts=frame.pts, duration=frame.duration))
            )
        _spans.carry_context(frame, (cut for _, cut in out))
        return out
