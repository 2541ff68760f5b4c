"""``tensor_merge``: N× tensors → one *bigger* tensor, concatenated along a
dimension.

Analog of ``gst/nnstreamer/tensor_merge/gsttensormerge.{c,h}`` (mode
``linear`` with direction option, ``gsttensormerge.h:47-66``), sharing the
mux's CollectPads/time-sync machinery.  The ``option`` property is the NNS
dimension index (0 = innermost) to concatenate along; we translate to the
numpy axis of the negotiated rank.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from ..buffer import Frame
from ..graph.node import NegotiationError
from ..graph.registry import register_element
from ..obs import hooks as _hooks
from ..obs import spans as _spans
from ..spec import TensorSpec, TensorsSpec
from .collect import CollectNode


@register_element("tensor_merge")
class TensorMerge(CollectNode):
    def __init__(
        self,
        name: Optional[str] = None,
        mode: str = "linear",
        option: str = "0",
        sync_mode: str = "slowest",
        sync_option: str = "",
    ):
        super().__init__(name, sync_mode=sync_mode, sync_option=sync_option)
        if mode != "linear":
            raise ValueError(f"tensor_merge supports mode=linear, got {mode!r}")
        self.mode = mode
        self.nns_dim = int(option)
        self._axis = 0  # numpy axis, resolved at configure

    def _resolve_axis(self, rank: int) -> int:
        if self.nns_dim >= rank:
            raise NegotiationError(
                f"{self.name}: merge dim {self.nns_dim} out of rank {rank}"
            )
        return rank - 1 - self.nns_dim  # NNS innermost-first → numpy axis

    def configure(self, in_specs: Dict[str, TensorsSpec]) -> Dict[str, TensorsSpec]:
        order = sorted(in_specs, key=lambda n: (len(n), n))
        specs = []
        rate = None
        for name in order:
            s = in_specs[name]
            if s.num_tensors != 1:
                raise NegotiationError(f"{self.name}: merge inputs must be single-tensor")
            specs.append(s.tensors[0])
            if s.rate is not None:
                rate = s.rate if rate is None else min(rate, s.rate)
        first = specs[0]
        rank = first.rank
        if any(t.rank != rank for t in specs):
            raise NegotiationError(f"{self.name}: merge inputs must share rank")
        if any(t.dtype != first.dtype for t in specs):
            raise NegotiationError(f"{self.name}: merge inputs must share dtype")
        self._axis = self._resolve_axis(rank)
        out_dim = 0
        for t in specs:
            for ax, (a, b) in enumerate(zip(t.shape, first.shape)):
                if ax != self._axis and a != b:
                    raise NegotiationError(
                        f"{self.name}: non-merge dims differ: {t} vs {first}"
                    )
            out_dim += t.shape[self._axis]
        out_shape = tuple(
            out_dim if ax == self._axis else d for ax, d in enumerate(first.shape)
        )
        out = TensorSpec(dtype=first.dtype, shape=out_shape)
        return {"src": TensorsSpec(tensors=(out,), rate=rate)}

    def combine(self, frames: Dict[str, Frame]) -> Optional[Frame]:
        order = sorted(frames, key=lambda n: (len(n), n))
        arrays = [frames[name].tensor(0) for name in order]
        if any(hasattr(a, "devices") for a in arrays):  # jax arrays: stay on device
            import jax.numpy as jnp

            merged = jnp.concatenate(arrays, axis=self._axis)
        else:
            # into a recycled pooled buffer (nnstreamer_tpu/pool.py), as
            # tensor_batch assembles: a fresh multi-MB result is a new
            # mapping whose pages fault in under the copy
            from ..pool import default_pool

            arrays = [np.asarray(a) for a in arrays]
            shape = list(arrays[0].shape)
            shape[self._axis] = sum(a.shape[self._axis] for a in arrays)
            merged = default_pool().lease(shape, arrays[0].dtype)
            np.concatenate(arrays, axis=self._axis, out=merged)
            if _hooks.enabled:
                _hooks.emit("copy", self, merged.nbytes,
                            1 if merged.pool_fresh else 0)
        pts, dur = self.output_timing(frames)
        meta: Dict[str, Any] = {}
        if _spans.enabled:
            # as tensor_mux: the merged frame's span names every merged
            # frame's, so a source frame's trace reaches its device_exec
            self.coalesce(frames, meta)
        return Frame(tensors=(merged,), pts=pts, duration=dur, meta=meta)
