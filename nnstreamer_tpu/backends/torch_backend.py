"""Torch-CPU filter backend.

Functional parity with the reference's ``pytorch`` subplugin
(``tensor_filter_pytorch``): TorchScript files load via ``torch.jit.load``,
``nn.Module`` objects are used directly.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import numpy as np

from ..spec import TensorSpec, TensorsSpec
from .base import FilterBackend, register_backend


@register_backend("torch")
class TorchBackend(FilterBackend):
    device_resident = False

    def __init__(self):
        self.module = None
        self._in_spec: Optional[TensorsSpec] = None
        self._out_spec: Optional[TensorsSpec] = None

    def open(self, model, custom: str = "") -> None:
        import torch

        del custom
        if isinstance(model, (str, os.PathLike)):
            # map location from conf (the `torch use gpu` ini knob analog,
            # `nnstreamer.ini.in:19-20`); default cpu.
            from ..conf import conf

            device = conf.get("filter", "torch_device", "cpu")
            self.module = torch.jit.load(os.fspath(model), map_location=device)
        else:
            self.module = model  # nn.Module / scripted module
        self.module.eval()

    def close(self) -> None:
        self.module = None

    def input_spec(self) -> Optional[TensorsSpec]:
        return self._in_spec

    def model_spec(self) -> Optional[TensorsSpec]:
        # an nn.Module is shape-polymorphic: no declared constraint, so a
        # mid-stream renegotiation must not be judged against the previous
        # fixated shape (which is all _in_spec holds)
        return None

    def output_spec(self) -> Optional[TensorsSpec]:
        return self._out_spec

    def reconfigure(self, in_spec: TensorsSpec) -> TensorsSpec:
        import torch

        if not in_spec.is_fixed:
            in_spec = in_spec.fixate()
        self._in_spec = in_spec
        with torch.no_grad():
            dummies = [
                torch.zeros(tuple(t.shape), dtype=_torch_dtype(t.dtype))
                for t in in_spec.tensors
            ]
            outs = self.module(*dummies)
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        self._out_spec = TensorsSpec(
            tensors=tuple(
                TensorSpec(
                    dtype=np.dtype(str(o.dtype).replace("torch.", "")),
                    shape=tuple(o.shape),
                )
                for o in outs
            )
        )
        return self._out_spec

    def invoke(self, tensors: Tuple) -> Tuple:
        import torch

        from .interop import to_torch

        with torch.no_grad():
            # dlpack bridge: device-resident jax outputs from an upstream
            # filter enter torch zero-copy on CPU (interop.py)
            ins = [to_torch(t) for t in tensors]
            outs = self.module(*ins)
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        return tuple(o.numpy() for o in outs)


register_backend("torch-cpu")(TorchBackend)


def _torch_dtype(np_dtype):
    import torch

    return {
        np.dtype(np.float32): torch.float32,
        np.dtype(np.float64): torch.float64,
        np.dtype(np.float16): torch.float16,
        np.dtype(np.uint8): torch.uint8,
        np.dtype(np.int8): torch.int8,
        np.dtype(np.int16): torch.int16,
        np.dtype(np.int32): torch.int32,
        np.dtype(np.int64): torch.int64,
    }[np.dtype(np_dtype)]
