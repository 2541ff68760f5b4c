"""TensorFlow / TensorFlow-Lite filter backends.

Functional parity with the reference's two headline subplugins:

- ``tensorflow-lite`` (``tensor_filter_tensorflow_lite_core.cc``): loads a
  ``.tflite`` flatbuffer via ``tf.lite.Interpreter`` (the same runtime the
  reference embeds), reads I/O dims from the interpreter
  (``_core.cc:272-278``) and invokes into preallocated buffers.  A keras
  model object converts on open (weights stay local — zero-egress
  environments can't download pretrained ones).
- ``tensorflow`` (``tensor_filter_tensorflow_core.cc``): wraps a TF
  SavedModel / keras model / ``tf.function`` as a stream filter.

TensorFlow is imported lazily so the rest of the framework never pays for it.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import numpy as np

from ..spec import TensorSpec, TensorsSpec
from .base import FilterBackend, register_backend


def _tf():
    import tensorflow as tf

    return tf


@register_backend("tensorflow-lite")
class TFLiteBackend(FilterBackend):
    device_resident = False

    def __init__(self):
        self.interpreter = None
        self._in_spec: Optional[TensorsSpec] = None
        self._out_spec: Optional[TensorsSpec] = None

    def open(self, model, custom: str = "") -> None:
        tf = _tf()
        kwargs = {}
        for part in (custom or "").split(","):
            k, _, v = part.partition("=")
            if k.strip() == "num_threads" and v.strip():
                # the reference pins interpreter threads the same way
                # (tflite Interpreter option; see _core.cc interpreter build)
                kwargs["num_threads"] = int(v)
        if isinstance(model, (str, os.PathLike)) and os.fspath(model).endswith(".tflite"):
            self.interpreter = tf.lite.Interpreter(model_path=os.fspath(model), **kwargs)
        elif isinstance(model, (bytes, bytearray)):
            self.interpreter = tf.lite.Interpreter(model_content=bytes(model), **kwargs)
        else:
            # keras model / concrete function → convert in-memory
            converter = tf.lite.TFLiteConverter.from_keras_model(model)
            self.interpreter = tf.lite.Interpreter(
                model_content=converter.convert(), **kwargs)
        self.interpreter.allocate_tensors()
        self._read_specs()

    def _read_specs(self) -> None:
        def spec_of(details) -> TensorsSpec:
            tensors = []
            for d in details:
                tensors.append(
                    TensorSpec(
                        dtype=np.dtype(d["dtype"]),
                        shape=tuple(int(s) for s in d["shape"]),
                        name=d.get("name"),
                    )
                )
            return TensorsSpec(tensors=tuple(tensors))

        # cache details: invariant after allocate_tensors, and re-fetching
        # per frame is two C-API round trips in the hot loop
        self._in_details = self.interpreter.get_input_details()
        self._out_details = self.interpreter.get_output_details()
        self._in_spec = spec_of(self._in_details)
        self._out_spec = spec_of(self._out_details)

    def close(self) -> None:
        self.interpreter = None

    def input_spec(self) -> Optional[TensorsSpec]:
        return self._in_spec

    def model_spec(self) -> Optional[TensorsSpec]:
        # dtype/arity are the model's real constraints; shapes are
        # resizable (resize_tensor_input), so the template leaves them open
        if self._in_spec is None:
            return None
        return TensorsSpec(
            tensors=tuple(
                TensorSpec(dtype=t.dtype, shape=None)
                for t in self._in_spec.tensors
            )
        )

    def output_spec(self) -> Optional[TensorsSpec]:
        return self._out_spec

    def reconfigure(self, in_spec: TensorsSpec) -> TensorsSpec:
        merged = self._in_spec.intersect(in_spec) if self._in_spec else in_spec
        if merged is None:
            # Shape mismatch is resizable (tflite dynamic batch); anything
            # else (dtype, arity) is a real negotiation failure — surface it
            # now, not mid-stream in invoke().
            if self._in_spec is not None and (
                in_spec.num_tensors != self._in_spec.num_tensors
                or any(
                    a.dtype is not None and b.dtype is not None and a.dtype != b.dtype
                    for a, b in zip(in_spec.tensors, self._in_spec.tensors)
                )
            ):
                raise ValueError(
                    f"tensorflow-lite: stream spec {in_spec} incompatible "
                    f"with model spec {self._in_spec}"
                )
            merged = in_spec
        if merged.tensors_fixed and merged != self._in_spec:
            details = self.interpreter.get_input_details()
            for d, t in zip(details, merged.tensors):
                if tuple(int(s) for s in d["shape"]) != t.shape:
                    self.interpreter.resize_tensor_input(d["index"], list(t.shape))
            self.interpreter.allocate_tensors()
            self._read_specs()
        return self._out_spec

    def invoke(self, tensors: Tuple) -> Tuple:
        for d, t in zip(self._in_details, tensors):
            self.interpreter.set_tensor(d["index"], np.asarray(t))
        self.interpreter.invoke()
        return tuple(
            self.interpreter.get_tensor(d["index"]) for d in self._out_details
        )


@register_backend("tensorflow")
class TFBackend(FilterBackend):
    device_resident = False

    def __init__(self):
        self.fn = None
        self._in_spec: Optional[TensorsSpec] = None
        self._out_spec: Optional[TensorsSpec] = None

    def open(self, model, custom: str = "") -> None:
        tf = _tf()
        del custom
        if isinstance(model, (str, os.PathLike)):
            loaded = tf.saved_model.load(os.fspath(model))
            sig = loaded.signatures.get("serving_default")
            if sig is not None:
                # restored signature ConcreteFunctions are keyword-only;
                # adapt positional stream tensors onto the signature's
                # declared input names (in declaration order)
                _, kwargs_spec = sig.structured_input_signature
                names = list(kwargs_spec)

                def call_sig(*args, _sig=sig, _names=names):
                    return _sig(**dict(zip(_names, args)))

                self.fn = call_sig
                self._keep = loaded  # prevent GC of the SavedModel
            else:
                self.fn = loaded
        elif callable(model):
            self.fn = model  # keras model or tf.function
        else:
            raise TypeError(f"unsupported tensorflow model: {type(model)}")

    def close(self) -> None:
        self.fn = None

    def input_spec(self) -> Optional[TensorsSpec]:
        return self._in_spec

    def model_spec(self) -> Optional[TensorsSpec]:
        # tf.functions/keras models retrace per shape: polymorphic, so the
        # last fixated spec must not veto a mid-stream renegotiation
        return None

    def output_spec(self) -> Optional[TensorsSpec]:
        return self._out_spec

    def reconfigure(self, in_spec: TensorsSpec) -> TensorsSpec:
        tf = _tf()
        if not in_spec.tensors_fixed:
            in_spec = in_spec.fixate()
        self._in_spec = in_spec
        dummies = [
            tf.zeros(t.shape, dtype=tf.dtypes.as_dtype(t.dtype))
            for t in in_spec.tensors
        ]
        outs = self.fn(*dummies)
        outs = self._normalize(outs)
        self._out_spec = TensorsSpec(
            tensors=tuple(
                TensorSpec(dtype=np.dtype(o.dtype.as_numpy_dtype), shape=tuple(o.shape))
                for o in outs
            )
        )
        return self._out_spec

    @staticmethod
    def _normalize(outs):
        if isinstance(outs, dict):
            return tuple(outs[k] for k in sorted(outs))
        if not isinstance(outs, (tuple, list)):
            return (outs,)
        return tuple(outs)

    def invoke(self, tensors: Tuple) -> Tuple:
        from .interop import to_tf

        # dlpack bridge for device-resident jax inputs (interop.py)
        outs = self._normalize(self.fn(*[to_tf(t) for t in tensors]))
        return tuple(np.asarray(o) for o in outs)
