"""Weight quantization: int8 storage, dequant-on-device.

The reference's flagship model is a **uint8-quantized** tflite MobileNet
(``tests/test_models``, survey §4/§7f) executed by CPU integer kernels.
The TPU-native equivalent implemented here:

- **weight-only symmetric int8** per output channel: weights live in HBM at
  1 byte/element (halving weight bandwidth — the usual inference bottleneck)
  and dequantize on the fly inside the XLA program, fusing into the conv /
  matmul that consumes them;
- optionally, the **int8 MXU path**: quantize activations dynamically and
  accumulate int8×int8 in int32 on the MXU
  (:func:`nnstreamer_tpu.ops.pallas_kernels.int8_matmul`).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any

import jax.numpy as jnp
import numpy as np


@dataclass
class QuantizedWeight:
    """Symmetric per-output-channel int8 weight.

    ``q`` has the original shape; ``scale`` broadcasts against it (shape
    ``(1, ..., 1, cout)``).  Registered as a pytree so it flows through
    jit/sharding like any other param leaf.
    """

    q: Any        # int8 ndarray, original weight shape (..., cout)
    scale: Any    # float32, broadcastable to q's shape

    def dequantize(self, dtype=jnp.float32):
        return self.q.astype(dtype) * self.scale.astype(dtype)


try:  # register as a pytree node (available on all supported jax versions)
    import jax.tree_util as _jtu

    _jtu.register_pytree_node(
        QuantizedWeight,
        lambda qw: ((qw.q, qw.scale), None),
        lambda aux, leaves: QuantizedWeight(*leaves),
    )
except Exception:  # pragma: no cover
    pass


def quantize_weight(w, axis: int = -1) -> QuantizedWeight:
    """Symmetric int8 quantization per slice along ``axis`` (the output
    channel for HWIO conv kernels and (cin, cout) dense kernels)."""
    w = np.asarray(w, np.float32)
    reduce_axes = tuple(i for i in range(w.ndim) if i != (axis % w.ndim))
    amax = np.max(np.abs(w), axis=reduce_axes, keepdims=True)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
    return QuantizedWeight(q=jnp.asarray(q), scale=jnp.asarray(scale))


def dequantize(qw: QuantizedWeight, dtype=jnp.float32):
    return qw.dequantize(dtype)


def maybe_dequantize(w, dtype=None):
    """Materialize a weight leaf: pass floats through, dequantize
    :class:`QuantizedWeight` (the hook the layer library calls, so any model
    in the zoo runs quantized by swapping its param leaves)."""
    if isinstance(w, QuantizedWeight):
        return w.dequantize(dtype if dtype is not None else jnp.float32)
    if dtype is not None:
        return w.astype(dtype)
    return w


def quantize_params(params):
    """Walk a pytree-of-dicts/lists quantizing every ``"w"`` leaf with
    ndim >= 2 (conv kernels, dense/matmul weights) to per-output-channel
    int8; biases, norms, embeddings-by-name and scalars stay float.  Works
    on any zoo model's params (mobilenet/SSD convs, transformer matmuls)."""

    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k == "w" and hasattr(v, "ndim") and v.ndim >= 2:
                    out[k] = quantize_weight(v, axis=-1)
                else:
                    out[k] = walk(v)
            return out
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node

    return walk(params)


def quantize_model(m, name_suffix: str = "_q8"):
    """Quantize a built ``JaxModel``'s params in place of a float build:
    same apply/spec, int8 ``"w"`` leaves, ``name + suffix``.  The shared
    implementation behind the SSD/posenet/transformer/ViT
    ``build_quantized`` delegates (mobilenet_v2 keeps its own multi-tier
    builder — int8_convs/int8_head combinations).  The forward must
    already dispatch on the leaf type (``int8=`` conv flags or
    ``transformer._proj``)."""
    from ..backends.jax_backend import JaxModel

    return JaxModel(
        apply=m.apply,
        params=quantize_params(m.params),
        input_spec=m.input_spec,
        name=m.name + name_suffix,
    )


def matmul_int8(x, qw: QuantizedWeight, dtype=jnp.float32):
    """W8A8 matmul on the MXU: ``(..., d) @ (d, dout)`` with int8 operands
    and int32 accumulation.

    Activations quantize dynamically with **per-row** scales (one scale
    per token/sample — ``axes=(-1,)``), the finer-grained sibling of
    :func:`~nnstreamer_tpu.models.layers.conv2d_int8`'s per-sample scales:
    a transformer batch mixes tokens of very different magnitude, and one
    outlier token must not coarsen the whole batch.  The int32 result
    rescales by ``row_scale * per-channel weight scale`` in the epilogue.
    v5e executes int8 at 2x the bf16 rate."""
    import jax

    q, s = quantize_activations(x, axes=(-1,))          # s: (..., 1)
    y = jax.lax.dot_general(
        q, qw.q,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    rescale = (s * qw.scale.reshape(-1)).astype(jnp.float32)  # (..., dout)
    return (y.astype(jnp.float32) * rescale).astype(dtype)


def quantize_activations(x, dtype=jnp.int8, axes=None):
    """Dynamic symmetric activation quantization.

    Returns ``(q, scale)`` with ``q ≈ x / scale`` in int8.  Computed on
    device; fuses into the producing XLA program.

    ``axes=None``: one per-tensor scale (scalar).  ``axes=(1, 2, 3)`` on an
    NHWC batch: one scale **per sample** (shape ``(N, 1, 1, 1)``) — in
    batched serving a single outlier frame must not coarsen quantization
    for the rest of the batch, and a frame's numerics must not depend on
    which other frames it happened to be batched with.
    """
    if axes is None:
        amax = jnp.max(jnp.abs(x))
    else:
        amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(dtype)
    return q, scale


def quantize_static(x, scale, dtype=jnp.int8):
    """Quantize with a FIXED (calibrated) scale.

    Unlike :func:`quantize_activations`, there is no ``max(|x|)``
    reduction: the op is purely elementwise, so XLA fuses it into the
    producing conv's epilogue — zero extra HBM passes.  The dynamic
    per-sample reduce was the measured reason the full-int8 tier lost to
    float end-to-end on chip in round 4 (0.6x) despite the int8 kernels
    themselves winning 3.56x: ~35 convs × (max-reduce pass + quantize
    pass) of activation traffic per frame."""
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(dtype)


# -- static-scale calibration (the reference's uint8 flagship uses fixed
# scales the same way: tflite bakes activation ranges at conversion time,
# ``tests/nnstreamer_filter_tensorflow_lite/runTest.sh:30-38``) -----------

# Thread-LOCAL, not a process global (ADVICE r5 #1): calibration on one
# thread must never flip another thread's int8 convs into the eager
# recording branch — under jit that raises ConcretizationTypeError in the
# victim thread; eagerly it silently pollutes the other model's
# act_scale leaves.
_CALIBRATING = threading.local()


def is_calibrating() -> bool:
    return getattr(_CALIBRATING, "active", False)


@contextmanager
def calibration():
    """While active ON THIS THREAD, int8 convs run their dynamic path
    EAGERLY and record the raw running ``max(|activation|)/127`` into
    their own param dict as a float ``act_scale`` leaf (max over all
    samples seen; the zero-guard floor is applied once at the end of
    :func:`calibrate_static_scales`, never per sample)."""
    prev = getattr(_CALIBRATING, "active", False)
    _CALIBRATING.active = True
    try:
        yield
    finally:
        _CALIBRATING.active = prev


def calibrate_static_scales(apply_fn, params, samples, device=None):
    """Run ``apply_fn(params, x)`` eagerly over calibration ``samples``;
    every int8 conv annotates its param dict with a static ``act_scale``.

    Must run OUTSIDE jit (recording is a Python side effect).  Runs on the
    CPU backend by default: eager per-op dispatch pays one device round
    trip per op, and the recorded scales are values, not timings —
    platform-independent."""
    import jax

    if device is None:
        try:
            device = jax.devices("cpu")[0]
        except RuntimeError:
            device = None  # no cpu backend registered: use the default
    ctx = jax.default_device(device) if device is not None else None
    with calibration():
        if ctx is not None:
            with ctx:
                for x in samples:
                    apply_fn(params, jnp.asarray(x))
        else:
            for x in samples:
                apply_fn(params, jnp.asarray(x))
    _floor_act_scales(params)
    return params


def _floor_act_scales(tree) -> None:
    """Apply the zero-guard ONCE, after all samples: an ``act_scale``
    still 0.0 (every calibration sample was all-zero) floors to 1.0.
    Applying the floor per sample (ADVICE r5 #4) pinned the scale at
    >= 1.0 forever after one degenerate sample — ``max(1.0, real)``
    never shrinks — silently coarsening tensors whose true activation
    range is far below 1.0."""
    if isinstance(tree, dict):
        v = tree.get("act_scale")
        if isinstance(v, (int, float)) and not v:
            tree["act_scale"] = 1.0
        for child in tree.values():
            _floor_act_scales(child)
    elif isinstance(tree, (list, tuple)):
        for child in tree:
            _floor_act_scales(child)
