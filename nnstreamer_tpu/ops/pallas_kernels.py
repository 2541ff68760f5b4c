"""Hand-written Pallas TPU kernels.

Two kernels where explicit control pays over letting XLA schedule:

- :func:`fused_arith` — one VPU pass applying a whole ``tensor_transform``
  arithmetic chain (typecast/add/sub/mul/div/clamp) tile by tile.  This is
  the direct analog of the reference's generated Orc SIMD kernels
  (``transform-orc.orc``, ``tensor_transform.c:330-405``): the acceleration
  backend behind ``tensor_transform acceleration=pallas``.
- :func:`int8_matmul` — quantized matmul on the MXU: int8×int8 operands,
  int32 accumulation, fused per-column dequant + bias.  The TPU-native
  equivalent of the reference's uint8-quantized tflite CPU kernels
  (survey §7 hard part f).

Off-TPU (tests run on the virtual CPU mesh) the kernels execute in Pallas
interpret mode, so behavior is platform-independent.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
# Row block: a multiple of every dtype's min sublane count (8/16/32).
BLOCK_ROWS = 256


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _cast(x, dtype):
    """astype with Mosaic-safe routing: narrow uints → float lowers via
    int32 (the direct cast is unsupported in-kernel on TPU)."""
    dtype = jnp.dtype(dtype)
    if (
        jnp.issubdtype(x.dtype, jnp.unsignedinteger)
        and x.dtype.itemsize < 4
        and jnp.issubdtype(dtype, jnp.floating)
    ):
        x = x.astype(jnp.int32)
    return x.astype(dtype)


def _apply_chain(x, ops: Sequence[Tuple[str, object]]):
    """The op chain, shared by kernel body and reference path."""
    for op, val in ops:
        if op == "typecast":
            x = _cast(x, val)
        elif op == "add":
            x = x + val
        elif op == "sub":
            x = x - val
        elif op == "mul":
            x = x * val
        elif op == "div":
            x = x / val
        elif op == "clamp":
            lo, hi = val
            x = jnp.clip(x, lo, hi)
        else:
            raise ValueError(f"unknown chain op {op!r}")
    return x


def chain_out_dtype(in_dtype, ops: Sequence[Tuple[str, object]]):
    """Result dtype of a chain (numpy promotion rules, as the jit path)."""
    probe = jnp.zeros((1,), in_dtype)
    return jax.eval_shape(lambda x: _apply_chain(x, tuple(ops)), probe).dtype


def fused_arith(x, ops: Sequence[Tuple[str, object]], interpret: Optional[bool] = None):
    """Apply an arithmetic chain in a single Pallas pass.

    Accepts any shape/dtype; the array is viewed as a padded (rows, 128)
    grid and processed BLOCK_ROWS rows per program instance.
    """
    ops = tuple(ops)
    if interpret is None:
        interpret = _interpret()
    out_dtype = chain_out_dtype(x.dtype, ops)
    shape = x.shape
    n = int(x.size)
    if n == 0:
        return jnp.zeros(shape, out_dtype)

    tile = BLOCK_ROWS * LANES
    n_pad = -n % tile
    flat = jnp.ravel(x)
    if n_pad:
        flat = jnp.concatenate([flat, jnp.zeros((n_pad,), x.dtype)])
    rows = flat.size // LANES
    grid = rows // BLOCK_ROWS

    def kernel(in_ref, out_ref):
        x = in_ref[:]
        # When the chain will promote a narrow integer (implicitly, via a
        # float op value), promote through int32 up front: Mosaic cannot
        # lower narrow-int → float casts mid-expression.
        if (
            x.dtype != out_dtype
            and jnp.issubdtype(x.dtype, jnp.integer)
            and x.dtype.itemsize < 4
        ):
            x = x.astype(jnp.int32)
        out_ref[:] = _cast(_apply_chain(x, ops), out_dtype)

    out = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), out_dtype),
        interpret=interpret,
    )(flat.reshape(rows, LANES))
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("interpret", "block_m", "block_n"))
def int8_matmul(
    x_q,
    w_q,
    x_scale,
    w_scale,
    bias=None,
    interpret: Optional[bool] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
):
    """``(x_q · w_q) * (x_scale * w_scale) + bias`` on the MXU.

    x_q: (M, K) int8; w_q: (K, N) int8; x_scale: scalar f32 (per-tensor
    dynamic activation scale); w_scale: (1, N) f32 (per-output-channel);
    bias: (N,) f32 or None.  Returns (M, N) float32.  K rides whole into
    VMEM (fine for classifier-head sizes; block over K before reusing this
    for giant matmuls).

    Default tiles are adaptive: a persistent autotune winner for this
    exact ``(m, k, n)`` on this platform when one exists
    (:mod:`nnstreamer_tpu.ops.autotune` — the tuned int8 tile
    split survives process restarts; consulted at TRACE time, zero
    per-dispatch cost), else the whole M dim in one block when it fits a
    VMEM budget (classifier heads have small M — one pass over the
    weight stream, no re-fetch per row block), N in 256-lane stripes.
    """
    if interpret is None:
        interpret = _interpret()
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2, (x_q.shape, w_q.shape)
    if block_m is None and block_n is None:
        from .autotune import cached_int8_blocks

        block_m, block_n = cached_int8_blocks(m, k, n)
    if block_m is None:
        if m <= 256:
            # whole-M single block, rounded up to the int8 sublane tile
            # (32): x block ≤ 256×K int8 (K=1280 → 320 KB of VMEM)
            block_m = max(32, -(-m // 32) * 32)
        else:
            block_m = 128  # row stripes; ≤127 padded rows
    if block_n is None:
        block_n = 256 if n >= 256 else 128

    m_pad = -m % block_m
    n_pad = -n % block_n
    if m_pad:
        x_q = jnp.pad(x_q, ((0, m_pad), (0, 0)))
    if n_pad:
        w_q = jnp.pad(w_q, ((0, 0), (0, n_pad)))
        w_scale = jnp.pad(w_scale, ((0, 0), (0, n_pad)))
    mp, np_ = m + m_pad, n + n_pad
    if bias is None:
        bias = jnp.zeros((n,), jnp.float32)
    bias2 = jnp.pad(bias, (0, n_pad)).reshape(1, np_)
    xs = jnp.asarray(x_scale, jnp.float32).reshape(1, 1)

    def kernel(x_ref, w_ref, xs_ref, ws_ref, b_ref, out_ref):
        acc = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.int32)
        out_ref[:] = (
            acc.astype(jnp.float32) * (xs_ref[0, 0] * ws_ref[:]) + b_ref[:]
        )

    out = pl.pallas_call(
        kernel,
        grid=(mp // block_m, np_ // block_n),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(x_q, w_q, xs, w_scale, bias2)
    return out[:m, :n]
