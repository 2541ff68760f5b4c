"""Fused attention: the ``T x T`` scores never leave the chip.

XLA lowers ``full_attention`` to three fusions per layer that write the
scores to HBM and read them back, with a split of the fused ``qkv``
projection and a transpose to head-major in front.  At the sequence lengths
of a vision tower a head's whole score matrix fits in VMEM many times over,
so this kernel takes the projection ``[B, T, 3*d]`` as the matmul left it
and returns ``[B, T, d]``, token-major on both sides:

- one grid step handles one batch row and one *head group*: the fewest heads
  whose joint width is a whole number of 128-lane tiles (4 heads of 96, 2 of
  64, 1 of 128).  The group's q, k and v are three column blocks of the same
  ``qkv`` array, picked by the ``BlockSpec`` index maps, and its output is
  the same column block of the result: no split, no transpose, no copy back;
- per head ``QK^T`` goes to float32 scores in VMEM, max / exp / row sum run
  in float32 (v5e has no bf16 VPU or EUP), ``P`` is cast to the input dtype
  for ``PV`` with float32 accumulation, and the ``[T, dh]`` result — not the
  ``[T, T]`` matrix — is divided by the row sum.  ``dh**-0.5`` is folded into
  q.  A whole row of scores is resident, so there is no online softmax and no
  loop over K blocks; :func:`tiles` is what says when that holds.

``models/transformer.py`` calls :func:`attention`, one primitive with two
lowerings.  Which one a call gets is decided when its program is lowered,
from what it is lowered for: the kernel for a TPU program that runs on one
device (or is the all-manual body of a ``shard_map``) where :func:`tiles`
holds, ``parallel.ring_attention.full_attention`` through XLA everywhere else
— a CPU program on a TPU host, a program GSPMD partitions over a mesh, a shape
the kernel does not tile.  ``full_attention`` stays the reference.  Called
directly off-TPU, :func:`fused_attention` executes in Pallas interpret mode
(the tests), like ``ops/pallas_kernels.py``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir

from .pallas_kernels import LANES, _interpret

KERNEL_NAME = "nns_fused_attention"
# What one grid step may hold by vmem_bytes()'s count: double-buffered
# q/k/v/o blocks plus one head's scores, exponentials and P.  Mosaic is given
# twice that (VMEM_LIMIT) because it may keep two heads' temporaries alive
# across the unrolled loop; a v5e core has 128 MiB.
VMEM_BUDGET = 12 * 2 ** 20
VMEM_LIMIT = 2 * VMEM_BUDGET
# Head widths are multiples of this: the lane-unaligned slices of 32, 64 and
# 96 out of a group block are what Mosaic has compiled and the chip has
# checked against full_attention (and timed, at 96).
HEAD_WIDTH_STEP = 32
# The shortest sequence the kernel is chosen for.  On the v5e at batch 48 it
# ran 1.9-2.5 times as fast as XLA's fusions at 384 tokens for every head
# width timed, level with them at 256 and behind at 196 (a ViT-B/16 at 224),
# where a grid step's fixed cost outweighs the scores' trip through HBM.
MIN_TOKENS = 384
# A finite stand-in for -inf under the causal mask: exp() of it is 0 and no
# inf - inf can arise.
MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def head_group(head_width: int) -> int:
    """The fewest heads whose joint width is a multiple of 128 lanes."""
    return math.lcm(head_width, LANES) // head_width


def vmem_bytes(t: int, head_width: int, itemsize: int) -> int:
    """VMEM one grid step needs: four ``[T, group width]`` blocks, double
    buffered, and one head's ``[T, T]`` scores and exponentials in float32
    and P in the operands' type, their rows padded to whole lane tiles."""
    width = head_group(head_width) * head_width
    row = -(-t // LANES) * LANES
    return 4 * 2 * t * width * itemsize + t * row * (4 + 4 + itemsize)


def tiles(shape, dtype, n_heads: int) -> bool:
    """Whether the kernel is the lowering for a ``qkv`` of this shape and
    type: bf16 or f32, the head width a multiple of 32 whose head group
    divides the heads, at least :data:`MIN_TOKENS` tokens and a whole row of
    scores within :data:`VMEM_BUDGET`.  T need not tile: Mosaic pads and
    masks it."""
    if len(shape) != 3 or shape[-1] % (3 * n_heads):
        return False
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    t, dh = shape[1], shape[-1] // (3 * n_heads)
    return (dh % HEAD_WIDTH_STEP == 0 and n_heads % head_group(dh) == 0
            and t >= MIN_TOKENS
            and vmem_bytes(t, dh, dtype.itemsize) <= VMEM_BUDGET)


def _kernel(q_ref, k_ref, v_ref, o_ref, *, heads: int, dh: int, causal: bool):
    t = q_ref.shape[1]
    scale = dh ** -0.5
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        masked = col > row
    for i in range(heads):  # static: a head is a lane slice of the block
        at = slice(i * dh, (i + 1) * dh)
        q = q_ref[0, :, at] * scale  # a weak scalar: q keeps its type
        s = jax.lax.dot_general(q, k_ref[0, :, at], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = jnp.where(masked, MASKED, s)
        e = jnp.exp(s - s.max(axis=-1, keepdims=True))
        v = v_ref[0, :, at]
        o = jnp.dot(e.astype(v.dtype), v, preferred_element_type=jnp.float32)
        o_ref[0, :, at] = (o / e.sum(axis=-1, keepdims=True)).astype(o_ref.dtype)


def fused_attention(qkv, n_heads: int, causal: bool = False,
                    interpret: Optional[bool] = None):
    """Softmax attention over the fused projection.

    ``qkv``: ``[B, T, 3*d]``, the columns q, k, v in turn, each ``n_heads``
    heads of width ``d / n_heads`` (what ``x @ w_qkv`` leaves).  Returns
    ``[B, T, d]`` in ``qkv``'s type.  The heads have to group into whole
    lane tiles; ``interpret`` defaults to interpret mode off-TPU.
    """
    b, t, d3 = qkv.shape
    d = d3 // 3
    dh = d // n_heads
    heads = head_group(dh)
    if d3 != 3 * n_heads * dh or n_heads % heads:
        raise ValueError(
            f"qkv {qkv.shape} with {n_heads} heads does not tile: head width "
            f"{dh} needs groups of {heads} heads")
    if interpret is None:
        interpret = _interpret()
    groups = n_heads // heads
    width = heads * dh

    def block(part: int):
        return pl.BlockSpec((1, t, width),
                            lambda i, g, part=part: (i, 0, part * groups + g))

    itemsize = jnp.dtype(qkv.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, dh=dh, causal=causal),
        out_shape=jax.ShapeDtypeStruct((b, t, d), qkv.dtype),
        grid=(b, groups),
        in_specs=[block(0), block(1), block(2)],
        out_specs=block(0),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * n_heads * t * t * dh,
            transcendentals=b * n_heads * t * t,
            bytes_accessed=4 * b * t * d * itemsize),
        interpret=interpret,
        name=KERNEL_NAME,
    )(qkv, qkv, qkv)


def plain_attention(qkv, n_heads: int, causal: bool = False):
    """``full_attention`` over the fused projection, as XLA lowers it: the
    split and the reshape to ``[B, T, H, Dh]`` it needs, and back."""
    from ..parallel.ring_attention import full_attention

    b, t, d3 = qkv.shape
    q, k, v = (a.reshape(b, t, n_heads, d3 // (3 * n_heads))
               for a in jnp.split(qkv, 3, axis=-1))
    return full_attention(q, k, v, causal=causal).reshape(b, t, d3 // 3)


# -- one primitive, two lowerings -------------------------------------------
#
# A trace does not know what it will be lowered for: the same jaxpr goes to
# the TPU, to the CPU under ``jax.default_device`` (the backend's
# ``cpu_fallback`` retry), or into a program GSPMD partitions because an
# input arrived with a NamedSharding.  So the choice is a lowering rule's.

attention_p = Primitive("nns_full_attention")


def attention(qkv, n_heads: int, causal: bool = False):
    """Softmax attention over the fused projection ``[B, T, 3*d]`` →
    ``[B, T, d]``; see the module's docstring for which lowering it gets."""
    return attention_p.bind(qkv, n_heads=n_heads, causal=causal)


attention_p.def_impl(jax.jit(attention, static_argnames=("n_heads", "causal")))
attention_p.def_abstract_eval(
    lambda qkv, **_: qkv.update(shape=(*qkv.shape[:-1], qkv.shape[-1] // 3)))


def _count_lowering(path: str) -> None:
    from ..obs.metrics import REGISTRY

    REGISTRY.counter(
        "nnstpu_attention_lowerings_total",
        "full-attention calls lowered into a program, by the path chosen "
        "(fused = the Pallas kernel, plain = full_attention through XLA)",
        labelnames=("path",),
    ).inc(path=path)


def _lower_plain(ctx, qkv, *, n_heads, causal):
    _count_lowering("plain")
    return mlir.lower_fun(lambda a: plain_attention(a, n_heads, causal),
                          multiple_results=False)(ctx, qkv)


def _on_one_device(axis_context) -> bool:
    """Mosaic's own condition for lowering a kernel (jax's
    ``tpu_custom_call``): the program is for one device, or it is the body
    of a ``shard_map`` over every axis of its mesh."""
    if hasattr(axis_context, "num_devices"):  # a jit's ShardingContext
        return axis_context.num_devices == 1
    mesh = getattr(axis_context, "mesh", None)  # shard_map's SPMDAxisContext
    return mesh is not None and (
        set(axis_context.manual_axes) | set(mesh.manual_axes)
        == set(mesh.axis_names))


def _lower_tpu(ctx, qkv, *, n_heads, causal):
    aval, = ctx.avals_in
    if not (tiles(aval.shape, aval.dtype, n_heads)
            and _on_one_device(ctx.module_context.axis_context)):
        return _lower_plain(ctx, qkv, n_heads=n_heads, causal=causal)
    _count_lowering("fused")
    return mlir.lower_fun(
        lambda a: fused_attention(a, n_heads, causal, interpret=False),
        multiple_results=False)(ctx, qkv)


# not cacheable: every call site is lowered, and counted, on its own
mlir.register_lowering(attention_p, _lower_plain, cacheable=False)
mlir.register_lowering(attention_p, _lower_tpu, platform="tpu",
                       cacheable=False)


def _jvp(primals, tangents, *, n_heads, causal):
    # derivatives are full_attention's: training runs the plain path
    (qkv,), (dqkv,) = primals, tangents
    return jax.jvp(lambda a: plain_attention(a, n_heads, causal),
                   (qkv,), (ad.instantiate_zeros(dqkv),))


def _batch(args, dims, *, n_heads, causal):
    # a mapped axis is more batch rows
    (qkv,), (dim,) = args, dims
    qkv = jnp.moveaxis(qkv, dim, 0)
    out = attention(qkv.reshape(-1, *qkv.shape[2:]), n_heads, causal)
    return out.reshape(*qkv.shape[:2], *out.shape[1:]), 0


ad.primitive_jvps[attention_p] = _jvp
batching.primitive_batchers[attention_p] = _batch
