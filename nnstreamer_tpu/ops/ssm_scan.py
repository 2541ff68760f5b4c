"""The chunked state-space scan of a Mamba-2 mixer (SSD), token-major.

Per head ``h`` of ``P`` channels reading B/C group ``g = h // (H / G)``
(``N`` state dims), with ``Δ_t`` the step and ``A_h < 0`` the decay rate,

    S_t = exp(Δ_t A_h) S_{t-1} + Δ_t x_t B_tᵀ        (S: P x N)
    y_t = S_t C_t + D_h x_t.

Over chunks of ``Q`` tokens the recurrence is three products (Dao and Gu
2024, "Transformers are SSMs", the SSD algorithm).  With ``a = cumsum(Δ A)``
inside a chunk and ``L[i, j] = exp(a_i - a_j)`` for ``j <= i`` (else 0):

- within the chunk, ``Y = ((C Bᵀ) ∘ L ∘ Δ_j) X``: the ``Q x Q`` Gram of C
  against B is a group's, shared by its heads;
- from the chunks before, ``Y += exp(a_i) · C S_inᵀ``;
- the state a chunk hands on, ``S_out = exp(a_Q) S_in + Xᵀ diag(Δ_j
  exp(a_Q - a_j)) B``.

``ssd_scan`` is one primitive, whose lowering ``ops/lowering.py`` chooses:
where the shapes :func:`scan_tiles` admits, the Pallas kernel
``nns_ssd_scan``; for every other program or width :func:`plain_scan`, the
same three products through XLA with the chunks' states carried by
``lax.scan``.  Both count
``nnstpu_ssm_scan_lowerings_total{path="kernel"|"plain"}`` as they lower,
and the kernel ``nnstpu_ssm_scan_bodies_total{body}`` too (below).

The kernel's grid runs (batch row, block of heads, chunk), the chunks in
order: a block's carried state ``[heads, P, N]`` stays in VMEM from one
chunk to the next in float32, so neither the chunks' states nor their
``Q x Q`` decay masks reach HBM.  It has two bodies, chosen by the head's
width ``P``: heads of whole lane tiles are taken one at a time, each its own
``[Q, P]`` slice of the block (*whole_tile*); heads narrower than a tile
(``P`` divides 128) share one, ``128 / P`` of them a tile (*shared_tile*):
each head's masked Gram multiplies the whole ``[Q, 128]`` tile of x and
keeps its own lanes of the product, and the decays, ``Δ`` and ``D`` are
chosen lane by lane, so no operand narrower than a tile is sliced; the
tile's heads carry their states stacked as one ``[128, N]`` float32 block,
a head's ``P`` rows each.  The products run on the MXU in the
operands' type with float32 accumulation; the segment sums are float32 and
their exponentials are taken in the kernel; ``Δ`` scales the products and
``D`` adds the skip there too.  The within-chunk ``cumsum(Δ A)`` is taken
through XLA in front (``[B, T, H]`` float32: 0.5 MB a window of 4096).

``low=True`` is the same scan with its carried state and its decays
(segment sums, their exponentials) rounded to bfloat16: the benchmark's
control, which has to come out not correct.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Primitive

from .fused_attention import MASKED
from .lowering import count, register
from .pallas_kernels import LANES, _interpret

KERNEL_NAME = "nns_ssd_scan"
# Heads a grid step takes, all of one B/C group (the whole group where it
# holds fewer): their Gram is computed once a step, their x and y blocks are
# that many lane tiles.
HEADS_PER_STEP = 8
VMEM_LIMIT = 32 * 2 ** 20
F32 = jnp.float32


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _low(a, low: bool):
    """``a`` rounded to bfloat16 and back where ``low`` (the control)."""
    return a.astype(jnp.bfloat16).astype(F32) if low else a


def chunk_cumsum(dt, A, chunk: int):
    """``cumsum(Δ A)`` within each chunk of ``chunk`` tokens: ``dt`` ``[B, T,
    H]`` (T a whole number of chunks), ``A`` ``[H]`` -> float32 ``[B, T,
    H]``."""
    b, t, h = dt.shape
    a = dt.astype(F32) * A.astype(F32)
    return jnp.cumsum(a.reshape(b, t // chunk, chunk, h), axis=2).reshape(
        b, t, h)


def _padded(x, dt, B, C, chunk: int):
    t = x.shape[1]
    tp = _round_up(t, chunk)
    if tp != t:  # Δ = 0 past the end: no decay, nothing added to the state
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, tp - t), (0, 0)))
                       for a in (x, dt, B, C))
    return x, dt, B, C


def plain_scan(x, dt, A, B, C, D, chunk: int, n_groups: int,
               low: bool = False):
    """The SSD scan through XLA: ``x`` ``[B, T, H * P]``, ``dt`` (Δ, after
    the softplus) ``[B, T, H]``, ``A``, ``D`` ``[H]``, ``B``, ``C`` ``[B, T,
    G * N]`` -> ``y`` ``[B, T, H * P]`` in ``x``'s type.  The products take
    their operands in ``x``'s type with float32 accumulation, as the
    kernel's do."""
    b, t, hp = x.shape
    h, g = dt.shape[-1], n_groups
    p, n, q = hp // h, B.shape[-1] // g, chunk
    x, dt, B, C = _padded(x, dt, B, C, q)
    c = x.shape[1] // q
    kind = x.dtype
    seg = _low(chunk_cumsum(dt, A, q), low).reshape(b, c, q, h)
    dt = dt.astype(F32).reshape(b, c, q, h)
    xs = x.reshape(b, c, q, h, p)
    bs = jnp.repeat(B.reshape(b, c, q, g, n), h // g, axis=3)
    cs = jnp.repeat(C.reshape(b, c, q, g, n), h // g, axis=3)
    causal = jnp.tril(jnp.ones((q, q), bool))
    seg_h = jnp.moveaxis(seg, 3, 2)                        # [b, c, h, q]
    decay = jnp.exp(jnp.where(causal, seg_h[..., :, None] - seg_h[..., None, :],
                              MASKED))
    gram = jnp.einsum("bcihn,bcjhn->bchij", cs, bs, preferred_element_type=F32)
    m = gram * _low(decay, low) * jnp.moveaxis(dt, 3, 2)[..., None, :]
    y = jnp.einsum("bchij,bcjhp->bcihp", m.astype(kind), xs,
                   preferred_element_type=F32)
    last = seg[:, :, -1:]                                  # [b, c, 1, h]
    w = _low(dt * jnp.exp(last - seg), low)
    xw = (xs.astype(F32) * w[..., None]).astype(kind)
    added = jnp.einsum("bcjhp,bcjhn->bchpn", xw, bs,
                       preferred_element_type=F32)
    keep = _low(jnp.exp(last[:, :, 0]), low)               # [b, c, h]
    state_type = jnp.bfloat16 if low else F32

    def step(state, inputs):
        add, k = inputs
        out = k[..., None, None] * state.astype(F32) + add
        return out.astype(state_type), state

    _, entering = jax.lax.scan(
        step, jnp.zeros((b, h, p, n), state_type),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(keep, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                # [b, c, h, p, n]
    y = y + jnp.einsum("bcihn,bchpn->bcihp", cs, entering.astype(kind),
                       preferred_element_type=F32) * jnp.exp(seg)[..., None]
    y = y + D.astype(F32)[:, None] * xs.astype(F32)
    return y.reshape(b, c * q, hp)[:, :t].astype(kind)


def scan_tiles(x_shape, dt_shape, b_shape, dtype, n_groups: int,
               chunk: int) -> bool:
    """Whether the kernel is the lowering for these shapes: bf16 or f32,
    heads of whole lane tiles or heads whose width divides a lane tile, so
    many that a step's fill whole tiles, state dims of whole lane tiles,
    chunks of whole lane tiles (a chunk's decays are rows of its lanes), and
    :data:`HEADS_PER_STEP` heads or a whole group a step."""
    if len(x_shape) != 3 or len(dt_shape) != 3 or len(b_shape) != 3:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
        return False
    h = dt_shape[-1]
    if n_groups <= 0 or h % n_groups or b_shape[-1] % n_groups:
        return False
    p, n = x_shape[-1] // h, b_shape[-1] // n_groups
    hb = _heads_per_step(h // n_groups)
    heads_fit = p % LANES == 0 or (LANES % p == 0 and hb * p % LANES == 0)
    return (x_shape[-1] == h * p and p > 0 and heads_fit
            and n % LANES == 0 and chunk % LANES == 0
            and (h // n_groups) % hb == 0)


def body(p: int) -> str:
    """The kernel's body for heads of ``p`` channels: ``whole_tile`` or
    ``shared_tile`` (the module's docstring)."""
    return "whole_tile" if p % LANES == 0 else "shared_tile"


def _heads_per_step(heads_of_group: int) -> int:
    return min(HEADS_PER_STEP, heads_of_group)


def _scan_kernel(x_ref, dt_ref, seg_ref, dtT_ref, segT_ref, d_ref, b_ref,
                 c_ref, o_ref, state_ref, *, hb: int, p: int, low: bool):
    block, chunk = pl.program_id(1), pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = x_ref.shape[1]
    cm, bm = c_ref[0], b_ref[0]                            # [q, n]
    gram = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)  # [q, q]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
              <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 0))
    dt_blk, seg_blk = dt_ref[0], seg_ref[0]                # [q, H]
    lane = jax.lax.broadcasted_iota(jnp.int32, dt_blk.shape, 1)
    d_lane = jax.lax.broadcasted_iota(jnp.int32, d_ref.shape, 1)
    for j in range(hb):  # static: a head is a lane tile of the block
        head = block * hb + j
        pick = lane == head
        dt_col = jnp.sum(jnp.where(pick, dt_blk, 0.0), axis=1, keepdims=True)
        seg_col = _low(jnp.sum(jnp.where(pick, seg_blk, 0.0), axis=1,
                               keepdims=True), low)        # [q, 1]
        dt_row = dtT_ref[0, pl.ds(head, 1), :]             # [1, q]
        seg_row = _low(segT_ref[0, pl.ds(head, 1), :], low)
        decay = _low(jnp.exp(jnp.where(causal, seg_col - seg_row, MASKED)),
                     low)
        xh = x_ref[0, :, j * p:(j + 1) * p]                # [q, p]
        y = jnp.dot((gram * decay * dt_row).astype(xh.dtype), xh,
                    preferred_element_type=F32)
        state = state_ref[j]                               # [p, n]
        y += jax.lax.dot_general(
            cm, state.astype(cm.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=F32) * jnp.exp(seg_col)
        skip = jnp.sum(jnp.where(d_lane == head, d_ref[...], 0.0), axis=1,
                       keepdims=True)                      # [1, 1]
        y += skip * xh.astype(F32)
        o_ref[0, :, j * p:(j + 1) * p] = y.astype(o_ref.dtype)
        last = seg_col[q - 1:q, :]                         # [1, 1]
        w = _low(dt_col * jnp.exp(last - seg_col), low)    # [q, 1]
        xw = (xh.astype(F32) * w).astype(xh.dtype)
        added = jax.lax.dot_general(xw, bm, (((0,), (0,)), ((), ())),
                                    preferred_element_type=F32)  # [p, n]
        keep = _low(jnp.exp(last), low)
        state_ref[j] = (keep * state.astype(F32) + added).astype(
            state_ref.dtype)


def _shared_tile_kernel(x_ref, dt_ref, seg_ref, dtT_ref, segT_ref, d_ref,
                        b_ref, c_ref, o_ref, state_ref, *, hb: int, p: int,
                        low: bool):
    """:func:`_scan_kernel`'s work for heads narrower than a lane tile:
    ``LANES // p`` heads a tile, each head's products over the whole tile
    and its lanes kept; ``state_ref[u]`` holds tile ``u``'s heads' states,
    ``p`` rows each."""
    block, chunk = pl.program_id(1), pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    q, per = x_ref.shape[1], LANES // p
    cm, bm = c_ref[0], b_ref[0]                            # [q, n]
    gram = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)  # [q, q]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
              <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 0))
    dt_blk, seg_blk = dt_ref[0], seg_ref[0]                # [q, H]
    lane = jax.lax.broadcasted_iota(jnp.int32, dt_blk.shape, 1)
    d_lane = jax.lax.broadcasted_iota(jnp.int32, d_ref.shape, 1)
    tile_lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // p
    tile_row = jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0) // p
    for u in range(hb * p // LANES):  # static: a lane tile of the block
        xt = x_ref[0, :, u * LANES:(u + 1) * LANES]        # [q, LANES]
        y = into = w = jnp.zeros((q, LANES), F32)
        skip = jnp.zeros((1, LANES), F32)
        keep = jnp.zeros((LANES, 1), F32)
        for s in range(per):  # static: the tile's heads, p lanes each
            head = block * hb + u * per + s
            pick = lane == head
            dt_col = jnp.sum(jnp.where(pick, dt_blk, 0.0), axis=1,
                             keepdims=True)
            seg_col = _low(jnp.sum(jnp.where(pick, seg_blk, 0.0), axis=1,
                                   keepdims=True), low)    # [q, 1]
            dt_row = dtT_ref[0, pl.ds(head, 1), :]         # [1, q]
            seg_row = _low(segT_ref[0, pl.ds(head, 1), :], low)
            decay = _low(jnp.exp(jnp.where(causal, seg_col - seg_row,
                                           MASKED)), low)
            mine = tile_lane == s                          # [1, LANES]
            y = jnp.where(mine, jnp.dot((gram * decay * dt_row).astype(
                xt.dtype), xt, preferred_element_type=F32), y)
            into = jnp.where(mine, jnp.exp(seg_col), into)
            last = seg_col[q - 1:q, :]                     # [1, 1]
            w = jnp.where(mine, _low(dt_col * jnp.exp(last - seg_col), low),
                          w)
            skip = jnp.where(mine, jnp.sum(jnp.where(
                d_lane == head, d_ref[...], 0.0), axis=1, keepdims=True),
                skip)
            keep = jnp.where(tile_row == s, _low(jnp.exp(last), low), keep)
        state = state_ref[u]                               # [LANES, n]
        y += jax.lax.dot_general(
            cm, state.astype(cm.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=F32) * into
        y += skip * xt.astype(F32)
        o_ref[0, :, u * LANES:(u + 1) * LANES] = y.astype(o_ref.dtype)
        xw = (xt.astype(F32) * w).astype(xt.dtype)
        added = jax.lax.dot_general(xw, bm, (((0,), (0,)), ((), ())),
                                    preferred_element_type=F32)  # [LANES, n]
        state_ref[u] = (keep * state.astype(F32) + added).astype(
            state_ref.dtype)


def ssd_scan_kernel(x, dt, A, B, C, D, chunk: int, n_groups: int,
                    low: bool = False, interpret: Optional[bool] = None):
    """The SSD scan as the Pallas kernel ``nns_ssd_scan`` (arguments as
    :func:`plain_scan`'s); off-TPU it runs in interpret mode (the tests)."""
    if interpret is None:
        interpret = _interpret()
    b, t, hp = x.shape
    h, g = dt.shape[-1], n_groups
    p, n, q = hp // h, B.shape[-1] // g, chunk
    hb = _heads_per_step(h // g)
    x, dt, B, C = _padded(x, dt, B, C, q)
    tp = x.shape[1]
    dt = dt.astype(F32)
    seg = chunk_cumsum(dt, A, q)
    dtT, segT = (jnp.swapaxes(a, 1, 2) for a in (dt, seg))   # [b, h, tp]
    per_group = (h // g) // hb
    x_spec = pl.BlockSpec((1, q, hb * p), lambda i, k, c: (i, c, k))
    col_spec = pl.BlockSpec((1, q, h), lambda i, k, c: (i, c, 0))
    row_spec = pl.BlockSpec((1, h, q), lambda i, k, c: (i, 0, c))
    bc_spec = pl.BlockSpec((1, q, n), lambda i, k, c: (i, c, k // per_group))
    chunks = tp // q
    itemsize = jnp.dtype(x.dtype).itemsize
    whole = body(p) == "whole_tile"
    # a unit of the carried state: a head, or a lane tile of heads
    units, rows = (hb, p) if whole else (hb * p // LANES, LANES)
    out = pl.pallas_call(
        functools.partial(_scan_kernel if whole else _shared_tile_kernel,
                          hb=hb, p=p, low=low),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(b, h // hb, chunks),
        in_specs=[x_spec, col_spec, col_spec, row_spec, row_spec,
                  pl.BlockSpec((1, h), lambda i, k, c: (0, 0)),
                  bc_spec, bc_spec],
        out_specs=x_spec,
        scratch_shapes=[pltpu.VMEM((units, rows, n),
                                   jnp.bfloat16 if low else F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=b * chunks * (2 * q * q * n * (h // hb)
                                + h * (2 * q * q * max(p, LANES)
                                       + 4 * q * n * p)),
            transcendentals=b * chunks * h * (q * q + 2 * q + 1),
            bytes_accessed=b * tp * (2 * hp * itemsize
                                     + 2 * g * n * itemsize * per_group
                                     + 4 * 4 * h)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(x, dt, seg, dtT, segT, D.astype(F32).reshape(1, h), B, C)
    return out[:, :t] if tp != t else out


# -- one primitive, its lowerings -------------------------------------------

ssm_scan_p = Primitive("nns_ssm_scan")


def ssd_scan(x, dt, A, B, C, D, chunk: int, n_groups: int = 1,
             low: bool = False):
    """``y`` ``[B, T, H * P]`` of the SSD scan over ``x`` ``[B, T, H * P]``,
    ``dt`` (Δ, after the softplus) ``[B, T, H]``, ``A`` (negative) and
    ``D`` ``[H]``, ``B`` and ``C`` ``[B, T, n_groups * N]``, in chunks of
    ``chunk`` tokens.  See the module's docstring for which lowering a call
    gets; ``low`` is the control's bfloat16 state and decays."""
    return ssm_scan_p.bind(x, dt, A, B, C, D, chunk=chunk, n_groups=n_groups,
                           low=low)


ssm_scan_p.def_impl(jax.jit(ssm_scan_p.bind,
                            static_argnames=("chunk", "n_groups", "low")))
ssm_scan_p.def_abstract_eval(lambda x, *_, **__: x)


def _count_lowering(path: str, kernel_body: Optional[str] = None) -> None:
    count("nnstpu_ssm_scan_lowerings_total",
          "state-space scans lowered into a program, by the path chosen "
          "(kernel = the chunked Pallas kernel nns_ssd_scan, plain = the "
          "same chunks through XLA)", path=path)
    if kernel_body is not None:
        count("nnstpu_ssm_scan_bodies_total",
              "state-space scans lowered to the kernel, by the body its "
              "heads took (whole_tile = a head of whole lane tiles at a "
              "time, shared_tile = heads narrower than a lane tile, two of "
              "64 channels a tile)", body=kernel_body)


def _lower_plain(avals, **kw):
    _count_lowering("plain")
    return functools.partial(plain_scan, **kw)


def _lower_kernel(avals, *, chunk, n_groups, low):
    x, dt, _, B, C, _ = avals
    if not (x.dtype == B.dtype == C.dtype
            and scan_tiles(x.shape, dt.shape, B.shape, x.dtype, n_groups,
                           chunk)):
        return None
    _count_lowering("kernel", body(x.shape[-1] // dt.shape[-1]))
    return functools.partial(ssd_scan_kernel, chunk=chunk, n_groups=n_groups,
                             low=low, interpret=False)


register(ssm_scan_p, _lower_plain, _lower_kernel)
