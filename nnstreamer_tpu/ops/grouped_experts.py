"""The routed experts as one grouped kernel: the hidden rows never leave the
chip.

``parallel/moe.routed_experts`` sorts a chunk's (token, expert) pairs by
expert, so that each expert's rows lie together in one ``[pairs, d]`` array.
Through XLA the experts' SwiGLU is then two ``ragged_dot`` products with a
fusion between them, and the ``[pairs, 2f]`` gate|up rows and the ``[pairs,
f]`` hidden rows go out to HBM and come back.  This kernel makes one pass
over the sorted rows:

- a grid step (a *visit*) takes one tile of ``tile_rows`` rows and one
  expert.  Which ones comes from scalar-prefetched metadata made from the
  group sizes (:func:`visits`): a tile that lies inside one expert's rows is
  visited once, a tile that straddles experts once per expert, its visits
  one after the other so that the output tile stays in VMEM between them and
  each stores only its own expert's rows (megablox's scheme).  The grid has
  ``tiles + experts - 1`` steps, the most there can be; the unused ones at the
  end repeat the last visit's blocks and do nothing;
- the expert's ``w_in[e]`` ``[d, 2f]`` and ``w_out[e]`` ``[f, d]`` are blocks
  picked by the visit's expert, so they stay in VMEM over the expert's
  consecutive visits and the next expert's are fetched behind the last one;
- a tile is worked in blocks of ``block_rows`` rows, and a block none of whose
  rows are the visit's expert's is skipped, so a straddled tile costs a block
  more than its rows, not a tile more.  Per block ``gate|up = x @ w_in[e]`` in
  float32, ``silu(gate) * up`` cast to the rows' type, ``@ w_out[e]`` in
  float32, each row times its pair's weight in float32, then the cast;
- the pairs' weights come as a lane-major ``[tiles, tile_rows]`` float32
  array, eight tiles a block; a tile's row of it is turned into a column by a
  masked lane reduction (Mosaic has no cheap 1-D to sublane reshape).

``parallel/moe.py`` holds the primitive whose TPU lowering takes this kernel
where :func:`tiles` holds.  Called directly off-TPU the kernel executes in
Pallas interpret mode (the tests), like ``ops/fused_attention.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import LANES, _interpret

KERNEL_NAME = "nns_grouped_experts"
# Rows a visit takes and rows a product inside it takes.  On the v5e at
# 262 144 rows of 2048 over 256 experts of width 512, a router's uneven groups
# (941-1101 rows): 512 x 128 ran fastest, 11.3 ms (128 x 128 12.3, 256 x 128
# 11.7, 512 x 256 11.8, 512 x 512 13.4, 1024 x 128 11.8, 512 x 64 11.7; XLA's
# two ragged dots and the fusion between them 18.9).  On even groups of 1024
# every tile of 512 or more reads 9.0-9.3 ms (the products alone are 8.4 ms at
# the chip's peak): what uneven groups add is the block each group's edge
# splits, worked twice, so a small block under a large tile.
TILE_ROWS = 512
BLOCK_ROWS = 128
# What one visit may hold by vmem_bytes()'s count; Mosaic is given
# VMEM_LIMIT, because it keeps more than one block's temporaries alive across
# the unrolled loop.  A v5e core has 128 MiB.
VMEM_BUDGET = 40 * 2 ** 20
VMEM_LIMIT = 96 * 2 ** 20
# the weights' rows are fetched eight tiles at a time: float32's sublanes
WEIGHT_TILES = 8


def vmem_bytes(d: int, f: int, itemsize: int) -> int:
    """VMEM one visit needs at the module's tile: one expert's ``w_in`` and
    ``w_out`` and the row and output tiles, all double buffered, and a
    block's float32 gate|up and output rows and its hidden rows."""
    return (2 * 3 * d * f * itemsize + 2 * 2 * TILE_ROWS * d * itemsize
            + BLOCK_ROWS * (2 * f * 4 + f * itemsize + d * 4)
            + 2 * WEIGHT_TILES * TILE_ROWS * 4)


def tiles(rows_shape, w_in_shape, w_out_shape, dtype) -> bool:
    """Whether the kernel is the lowering for sorted rows ``[pairs, d]`` over
    ``w_in`` ``[E, d, 2f]`` and ``w_out`` ``[E, f, d]``, all of ``dtype``:
    bf16 or f32, ``d`` and ``f`` whole lane tiles, one expert's weights twice
    over beside the tiles within :data:`VMEM_BUDGET`, and a mean of at least
    one row tile an expert (below that most of a visit's rows are another
    expert's, and XLA's ``ragged_dot`` is the better product)."""
    if len(rows_shape) != 2 or len(w_in_shape) != 3 or len(w_out_shape) != 3:
        return False
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    (m, d), e, f = rows_shape, w_in_shape[0], w_out_shape[1]
    return (tuple(w_in_shape) == (e, d, 2 * f)
            and tuple(w_out_shape) == (e, f, d)
            and d % LANES == 0 and f % LANES == 0
            and vmem_bytes(d, f, dtype.itemsize) <= VMEM_BUDGET
            and m >= e * TILE_ROWS)


def visits(sizes, m: int, tile_rows: int):
    """The grid's metadata from the group sizes ``[E]`` (which sum to ``m``):
    ``offsets`` ``[E + 1]``, the row each expert's group starts at; per grid
    step its ``expert`` and its ``tile``; and ``count`` ``[1]``, the steps
    that are visits.  An expert with rows visits every tile that holds one of
    them, in order; an expert without is never visited.  The steps past
    ``count`` repeat the last visit, so that they move no block."""
    e = sizes.shape[0]
    n_tiles = -(-m // tile_rows)
    steps = n_tiles + e - 1
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = offsets[:-1] // tile_rows
    per_expert = jnp.where(sizes > 0, -(-ends // tile_rows) - first, 0)
    upto = jnp.cumsum(per_expert)            # visits of experts 0..e inclusive
    count = upto[-1]
    step = jnp.minimum(jnp.arange(steps, dtype=jnp.int32), count - 1)
    expert = jnp.searchsorted(upto, step, side="right").astype(jnp.int32)
    tile = first[expert] + step - (upto - per_expert)[expert]
    return offsets, expert, tile.astype(jnp.int32), count.reshape(1)


def _column(row):
    """``[1, n]`` -> ``[n, 1]``: lane ``i`` to sublane ``i``."""
    n = row.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _kernel(offsets_ref, expert_ref, tile_ref, count_ref, rows_ref, w_in_ref,
            w_out_ref, weight_ref, out_ref, *, tm: int, tb: int, f: int):
    v = pl.program_id(0)

    @pl.when(v < count_ref[0])
    def _visit():
        e, t = expert_ref[v], tile_ref[v]
        lo, hi = offsets_ref[e], offsets_ref[e + 1]  # the expert's rows
        weights = _column(weight_ref[pl.ds(t % WEIGHT_TILES, 1), :])  # [tm, 1]
        for j in range(tm // tb):  # static: a block is a sublane slice
            at = slice(j * tb, (j + 1) * tb)
            r0 = t * tm + j * tb

            @pl.when((r0 < hi) & (r0 + tb > lo))
            def _block(at=at, r0=r0):
                x = rows_ref[at, :]
                gate_up = jnp.dot(x, w_in_ref[...],
                                  preferred_element_type=jnp.float32)
                h = (jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]).astype(
                    x.dtype)
                out = jnp.dot(h, w_out_ref[...],
                              preferred_element_type=jnp.float32)
                out = (out * weights[at, :]).astype(out_ref.dtype)
                whole = (r0 >= lo) & (r0 + tb <= hi)

                @pl.when(whole)
                def _():
                    out_ref[at, :] = out

                @pl.when(jnp.logical_not(whole))
                def _():  # the other rows are another visit's
                    r = r0 + jax.lax.broadcasted_iota(jnp.int32, (tb, 1), 0)
                    out_ref[at, :] = jnp.where((r >= lo) & (r < hi), out,
                                               out_ref[at, :])


def grouped_experts(rows, w_in, w_out, sizes, pair_weights,
                    tile_rows: Optional[int] = None,
                    block_rows: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Every row of ``rows`` ``[m, d]`` through its expert's SwiGLU and times
    its weight: row ``r`` belongs to expert ``e`` where ``sizes[:e].sum() <=
    r < sizes[:e + 1].sum()`` (``sizes`` ``[E]`` int32 sums to ``m``), and the
    result's row is ``(silu(gate) * up) @ w_out[e] * pair_weights[r]`` with
    ``gate | up = rows[r] @ w_in[e]``.  ``w_in``: ``[E, d, 2f]``, ``w_out``:
    ``[E, f, d]``, ``pair_weights``: ``[m]`` float32.  ``[m, d]`` in ``rows``'
    type; products and the scaling accumulate in float32.  ``m`` need not be
    a multiple of the tile: the last tile is cut off at the array's end."""
    m, d = rows.shape
    f = w_out.shape[1]
    if interpret is None:
        interpret = _interpret()
    tm = tile_rows or TILE_ROWS
    tb = min(block_rows or BLOCK_ROWS, tm)
    if tm % tb:
        raise ValueError(f"a tile of {tm} rows is no whole number of blocks "
                         f"of {tb}")
    offsets, expert, tile, count = visits(sizes, m, tm)
    weights = jnp.pad(pair_weights.astype(jnp.float32), (
        0, -m % (WEIGHT_TILES * tm))).reshape(-1, tm)
    itemsize = jnp.dtype(rows.dtype).itemsize
    row_tile = pl.BlockSpec((tm, d), lambda v, o, e, t, c: (t[v], 0))
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tb=tb, f=f),
        out_shape=jax.ShapeDtypeStruct((m, d), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(expert.shape[0],),
            in_specs=[
                row_tile,
                pl.BlockSpec((None, d, 2 * f),
                             lambda v, o, e, t, c: (e[v], 0, 0)),
                pl.BlockSpec((None, f, d), lambda v, o, e, t, c: (e[v], 0, 0)),
                pl.BlockSpec((WEIGHT_TILES, tm),
                             lambda v, o, e, t, c: (t[v] // WEIGHT_TILES, 0)),
            ],
            out_specs=row_tile),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # a tile's visits are in turn
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=6 * m * d * f, transcendentals=m * f,
            bytes_accessed=(2 * m * d + w_in.shape[0] * 3 * d * f) * itemsize),
        interpret=interpret,
        name=KERNEL_NAME,
    )(offsets, expert, tile, count, rows, w_in, w_out, weights)
