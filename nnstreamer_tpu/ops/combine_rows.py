"""The way back of a share's expert pass as a banded one-hot product.

A pass of ``parallel/moe._routed_share`` leaves ``rows`` result rows in the
experts' order, each some token's: ``out[token[j]] += w[j] * y[j]``.  XLA's
scatter-add takes the rows one at a time; this module is the same sum at the
MXU's speed:

- the pass's rows are sorted by token (``rows`` keys) and ``y`` is gathered
  into that order (``rows`` row reads, whatever the number of pairs routed),
  so that the rows of a block of ``token_block`` tokens lie together;
- a grid step (a *visit*, ``ops/grouped_experts.visits``: the token blocks
  are its groups) takes one token block and one tile of ``row_tile`` sorted
  rows that holds rows of it, builds ``W[t, j] = w[j] * (token[j] == t)`` in
  the rows' type and adds ``W @ y_tile`` to the block in float32.  A block's
  visits follow each other, so its float32 rows stay in VMEM between them;
- ``out`` is the kernel's input and its output (aliased): a visit's first
  step of a block starts from the block's rows as they came, and a block no
  row of the pass belongs to is never visited and keeps them.

A product of the rows' type times the rows' type is exact in float32 and
the sums are float32, as the scatter-add's.  ``parallel/moe.py``'s lowering
rule takes this form where :func:`tiles` holds; called directly off-TPU the
kernel executes in Pallas interpret mode (the tests).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_experts import visits
from .pallas_kernels import LANES, _interpret

KERNEL_NAME = "nns_combine_rows"
# Tokens a block and sorted rows a tile.  On the v5e, a pass of 8192 rows
# into 8192 tokens (host clock, even load / every row live; d 7168, 6144):
# 128 x 128 2.34 / 2.40, 2.09 / 2.20 ms; 256 x 256 2.36 / 3.03, 2.11 / 2.11;
# 512 x 512 2.33 / 2.37, 2.08 / 2.15; by device time 128 and 256 read alike
# (1.69, 1.67 at 7168).  The smallest: a visit's blocks are 22 MB at 7168.
TOKEN_BLOCK = 128
ROW_TILE = 128
VMEM_BUDGET = 64 * 2 ** 20
VMEM_LIMIT = 96 * 2 ** 20


def vmem_bytes(d: int, itemsize: int) -> int:
    """VMEM one visit needs at the module's tiles: the row tile, the block's
    float32 rows in and out, all double buffered, and the product."""
    return 2 * ROW_TILE * d * itemsize + 5 * TOKEN_BLOCK * d * 4


def tiles(out_shape, rows: int, dtype) -> bool:
    """Whether the kernel is the lowering for ``rows`` rows of ``dtype`` into
    ``out_shape`` ``[n, d]``: bf16 or f32, ``d`` whole lane tiles, whole
    token blocks and row tiles, a visit within :data:`VMEM_BUDGET`."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    n, d = out_shape
    return (d % LANES == 0 and n % TOKEN_BLOCK == 0 and rows % ROW_TILE == 0
            and rows > 0 and vmem_bytes(d, dtype.itemsize) <= VMEM_BUDGET)


def _kernel(offsets_ref, block_ref, tile_ref, count_ref, token_ref, weight_ref,
            rows_ref, carry_ref, out_ref):
    v = pl.program_id(0)
    b = block_ref[v]

    @pl.when((v == 0) | (block_ref[jnp.maximum(v - 1, 0)] != b))
    def _first():
        out_ref[...] = carry_ref[...]

    @pl.when(v < count_ref[0])
    def _visit():
        # nobody's rows lie behind every token's and may hold anything
        tb, tr = out_ref.shape[0], rows_ref.shape[0]
        at = tile_ref[v] * tr + jax.lax.broadcasted_iota(
            jnp.int32, (tr, 1), 0)
        live = offsets_ref[offsets_ref.shape[0] - 1]
        rows = jnp.where(at < live, rows_ref[...], 0)
        tokens = b * tb + jax.lax.broadcasted_iota(jnp.int32, (tb, tr), 0)
        onehot = jnp.where(token_ref[...] == tokens, weight_ref[...],
                           0.0).astype(rows.dtype)
        out_ref[...] += jnp.dot(
            onehot, rows, preferred_element_type=jnp.float32,
            precision=(jax.lax.Precision.HIGHEST
                       if rows.dtype == jnp.float32 else None))


def combine_rows(out, y, token, w, token_block: Optional[int] = None,
                 row_tile: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """``out[token[j]] += w[j] * y[j]`` over the rows ``j`` of ``y`` ``[rows,
    d]``; ``token[j] == n`` says row ``j`` is nobody's.  ``out``: ``[n, d]``
    float32, ``token``: ``[rows]`` int32, ``w``: ``[rows]``, taken in ``y``'s
    type.  ``n`` in whole token blocks, ``rows`` in whole row tiles."""
    n, d = out.shape
    rows = y.shape[0]
    if interpret is None:
        interpret = _interpret()
    tb, tr = token_block or TOKEN_BLOCK, row_tile or ROW_TILE
    if n % tb or rows % tr:
        raise ValueError(f"{n} tokens and {rows} rows in blocks of {tb} and "
                         f"{tr}")
    token, order, w = jax.lax.sort(
        (token.astype(jnp.int32), jnp.arange(rows, dtype=jnp.int32),
         w.astype(jnp.float32)), num_keys=1, is_stable=True)
    sizes = jnp.diff(jnp.searchsorted(
        token, jnp.arange(0, n + 1, tb, dtype=jnp.int32))).astype(jnp.int32)
    offsets, block, tile, count = visits(sizes, rows, tr)
    tile = jnp.maximum(tile, 0)                 # no row at all: no visit
    lane_major = pl.BlockSpec((1, tr), lambda v, o, b, t, c: (0, t[v]))
    block_rows = pl.BlockSpec((tb, d), lambda v, o, b, t, c: (b[v], 0))
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(block.shape[0],),
            in_specs=[
                lane_major, lane_major,
                pl.BlockSpec((tr, d), lambda v, o, b, t, c: (t[v], 0)),
                block_rows,
            ],
            out_specs=block_rows),
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # a block's visits are in turn
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * block.shape[0] * tb * tr * d, transcendentals=0,
            bytes_accessed=rows * d * y.dtype.itemsize + 8 * n * d),
        interpret=interpret,
        name=KERNEL_NAME,
    )(offsets, block, tile, count, token.reshape(1, rows), w.reshape(1, rows),
      y[order], out)
