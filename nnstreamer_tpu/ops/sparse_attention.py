"""Latent attention, under a learned selection of keys or over all of them.

Two ops of one mechanism (multi-head latent attention whose softmax runs
over the ``top_k`` keys a small second attention, the *indexer*, scores
highest for each query):

- :func:`select_keys`: the indexer's scores ``I[t, s] = sum_h w[t, h] *
  relu(q_I[t, h] . k_I[s])`` over the causal pairs, float32, and of each
  query the ``top_k`` highest keys (every causal key while ``t < top_k``;
  among equal scores the earlier key), as a ``[B, T, T]`` int8 mask.  The
  mask is what later layers that share the selection are handed;
- :func:`latent_sparse_attention`: per head ``softmax_{s in S_t}((q_n . k_n
  + q_r . k_r) / sqrt(d)) v`` with the rotary key part ``k_r`` common to
  every head, over exactly the selected keys.

Both walk the queries in row blocks, so that neither the ``T x T`` scores
nor a gather of the selected rows is ever whole in HBM.  The attention is
computed in the per-head form under the mask (every causal key block is
read, the unselected keys masked out of the softmax), not over gathered
latent rows: gathering ``top_k`` rows of 1152 bytes a query is 77 GB a
layer at two windows of 16 k tokens, and a per-row gather runs at a quarter
of the chip's bandwidth (PERF.md).

:func:`latent_sparse_attention` is one primitive, like
``fused_attention.attention``, and like it takes a layer's rotary tables:
``q`` ``[B, T, H * (dn + dr)]`` and ``k_rope`` ``[B, T, dr]`` then come as
their products wrote them, beside ``k_nope`` ``[B, T, H * dn]``, ``v`` and
the selection.  Which lowering a call gets is decided from its operands
when its program is lowered.  A one-device TPU program whose shapes
:func:`sparse_tiles` admits (an even number of heads, each ``dn`` = whole
lane tiles and a half, 64 rotary dims, ``rot`` <= 64) lowers the Pallas
kernel ``nns_latent_sparse_attention``: a grid step takes a **pair of
heads**, whose ``2 * dn`` columns of ``k_nope`` are whole lane tiles where
one head's are not, streams the key blocks of the causal half past the
pair's resident query block with a running softmax a head, and forms in
VMEM what it multiplies: at a query block's first key step each head's
rotary dims are rotated with ``rotate``'s arithmetic (float32, rounded
once), q scaled, and the second head's dims rolled half a tile on; at each
key step a head's key block ``[k_n | k_r]`` is its whole tiles of the
``k_nope`` block and the tile the pair shares with ``k_r`` selected into the
half that is not the head's own; the selection's block is fetched and
turned into a predicate once for both heads.  No ``[B, T, H, dn + dr]``
array of q or of the keys exists in HBM (``k_r`` alone is rotated through
XLA: 4 MB).  Every other program (the CPU's, one GSPMD partitions, odd
shapes) lowers ``rotate`` on q and ``k_rope``, :func:`_head_keys` and the
plain walk through XLA.  ``nnstpu_attention_rotary_total{where}`` counts a
call with tables by where q is rotated.

The selection is optional: ``mask=None`` is the same attention over every
key up to the query's own position, and then no ``[B, T, T]`` array is an
operand or made anywhere.  A one-device TPU program whose shapes
:func:`latent_tiles` admits (an even number of heads, ``dn`` and ``dv``
whole lane tiles, 64 rotary dims: heads of 128 | 64 with values of 128)
lowers the Pallas kernel ``nns_latent_attention``: again a pair of heads a
step, because a head's 192 columns of q are a lane tile and a half and a
pair's are three; the key blocks of the causal half stream past the pair's
resident query block, those wholly under its first row without a predicate,
those on the diagonal masked by position, those above it neither fetched
nor computed.  At a query block's first key step each head's q becomes ``[q_n
| q_r rotated | 0]`` in VMEM (the second head's columns rolled back half a
tile), scaled by the caller's ``scale``; a head's key block is ``[k_n | k_r
| 0]``.  Every other program walks row blocks through XLA with the
predicate made from positions (``latent_plain``).

:func:`select_keys` is a primitive of the same kind: where
:func:`index_tiles` holds the Pallas kernel ``nns_index_select`` scores a
block of query rows (the heads' products, ReLU and weighted sum in VMEM) and
selects there too, finding each row's ``top_k``-th highest score a bit at a
time by counting instead of sorting, so that neither the per-head scores
nor the summed ones reach HBM; elsewhere XLA's products and ``lax.top_k``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Primitive

from .fused_attention import (MASKED, _lane_tables, _rotated, count_attention,
                              rotate)
from .lowering import count, register
from .pallas_kernels import LANES, _interpret

KERNEL_NAME = "nns_latent_sparse_attention"
LATENT_KERNEL_NAME = "nns_latent_attention"
INDEX_KERNEL_NAME = "nns_index_select"
# Query rows and key rows a grid step of the attention kernel takes: on the
# v5e, one window of 16 384 tokens and 64 heads of 192 | 64, a pair of heads a
# step and q rotated on its blocks, 2048 x 512 ran in 64.2 ms, 1024 x 1024
# 65.5, 1024 x 512 65.6, 2048 x 1024 67.0, 1024 x 2048 68.6, 512 x 1024 68.8
# and 512 x 512 69.9 (PERF.md).  And the rows the walks through XLA take.
BLOCK_Q = 2048
BLOCK_K = 512
SELECT_ROWS = 512
# The same of the kernel without a selection: on the v5e, one window of
# 16 384 tokens and 64 heads of 128 | 64 with values of 128, 1024 x 1024 ran
# in 46.4 ms, 2048 x 1024 46.7, 512 x 1024 49.8, 512 x 512 75.4, 1024 x 512
# 81.1, 2048 x 512 85.2, 2048 x 256 118.4 and 1024 x 256 130.5 (PERF.md).
LATENT_BLOCK_Q = 1024
LATENT_BLOCK_K = 1024
# The selection kernel: query rows a grid step scores and selects for, the
# keys it scores at a time; its VMEM holds a window's indexer keys twice,
# the rows' scores against every key once and their one-byte selection twice
# (4 + 4 + 16 + 8 MiB at 16 k tokens).
INDEX_ROWS = 256
INDEX_BLOCK_K = 1024
VMEM_LIMIT = 64 * 2 ** 20
LATENT_VMEM_LIMIT = 100 * 2 ** 20
INDEX_VMEM_LIMIT = 100 * 2 ** 20


def row_blocks(t: int, rows: int) -> int:
    """How many blocks of ``rows`` query rows a walk over ``t`` takes: one
    where ``t`` is no whole number of them."""
    return t // rows if t > rows and t % rows == 0 else 1


# -- the selection -----------------------------------------------------------

def _index_scores_plain(q_i, k_i, w):
    """One block of queries against every key: ``q_i`` ``[R, H, D]``, ``k_i``
    ``[T, D]``, ``w`` ``[R, H]`` float32 -> ``[R, T]`` float32."""
    s = jnp.einsum("rhd,sd->rhs", q_i, k_i,
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * w[:, :, None]).sum(axis=1)


INT_MIN = -2 ** 31


def _ordered(x):
    """float32 -> int32 whose signed order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _select_kernel(q_ref, k_ref, w_ref, o_ref, keys_ref, *, heads: int,
                   width: int, top_k: int, rows: int, bk: int):
    """One block of query rows: its scores against every key block that
    holds a causal key, kept in VMEM as order-preserving int32; each row's
    ``top_k``-th highest found a bit at a time by counting (31 passes over
    the block's scores, no sort), the earliest of its equals by counting
    columns the same way; the selection written as int8."""
    row0 = pl.program_id(1) * rows
    blocks = k_ref.shape[1]
    live = (row0 + rows - 1) // bk + 1      # key blocks with a causal key
    w = w_ref[0]
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)

    def score(j, carry):
        k = k_ref[0, j]
        acc = jnp.zeros((rows, bk), jnp.float32)
        for h in range(heads):  # static: a head is a lane tile of the block
            s = jax.lax.dot_general(q_ref[0, :, h * width:(h + 1) * width], k,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w[:, h:h + 1]
        acc = jnp.where(acc == 0, 0.0, acc)  # one zero, as _selected()
        keys_ref[j] = _ordered(jnp.where(j * bk + lane <= row, acc, -jnp.inf))
        return carry

    jax.lax.fori_loop(0, live, score, 0)

    def count(holds):
        """Per row, the keys of the live blocks that ``holds(key, j)``."""
        def body(j, acc):
            p = holds(keys_ref[j], j).astype(jnp.float32)
            return acc + sum(p[:, c * LANES:(c + 1) * LANES]
                             for c in range(bk // LANES))

        return jax.lax.fori_loop(
            0, live, body, jnp.zeros((rows, LANES), jnp.float32)
        ).sum(axis=-1, keepdims=True)

    k_f = jnp.float32(top_k)
    # the top_k-th highest key: the sign, then 31 bits from the top down
    # (INT_MIN, below every key, where the live blocks hold fewer than top_k)
    least = jnp.where(count(lambda key, j: key >= 0) >= k_f,
                      jnp.int32(0), jnp.int32(INT_MIN))

    def value_bit(b, least):
        cand = least | jnp.left_shift(jnp.int32(1), 30 - b)
        return jnp.where(count(lambda key, j: key >= cand) >= k_f, cand,
                         least)

    least = jax.lax.fori_loop(0, 31, value_bit, least)
    # of its equals the earliest ``need``: the column of the last one taken
    need = k_f - count(lambda key, j: key > least)
    bits = max(1, (blocks * bk - 1).bit_length())

    def column_bit(b, last):
        cand = last | jnp.left_shift(jnp.int32(1), bits - 1 - b)
        before = count(lambda key, j: (key == least)
                       & (j * bk + lane < cand))
        return jnp.where(before < need, cand, last)

    last = jax.lax.fori_loop(0, bits, column_bit,
                             jnp.zeros((rows, 1), jnp.int32))
    for j in range(blocks):  # static: the output's columns
        at = slice(j * bk, (j + 1) * bk)

        @pl.when(j < live)
        def _(j=j, at=at):
            key, col = keys_ref[j], j * bk + lane
            taken = (key > least) | ((key == least) & (col <= last))
            o_ref[0, :, at] = (taken & (col <= row)).astype(jnp.int8)

        @pl.when(j >= live)
        def _(at=at):
            o_ref[0, :, at] = jnp.zeros((rows, bk), jnp.int8)


def index_select(q_i, k_i, w, top_k: int, rows: Optional[int] = None,
                 block_k: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """:func:`select_keys` as one kernel: ``q_i`` ``[B, T, H * D]``, ``k_i``
    ``[B, T, D]``, ``w`` ``[B, T, H]`` float32 -> ``[B, T, T]`` int8.  A grid
    step takes a block of query rows against a window's resident keys;
    neither the per-head scores nor the summed ones reach HBM."""
    b, t, _ = q_i.shape
    width = k_i.shape[-1]
    heads = q_i.shape[-1] // width
    rows, bk = min(rows or INDEX_ROWS, t), min(block_k or INDEX_BLOCK_K, t)
    if interpret is None:
        interpret = _interpret()
    return pl.pallas_call(
        functools.partial(_select_kernel, heads=heads, width=width,
                          top_k=top_k, rows=rows, bk=bk),
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.int8),
        grid=(b, t // rows),
        in_specs=[
            pl.BlockSpec((1, rows, heads * width), lambda i, r: (i, r, 0)),
            pl.BlockSpec((1, t // bk, bk, width), lambda i, r: (i, 0, 0, 0)),
            pl.BlockSpec((1, rows, heads), lambda i, r: (i, r, 0))],
        out_specs=pl.BlockSpec((1, rows, t), lambda i, r: (i, r, 0)),
        scratch_shapes=[pltpu.VMEM((t // bk, rows, bk), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=INDEX_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=b * t * (t + 1) * heads * width, transcendentals=0,
            bytes_accessed=q_i.size * q_i.dtype.itemsize
            + k_i.size * k_i.dtype.itemsize + b * t * t),
        interpret=interpret,
        name=INDEX_KERNEL_NAME,
    )(q_i, k_i.reshape(b, t // bk, bk, width), w)


def index_tiles(q_shape, k_shape, dtype, top_k: int) -> bool:
    """Whether :func:`index_select` is the lowering: heads of whole lane
    tiles, bf16 or f32, whole blocks of rows and of keys, a selection that
    cuts."""
    dtype = jnp.dtype(dtype)
    t, width = k_shape[-2], k_shape[-1]
    return (dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and width % LANES == 0 and q_shape[-1] % width == 0
            and top_k < t and t % INDEX_ROWS == 0 and t % INDEX_BLOCK_K == 0)


def _selected(scores, row0, top_k: int):
    """The ``[R, T]`` int8 mask of one block of queries ``row0 ...``: of each
    row's causal scores the ``top_k`` highest, the earlier key among equals
    (``lax.top_k``'s order: what lies above the last value taken, and of
    its equals those up to the last index taken)."""
    r, t = scores.shape
    rows = row0 + jnp.arange(r, dtype=jnp.int32)[:, None]
    cols = jnp.arange(t, dtype=jnp.int32)[None, :]
    causal = cols <= rows
    if top_k >= t:
        return causal.astype(jnp.int8)
    # one zero: a sort may tell -0.0 from 0.0, and "equal" has to mean equal
    scores = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    top, at = jax.lax.top_k(scores, top_k)
    least, last = top[:, -1:], at[:, -1:]
    taken = (scores > least) | ((scores == least) & (cols <= last))
    return (taken & causal).astype(jnp.int8)


def _select(q_i, k_i, w, *, top_k: int):
    """Through XLA: a block of query rows at a time, the per-head scores
    through HBM, ``lax.top_k`` (a sort of each row with its indices)."""
    b, t, _ = q_i.shape
    width = k_i.shape[-1]
    heads = q_i.shape[-1] // width
    blocks = row_blocks(t, SELECT_ROWS)
    rows = t // blocks
    w = w.astype(jnp.float32)

    def window(q_w, k_w, w_w):
        def block(i):
            row0 = i * rows
            q_b = jax.lax.dynamic_slice_in_dim(q_w, row0, rows)
            w_b = jax.lax.dynamic_slice_in_dim(w_w, row0, rows)
            scores = _index_scores_plain(q_b.reshape(rows, heads, width),
                                         k_w, w_b)
            return _selected(scores, row0, top_k)

        return jax.lax.map(
            block, jnp.arange(blocks, dtype=jnp.int32)).reshape(t, t)

    return jax.lax.map(lambda a: window(*a), (q_i, k_i, w))


select_keys_p = Primitive("nns_select_keys")


def select_keys(q_i, k_i, w, top_k: int):
    """The indexer's choice: ``q_i`` ``[B, T, H * D]`` and ``k_i`` ``[B, T,
    D]`` (one key head, both rotated), ``w`` ``[B, T, H]`` the heads'
    weights.  Returns ``[B, T, T]`` int8, 1 where key ``s`` is among query
    ``t``'s ``top_k`` highest causal scores."""
    return select_keys_p.bind(q_i, k_i, w, top_k=top_k)


select_keys_p.def_impl(jax.jit(select_keys_p.bind, static_argnames=("top_k",)))
select_keys_p.def_abstract_eval(
    lambda q_i, *_, **__: q_i.update(shape=(*q_i.shape[:2], q_i.shape[1]),
                                     dtype=jnp.dtype(jnp.int8)))


def _lower_select(avals, *, top_k):
    count_attention("index_select_plain")
    return functools.partial(_select, top_k=top_k)


def _lower_select_kernel(avals, *, top_k):
    q_i, k_i, _ = avals
    if not (q_i.dtype == k_i.dtype
            and index_tiles(q_i.shape, k_i.shape, q_i.dtype, top_k)):
        return None
    count_attention("index_select")
    return lambda q_i, k_i, w: index_select(q_i, k_i, w.astype(jnp.float32),
                                            top_k, interpret=False)


register(select_keys_p, _lower_select, _lower_select_kernel)


# -- the attention -----------------------------------------------------------

def _head_keys(k_nope, k_rope, n_heads: int):
    """``[B, T, H * (dn + dr)]``: each head's own part beside the part every
    head shares."""
    b, t, _ = k_nope.shape
    shared = jnp.broadcast_to(k_rope[:, :, None, :],
                              (b, t, n_heads, k_rope.shape[-1]))
    return jnp.concatenate([k_nope.reshape(b, t, n_heads, -1), shared],
                           axis=-1).reshape(b, t, -1)


def _plain(q, k_nope, k_rope, v, mask, *, n_heads: int,
           scale: Optional[float] = None):
    """Through XLA: a block of query rows against every key, the mask laid
    over the scores (without one, the keys up to the query's own position),
    a whole-row softmax."""
    b, t, _ = q.shape
    k = _head_keys(k_nope, k_rope, n_heads).reshape(b, t, n_heads, -1)
    vh = v.reshape(b, t, n_heads, -1)
    if scale is None:
        scale = k.shape[-1] ** -0.5
    blocks = row_blocks(t, SELECT_ROWS)
    rows = t // blocks

    def block(args):
        q_b, m_b = args  # [b, rows, H * d]; [b, rows, t], or the block's index
        s = jnp.einsum("brhd,bshd->bhrs", q_b.reshape(b, rows, n_heads, -1),
                       k, preferred_element_type=jnp.float32) * scale
        if mask is None:
            at = m_b * rows + jnp.arange(rows, dtype=jnp.int32)
            seen = jnp.arange(t, dtype=jnp.int32)[None, :] <= at[:, None]
        else:
            seen = m_b[:, None] != 0
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhrs,bshd->brhd", p.astype(v.dtype), vh,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype).reshape(b, rows, -1)

    def split(a):  # [b, t, x] -> [blocks, b, rows, x]
        return jnp.moveaxis(a.reshape(b, blocks, rows, -1), 1, 0)

    out = jax.lax.map(block, (split(q), jnp.arange(blocks, dtype=jnp.int32)
                              if mask is None else split(mask)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, -1)


def _start(m_ref, l_ref, acc_ref):
    """A query block's first key step: nothing seen yet."""
    m_ref[...] = jnp.full(m_ref.shape, MASKED, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


def _take_in(h: int, s, v, m_ref, l_ref, acc_ref):
    """Head ``h`` of the pair: one key block's scores ``s`` ``[bq, bk]`` and
    values ``v`` ``[bk, dv]`` into its running max, row sum and output."""
    m = m_ref[h]
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    e = jnp.exp(s - m_new)
    a = jnp.exp(m - m_new)
    l_ref[h] = a * l_ref[h] + e.sum(axis=-1, keepdims=True)
    acc_ref[h] = a * acc_ref[h] + jnp.dot(
        e.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[h] = m_new


def _write_out(o_ref, l_ref, acc_ref, dv: int):
    for h in range(2):
        o_ref[0, :, h * dv:(h + 1) * dv] = (
            acc_ref[h] / l_ref[h]).astype(o_ref.dtype)


def _sparse_kernel(q_ref, kn_ref, kr_ref, v_ref, mask_ref, *refs, bq: int,
                   bk: int, d: int, dv: int, scale: float,
                   half: Optional[int]):
    """One (batch row, pair of heads, block of query rows) against one key
    block of its causal half: each head's running max, row sum and output
    live in scratch across the key blocks, and so does the pair's q block as
    it is multiplied.  A head is ``d - 64`` unrotated dims and 64 rotary
    ones, so of the pair's ``2 * (d - 64)`` key columns the tile in the
    middle holds the first head's last 64 and the second's first 64; each
    head's key block is its whole tiles and that tile with ``k_r`` (which
    arrives in both half tiles) selected into the half that is not its own.
    The second head's key thus reads ``[dims 64... | k_r | dims ...64]``,
    and its q is rolled into the same order once, at the first key step; a
    score is a sum over dims and does not see it.  With tables (``half``
    lanes a rotary half) q's rotary dims are rotated there too."""
    if half is None:
        o_ref, q_s, m_ref, l_ref, acc_ref = refs
    else:
        c_ref, s_ref, o_ref, q_s, m_ref, l_ref, acc_ref = refs
    i, j = pl.program_id(2), pl.program_id(3)
    last = (i * bq + bq - 1) // bk  # the last key block a row here may see
    own = d - LANES  # a head's whole tiles of unrotated dims

    @pl.when(j == 0)
    def _():
        _start(m_ref, l_ref, acc_ref)
        lane = jax.lax.broadcasted_iota(jnp.int32, (bq, LANES), 1)

        def tile(n):
            x = q_ref[0, :, n * LANES:(n + 1) * LANES]
            if half is not None and (n + 1) * LANES % d == 0:
                x = _rotated(x, lane, c_ref, s_ref, half, LANES // 2)
            return x * scale  # a weak scalar: q keeps its type

        tiles = d // LANES
        for n in range(tiles):  # the first head, as it lies
            q_s[:, n * LANES:(n + 1) * LANES] = tile(n)
        rolled = [pltpu.roll(tile(tiles + n).astype(jnp.float32), LANES // 2,
                             1) for n in range(tiles)]
        for n in range(tiles):  # the second, half a tile on
            q_s[:, d + n * LANES:d + (n + 1) * LANES] = jnp.where(
                lane < LANES // 2, rolled[n], rolled[(n + 1) % tiles]
            ).astype(q_s.dtype)

    @pl.when(j <= last)
    def _():
        seen = mask_ref[0].astype(jnp.float32) > 0  # once for both heads
        low = jax.lax.broadcasted_iota(jnp.int32, (bk, LANES), 1) < LANES // 2
        mid, shared = kn_ref[0, :, own:own + LANES], kr_ref[0]
        keys = (jnp.where(low, mid, shared), jnp.where(low, shared, mid))
        for h, k in enumerate(keys):  # static: the pair
            if own:
                at = h * (own + LANES)
                k = jnp.concatenate([kn_ref[0, :, at:at + own], k], axis=1)
            s = jax.lax.dot_general(q_s[:, h * d:(h + 1) * d], k,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            _take_in(h, jnp.where(seen, s, MASKED),
                     v_ref[0, :, h * dv:(h + 1) * dv], m_ref, l_ref, acc_ref)

    pl.when(j == last)(functools.partial(_write_out, o_ref, l_ref, acc_ref,
                                         dv))


def sparse_attention_kernel(q, k_nope, k_rope, v, mask, n_heads: int,
                            rotary=None, block_q: Optional[int] = None,
                            block_k: Optional[int] = None,
                            interpret: Optional[bool] = None,
                            scale: Optional[float] = None):
    """Softmax attention over the keys ``mask`` lets through, token-major
    and on the projections as the products write them: ``q`` ``[B, T, H *
    (dn + 64)]``, ``k_nope`` ``[B, T, H * dn]``, ``k_rope`` ``[B, T, 64]``,
    ``v`` ``[B, T, H * dv]``, ``mask`` ``[B, T, T]`` int8 with nothing
    above the diagonal; :func:`sparse_tiles` says which shapes.  A
    grid step takes a pair of heads (a head's ``dn`` columns of ``k_nope``
    are no whole lane tiles, a pair's are) and forms their keys ``[k_n |
    k_r]`` in VMEM; key blocks past a query block's last row are neither
    fetched nor computed, and a selection block is fetched and turned into a
    predicate once for both heads.

    ``rotary`` = ``(cos, sin)``, each ``[T, rot/2]`` float32 (``rot`` <=
    64): q and ``k_rope`` come unrotated; the kernel applies ``rotate``'s
    arithmetic to each head's dims ``dn ... dn + rot``, the same roundings
    in the same order, on the q block it holds, once a block of query rows;
    ``k_rope``, 64 columns for all heads, goes through :func:`rotate`.
    ``scale``: what a score is multiplied by, ``(dn + 64) ** -0.5`` if left
    out."""
    b, t, _ = q.shape
    d, dv = q.shape[-1] // n_heads, v.shape[-1] // n_heads
    dn = k_nope.shape[-1] // n_heads
    if scale is None:
        scale = d ** -0.5
    bq, bk = min(block_q or BLOCK_Q, t), min(block_k or BLOCK_K, t)
    if interpret is None:
        interpret = _interpret()

    def keys(i, h, r, j):
        return (i, jnp.minimum(j, (r * bq + bq - 1) // bk), h)

    def rows(i, h, r, j):
        return (i, r, h)

    if rotary is not None:
        k_rope = rotate(k_rope, *rotary, 1)
    operands = [q, k_nope, jnp.concatenate([k_rope, k_rope], -1), v, mask]
    in_specs = [pl.BlockSpec((1, bq, 2 * d), rows),
                pl.BlockSpec((1, bk, 2 * dn), keys),
                pl.BlockSpec((1, bk, LANES),
                             lambda i, h, r, j: (*keys(i, h, r, j)[:2], 0)),
                pl.BlockSpec((1, bk, 2 * dv), keys),
                pl.BlockSpec((1, bq, bk),
                             lambda i, h, r, j: (i, r, keys(i, h, r, j)[1]))]
    half = None
    if rotary is not None:
        half = rotary[0].shape[-1]
        operands += _lane_tables(*rotary, t, LANES // 2)
        in_specs += [pl.BlockSpec((bq, LANES), lambda i, h, r, j: (r, 0))] * 2
    itemsize = jnp.dtype(q.dtype).itemsize
    seen = t * (t + 1) // 2
    return pl.pallas_call(
        functools.partial(_sparse_kernel, bq=bq, bk=bk, d=d, dv=dv,
                          scale=scale, half=half),
        out_shape=jax.ShapeDtypeStruct(v.shape, q.dtype),
        grid=(b, n_heads // 2, t // bq, t // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, 2 * dv), rows),
        scratch_shapes=[pltpu.VMEM((bq, 2 * d), q.dtype),
                        pltpu.VMEM((2, bq, 1), jnp.float32),
                        pltpu.VMEM((2, bq, 1), jnp.float32),
                        pltpu.VMEM((2, bq, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * n_heads * seen * (d + dv),
            transcendentals=b * n_heads * seen,
            bytes_accessed=b * t * n_heads * (2 * d + 2 * dv) * itemsize
            + b * (n_heads // 2) * seen),
        interpret=interpret,
        name=KERNEL_NAME,
    )(*operands)


def _pair_tiles(q_shape, k_nope_shape, k_rope_shape, v_shape, dtype,
                n_heads: int, rotary_shape, past_a_tile: int, block_q: int,
                block_k: int) -> bool:
    """What both kernels ask of a call: bf16 or f32, pairs of heads of 64
    rotary dims whose unrotated dims end ``past_a_tile`` lanes past a whole
    lane tile, heads of whole lane tiles for the value, whole blocks of rows
    and of keys (one block of either where T is shorter); with
    ``rotary_shape``, the ``[T, rot/2]`` of a call's tables, ``rot`` no more
    than the rotary dims."""
    dtype = jnp.dtype(dtype)
    t, dr = q_shape[1], k_rope_shape[-1]
    dn = k_nope_shape[-1] // n_heads
    if rotary_shape is not None and (
            len(rotary_shape) != 2 or rotary_shape[0] != t
            or not 0 < 2 * rotary_shape[1] <= dr):
        return False
    return (dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and n_heads % 2 == 0 and dr == LANES // 2
            and dn > 0 and dn % LANES == past_a_tile
            and k_nope_shape[-1] == n_heads * dn
            and q_shape[-1] == n_heads * (dn + dr)
            and v_shape[-1] % (n_heads * LANES) == 0
            and t % min(block_q, t) == 0 and t % min(block_k, t) == 0
            and t % LANES == 0)


def sparse_tiles(q_shape, k_nope_shape, k_rope_shape, v_shape, dtype,
                 n_heads: int, rotary_shape=None) -> bool:
    """Whether the kernel under a selection is the lowering
    (:func:`_pair_tiles`): a head's unrotated dims end half a lane tile in
    and its 64 rotary dims fill it, so a pair's ``k_nope`` columns are whole
    tiles (192 | 64)."""
    return _pair_tiles(q_shape, k_nope_shape, k_rope_shape, v_shape, dtype,
                       n_heads, rotary_shape, LANES // 2, BLOCK_Q, BLOCK_K)


# -- the same attention over every causal key ---------------------------------

def _latent_kernel(q_ref, kn_ref, kr_ref, v_ref, *refs, bq: int, bk: int,
                   dn: int, dv: int, scale: float, half: Optional[int]):
    """One (batch row, pair of heads, block of query rows) against one key
    block of its causal half, no selection: causal by position, the blocks
    wholly under the diagonal unmasked.  A head is ``dn`` unrotated dims
    (whole lane tiles) and 64 rotary ones, so a pair's q columns are whole
    tiles where one head's are not: the first head's lie as the product
    wrote them, the second's start half a tile in and are rolled back half a
    tile, once, at the first key step.  There each head's q becomes ``[q_n |
    q_r | 0]`` in scratch, its rotary half tile rotated (with tables), q
    scaled; a head's key block is ``[k_n | k_r | 0]`` (``k_r`` arrives padded
    to a tile), so one product gives ``q_n . k_n + q_r . k_r``."""
    if half is None:
        o_ref, q_s, m_ref, l_ref, acc_ref = refs
    else:
        c_ref, s_ref, o_ref, q_s, m_ref, l_ref, acc_ref = refs
    i, j = pl.program_id(2), pl.program_id(3)
    last = (i * bq + bq - 1) // bk  # the last key block a row here may see
    whole = (i * bq + 1) // bk      # key blocks every row here sees whole
    n = dn // LANES                 # a head's tiles of unrotated dims
    d = dn + LANES                  # a head in scratch: those and [q_r | 0]

    @pl.when(j == 0)
    def _():
        _start(m_ref, l_ref, acc_ref)
        lane = jax.lax.broadcasted_iota(jnp.int32, (bq, LANES), 1)
        low = lane < LANES // 2

        def tile(m):
            return q_ref[0, :, m * LANES:(m + 1) * LANES]

        def rotary(x):  # [q_r | whatever] -> [q_r rotated and scaled | 0]
            if half is not None:
                x = _rotated(x, lane, c_ref, s_ref, half)
            return jnp.where(low, x * scale, jnp.zeros_like(x))

        for m in range(n):  # the first head, as it lies
            q_s[:, m * LANES:(m + 1) * LANES] = tile(m) * scale
        q_s[:, dn:d] = rotary(tile(n))
        rolled = [pltpu.roll(tile(n + m).astype(jnp.float32), LANES // 2, 1)
                  for m in range(n + 1)]
        for m in range(n):  # the second, from half a tile in
            q_s[:, d + m * LANES:d + (m + 1) * LANES] = (jnp.where(
                low, rolled[m], rolled[m + 1]) * scale).astype(q_s.dtype)
        q_s[:, d + dn:2 * d] = rotary(rolled[n].astype(q_s.dtype))

    def step(masked: bool):
        shared = kr_ref[0]
        if masked:
            seen = (j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                    <= i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk),
                                                         0))
        for h in range(2):  # static: the pair
            k = jnp.concatenate([kn_ref[0, :, h * dn:(h + 1) * dn], shared],
                                axis=1)
            s = jax.lax.dot_general(q_s[:, h * d:(h + 1) * d], k,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if masked:
                s = jnp.where(seen, s, MASKED)
            _take_in(h, s, v_ref[0, :, h * dv:(h + 1) * dv], m_ref, l_ref,
                     acc_ref)

    pl.when(j < whole)(functools.partial(step, False))
    pl.when((j >= whole) & (j <= last))(functools.partial(step, True))
    pl.when(j == last)(functools.partial(_write_out, o_ref, l_ref, acc_ref,
                                         dv))


def latent_attention_kernel(q, k_nope, k_rope, v, n_heads: int, rotary=None,
                            scale: Optional[float] = None,
                            block_q: Optional[int] = None,
                            block_k: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """Causal softmax attention over every key up to the query's own,
    token-major and on the projections as the products write them: ``q``
    ``[B, T, H * (dn + 64)]``, ``k_nope`` ``[B, T, H * dn]``, ``k_rope``
    ``[B, T, 64]``, ``v`` ``[B, T, H * dv]``; :func:`latent_tiles` says
    which shapes.  No mask is an operand: a grid step takes a pair of heads
    (a head's ``dn + 64`` columns of q are no whole lane tiles, a pair's
    are) and one key block; key blocks past a query block's last row are
    neither fetched nor computed, and those wholly under its first row need
    no predicate.  ``rotary`` and ``scale`` as
    :func:`sparse_attention_kernel` takes them; ``k_rope`` is rotated
    through :func:`rotate` and padded to a lane tile."""
    b, t, _ = q.shape
    d, dv = q.shape[-1] // n_heads, v.shape[-1] // n_heads
    dn = k_nope.shape[-1] // n_heads
    if scale is None:
        scale = d ** -0.5
    bq = min(block_q or LATENT_BLOCK_Q, t)
    bk = min(block_k or LATENT_BLOCK_K, t)
    if interpret is None:
        interpret = _interpret()

    def keys(i, h, r, j):
        return (i, jnp.minimum(j, (r * bq + bq - 1) // bk), h)

    def rows(i, h, r, j):
        return (i, r, h)

    if rotary is not None:
        k_rope = rotate(k_rope, *rotary, 1)
    operands = [q, k_nope,
                jnp.pad(k_rope, ((0, 0), (0, 0), (0, LANES - d + dn))), v]
    in_specs = [pl.BlockSpec((1, bq, 2 * d), rows),
                pl.BlockSpec((1, bk, 2 * dn), keys),
                pl.BlockSpec((1, bk, LANES),
                             lambda i, h, r, j: (*keys(i, h, r, j)[:2], 0)),
                pl.BlockSpec((1, bk, 2 * dv), keys)]
    half = None
    if rotary is not None:
        half = rotary[0].shape[-1]
        operands += _lane_tables(*rotary, t)
        in_specs += [pl.BlockSpec((bq, LANES), lambda i, h, r, j: (r, 0))] * 2
    itemsize = jnp.dtype(q.dtype).itemsize
    seen = t * (t + 1) // 2
    return pl.pallas_call(
        functools.partial(_latent_kernel, bq=bq, bk=bk, dn=dn, dv=dv,
                          scale=scale, half=half),
        out_shape=jax.ShapeDtypeStruct(v.shape, q.dtype),
        grid=(b, n_heads // 2, t // bq, t // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, 2 * dv), rows),
        scratch_shapes=[pltpu.VMEM((bq, 2 * (dn + LANES)), q.dtype),
                        pltpu.VMEM((2, bq, 1), jnp.float32),
                        pltpu.VMEM((2, bq, 1), jnp.float32),
                        pltpu.VMEM((2, bq, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=LATENT_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * n_heads * seen * (d + dv),
            transcendentals=b * n_heads * seen,
            bytes_accessed=b * t * n_heads * (2 * d + 2 * dv) * itemsize),
        interpret=interpret,
        name=LATENT_KERNEL_NAME,
    )(*operands)


def latent_tiles(q_shape, k_nope_shape, k_rope_shape, v_shape, dtype,
                 n_heads: int, rotary_shape=None) -> bool:
    """Whether :func:`latent_attention_kernel` is the lowering
    (:func:`_pair_tiles`): a head's unrotated dims are whole lane tiles
    beside its 64 rotary ones (128 | 64 with values of 128 and wider)."""
    return _pair_tiles(q_shape, k_nope_shape, k_rope_shape, v_shape, dtype,
                       n_heads, rotary_shape, 0, LATENT_BLOCK_Q,
                       LATENT_BLOCK_K)


latent_sparse_attention_p = Primitive("nns_latent_sparse_attention")


def latent_sparse_attention(q, k_nope, k_rope, v, mask, n_heads: int,
                            rotary=None, scale: Optional[float] = None,
                            shared: bool = False):
    """``q`` ``[B, T, H * (dn + dr)]``, each head's ``dn`` unrotated dims and
    then its ``dr`` rotary ones; ``k_nope`` ``[B, T, H * dn]``; ``k_rope``
    ``[B, T, dr]``, the rotary key part every head shares; ``v`` ``[B, T, H
    * dv]``; ``mask`` ``[B, T, T]`` int8 from :func:`select_keys`, or
    ``None``: no selection, every key up to the query's own position (no
    ``[B, T, T]`` array is an operand then, or made).  With ``rotary`` =
    ``(cos, sin)``, each ``[T, rot/2]`` float32, ``q`` and ``k_rope`` come
    as the products wrote them and the lowering applies
    ``fused_attention.rotate`` to each head's rotary dims and to ``k_rope``;
    without, both come rotated.  Returns ``[B, T, H * dv]``: per head the
    softmax of ``(q_n . k_n + q_r . k_r) * scale`` (``scale``: ``(dn + dr)
    ** -0.5`` if left out) over the selected keys alone, times ``v``.
    ``shared``: ``mask`` is an earlier layer's selection taken over, not
    this layer's own (``nnstpu_indexer_lowerings_total{role}`` counts a
    call with a mask by it where the call is lowered).  See the module's
    docstring for which lowering a call gets."""
    selected = mask is not None
    return latent_sparse_attention_p.bind(
        q, k_nope, k_rope, v, *((mask,) if selected else ()),
        *(rotary or ()), n_heads=n_heads, selected=selected, shared=shared,
        scale=scale)


latent_sparse_attention_p.def_impl(jax.jit(
    latent_sparse_attention_p.bind,
    static_argnames=("n_heads", "selected", "shared", "scale")))
latent_sparse_attention_p.def_abstract_eval(
    lambda q, k_nope, k_rope, v, *_, **__: v.update(dtype=q.dtype))


def _count_indexer(selected: bool, shared: bool) -> None:
    if selected:
        count("nnstpu_indexer_lowerings_total",
              "attention layers lowered into a program by where their key "
              "selection comes from (full = the layer's own indexer scores "
              "and selects, shared = it reuses an earlier layer's selection)",
              role="shared" if shared else "full")


def _lower_plain(avals, *, n_heads, selected, shared, scale):
    count_attention("latent_sparse_plain" if selected else "latent_plain",
                    "outside" if len(avals) == 6 + selected else None)
    _count_indexer(selected, shared)

    def walk(q, k_nope, k_rope, v, *rest):
        mask, tables = (rest[0] if selected else None), rest[selected:]
        if tables:
            q = rotate(q, *tables, n_heads, k_nope.shape[-1] // n_heads)
            k_rope = rotate(k_rope, *tables, 1)
        return _plain(q, k_nope, k_rope, v, mask, n_heads=n_heads,
                      scale=scale)

    return walk


def _lower_kernel(avals, *, n_heads, selected, shared, scale):
    q, k_nope, k_rope, v, *rest = avals
    tables = rest[selected:]
    tiles = sparse_tiles if selected else latent_tiles
    if not (q.dtype == k_nope.dtype == k_rope.dtype == v.dtype
            and tiles(q.shape, k_nope.shape, k_rope.shape, v.shape, q.dtype,
                      n_heads, tables[0].shape if tables else None)):
        return None
    count_attention("latent_sparse" if selected else "latent",
                    "kernel" if tables else None)
    _count_indexer(selected, shared)

    def kernel(q, k_nope, k_rope, v, *rest):
        if selected:
            return sparse_attention_kernel(
                q, k_nope, k_rope, v, rest[0], n_heads, rest[1:] or None,
                interpret=False, scale=scale)
        return latent_attention_kernel(q, k_nope, k_rope, v, n_heads,
                                       rest or None, scale, interpret=False)

    return kernel


register(latent_sparse_attention_p, _lower_plain, _lower_kernel)
