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

Past what :func:`tiles` admits a second kernel, :func:`blocked_attention`,
walks key blocks with a running softmax: causal, over separate q and k, v
projections with grouped heads of width 128 (a head is one lane tile of the
token-major arrays), with a window if the layer has one; it skips the key
blocks above the diagonal and outside the band, and holds a key/value head's
whole K and V in VMEM, fetched once a head group.  Where the window is a
whole number of key blocks, a block of rows past it walks the band's lower
edge block and its own diagonal block, whose masks are complementary, as one
tile of scores (one softmax pass where there were two masked ones), in
straight-line code for the two chains of 256 rows a grid step holds.
Given a layer's rotary tables it rotates q and k itself, on the blocks it
already holds: float32 in VMEM only, where XLA's :func:`rotate` around the
projections re-tiles each ``[tokens, heads * 128]`` array to ``[heads,
128]`` through float32 copies in HBM.

The token models call :func:`attention`, one primitive, whose lowering
``ops/lowering.py`` chooses: a kernel where :func:`tiles` (the fused
projection) or :func:`blocked_tiles` (separate projections) holds,
``parallel.ring_attention.full_attention`` with the explicit mask through XLA
(after :func:`rotate`, where the call came with tables) for every other
program or shape.  ``full_attention`` stays the reference.  Called
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
from jax.interpreters import ad, batching

from .lowering import count, register
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


# -- grouped heads, causal, past what tiles() admits: key blocks ------------

BLOCKED_KERNEL_NAME = "nns_blocked_attention"
# Query rows and key rows a step of the walk takes.  On the v5e at 16 x 4096
# tokens 512 x 512 ran fastest for both kinds of layer (34.6 ms with 48 heads
# and no window, 25.9 ms with 64 heads and a window of 512); 256 x 256, which
# computes less of what a window's mask throws away, took 59.7 and 33.6 ms:
# a step's fixed cost outweighs the masked half.
BLOCK_Q = 512
BLOCK_K = 512
# A window of whole blocks of this many keys is walked in chains of as many
# rows, the band's edge and diagonal blocks folded into one tile (see
# _blocked_kernel).  At 16 x 4096 with 64 heads and a window of 512 on the
# v5e (PERF.md §5): the walk before the fold 27.5 ms a layer, the fold inside
# that walk's loops 25.8, the band in straight-line code with 512-key chains
# 19.3, with 256-key chains 18.1.  Two or four heads a grid step ran 17.0 and
# 16.2 but took Mosaic 1.7 and 4.2 s to compile against 0.8, and the cell's
# warm set-up with four rose 15-26 s (not explained: ROADMAP S9); 1024 rows a
# grid step ran 19.3, 128-key chains 29.5.
BAND_BLOCK_K = 256


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def rotate(x, cos, sin, n_heads: int, at: int = 0):
    """Rotary embedding on ``[B, T, n_heads * dh]``: each head's dims ``at
    ... at + rot`` as two halves ``(a, b)`` → ``(a cos - b sin, b cos + a
    sin)``, the rest passed through; ``cos``, ``sin``: ``[T, rot/2]``
    float32.  Float32 inside and rounded to ``x``'s type once: through XLA
    that is a pass over ``x`` in HBM and a re-tiling of it by head, which is
    why the blocked kernel does the same arithmetic on its own blocks."""
    b, t, _ = x.shape
    half = cos.shape[-1]
    h = x.reshape(b, t, n_heads, -1)
    # only the halves turn to float32: a cast of all of h is one more copy
    a, bb = (h[..., at + i * half:at + (i + 1) * half].astype(jnp.float32)
             for i in (0, 1))
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    out = jnp.concatenate(
        [h[..., :at], (a * cos - bb * sin).astype(x.dtype),
         (bb * cos + a * sin).astype(x.dtype), h[..., at + 2 * half:]], -1)
    return out.reshape(x.shape)


def blocked_tiles(q_shape, kv_shape, dtype, n_heads: int, n_kv_heads: int,
                  causal: bool, rotary_shape=None) -> bool:
    """Whether :func:`blocked_attention` is the lowering for these shapes:
    causal, heads of exactly one lane tile that group evenly over the
    key/value heads, bf16 or f32, and a (batch row, key/value head)'s whole
    K and V, double buffered, within :data:`VMEM_BUDGET` (T <= 12 k in
    bf16).  With ``rotary_shape``, the ``[T, rot/2]`` of a call's tables,
    the rotated K's scratch and a row block of both full-width tables,
    double buffered, count too (T <= 9 k in bf16), and ``rot`` is at most
    the head."""
    if not causal or len(q_shape) != 3 or len(kv_shape) != 3:
        return False
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    t = q_shape[1]
    rows = _round_up(t, BLOCK_K) * LANES * dtype.itemsize
    vmem = 2 * 2 * rows
    if rotary_shape is not None:
        if (len(rotary_shape) != 2 or rotary_shape[0] != t
                or not 0 < 2 * rotary_shape[1] <= LANES):
            return False
        vmem += rows + 2 * 2 * BLOCK_Q * LANES * 4
    return (n_kv_heads > 0 and n_heads % n_kv_heads == 0
            and q_shape[-1] == n_heads * LANES
            and kv_shape[-1] == n_kv_heads * LANES
            and vmem <= VMEM_BUDGET)


def _lane_tables(cos, sin, rows: int, at: int = 0):
    """``rotate``'s arithmetic as ``x * C + partner * S`` over whole lane
    tiles: ``C`` = ``[1 ... | cos | cos | 1 ...]``, ``S`` = ``[0 ... | -sin |
    sin | 0 ...]``, the rotated dims from lane ``at`` on, both ``[rows,
    128]`` float32, zero past the tables' T (padded rows)."""
    t, half = cos.shape
    cos, sin = cos.astype(jnp.float32), sin.astype(jnp.float32)
    before, rest = (t, at), (t, LANES - at - 2 * half)

    def table(a, b, fill):
        parts = (a, b, fill(rest, jnp.float32))
        if at:
            parts = (fill(before, jnp.float32),) + parts
        return jnp.pad(jnp.concatenate(parts, -1), ((0, rows - t), (0, 0)))

    return table(cos, cos, jnp.ones), table(-sin, sin, jnp.zeros)


def _rotated(x, lane, c_ref, s_ref, half: int, at: int = 0):
    """A ``[rows, 128]`` tile in VMEM rotated against the row block of
    :func:`_lane_tables`' pair: float32 here and rounded once, as
    :func:`rotate` does; the partner of a lane is ``half`` lanes on in the
    rotated dims' first half (from lane ``at``), ``half`` back in the second
    (``lane``: the tile's lane numbers)."""
    f = x.astype(jnp.float32)
    partner = pltpu.roll(f, half, 1)
    if at or 2 * half != LANES:
        first = lane < at + half
        if at:
            first &= lane >= at
        partner = jnp.where(first, pltpu.roll(f, LANES - half, 1), partner)
    return (f * c_ref[...] + partner * s_ref[...]).astype(x.dtype)


def _blocks(t: int, window: Optional[int] = None,
            block_q: Optional[int] = None, block_k: Optional[int] = None):
    """The walk's rows a grid step and key block rows for ``t`` tokens, and
    the padded T (a whole number of both).  Left to choose the key block, a
    window that folds at :data:`BAND_BLOCK_K` gets it."""
    cap = _round_up(t, 128)
    bq, bk = min(block_q or BLOCK_Q, cap), min(block_k or BLOCK_K, cap)
    if block_k is None and _folds(window, bq, BAND_BLOCK_K,
                                  _round_up(t, bq)):
        bk = BAND_BLOCK_K
    return bq, bk, _round_up(t, math.lcm(bq, bk))


def _folds(window: Optional[int], bq: int, bk: int, tp: int) -> bool:
    """Whether a row block past the window walks the band's two masked key
    blocks as one tile of scores: rows in whole key blocks, a window of
    whole key blocks, and a row block that lies past it."""
    return (window is not None and bq % bk == 0 and window % bk == 0
            and window < tp)


def _blocked_kernel(q_ref, k_ref, v_ref, *refs, bq: int, bk: int,
                    window: Optional[int], group: int, half: Optional[int],
                    fold: bool):
    """One (batch row, query head, block of query rows): walk the key blocks
    this block's rows may see with a running max, row sum and output.  With
    tables (``half`` lanes a rotary half) q's block is rotated first, and so
    is k's block of the same rows, into the scratch every walk reads, while
    the head group's first head passes: a walk reads no key past its own
    rows' block that the mask does not throw away.

    Without ``fold`` the block's rows walk as one chain.  With it, every
    ``bk`` rows are a chain of their own, and a row block at ``q0 >=
    window`` walks all of its chains at once in straight-line code: a chain
    at ``c0`` sees key block ``c0 - window`` under the strict upper
    triangle (key ``c`` of row ``i``, both block-local, iff ``c > i``), the
    ``window / bk - 1`` blocks after it whole, and its own block under the
    lower triangle (``c <= i``).  The two triangles leave one live score of
    two for every (row, column), so the edge and the diagonal block are one
    tile of ``bk`` keys a row and one softmax pass, and ``P`` goes back to
    each block's values by the same triangle.  A row block before the
    window walks every key block up to its own last under the whole mask,
    all chains at once."""
    q0 = pl.multiple_of(pl.program_id(2) * bq, bq)
    if half is None:
        o_ref, = refs
        q = q_ref[0]
        keys = k_ref.at[0]
    else:
        c_ref, s_ref, o_ref, keys = refs
        lane = jax.lax.broadcasted_iota(jnp.int32, (bq, LANES), 1)

        def rotated(x):
            return _rotated(x, lane, c_ref, s_ref, half)

        q = rotated(q_ref[0])

        @pl.when(pl.program_id(1) % group == 0)
        def _():
            keys[pl.ds(q0, bq), :] = rotated(k_ref[0, pl.ds(q0, bq), :])

    q = q * (LANES ** -0.5)  # a weak scalar: q keeps its type
    rb = bk if fold else bq  # a chain's rows
    chains = [(q[c * rb:(c + 1) * rb], c) for c in range(bq // rb)]
    local = jax.lax.broadcasted_iota(jnp.int32, (rb, bk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (rb, bk), 1)

    def scores(q, k0):
        return jax.lax.dot_general(q, keys[pl.ds(k0, bk), :],
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def softmax_step(carry, s):
        m, l, acc = carry
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        e = jnp.exp(s - m_new)
        a = jnp.exp(m - m_new)
        return m_new, a * l + e.sum(axis=-1, keepdims=True), a * acc, e

    def step(j, carry, q, rows, masked: bool):
        k0 = pl.multiple_of(j * bk, bk)
        s = scores(q, k0)
        if masked:
            at = k0 + cols
            seen = at <= rows
            if window is not None:
                seen &= at > rows - window
            s = jnp.where(seen, s, MASKED)
        m, l, acc, e = softmax_step(carry, s)
        v = v_ref[0, pl.ds(k0, bk), :]
        return m, l, acc + jnp.dot(e.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)

    def walk(carry):  # the block's rows as one chain
        # key blocks wholly under the diagonal (and inside the band) need
        # no mask: [lo, inner) masked at the band's lower edge, [inner,
        # whole) bare, [whole, hi) masked at the diagonal
        masked, bare = (functools.partial(step, q=q, rows=q0 + local,
                                          masked=m) for m in (True, False))
        hi = (q0 + bq - 1) // bk + 1
        whole = (q0 + 1) // bk  # blocks whose last key is <= the first row
        if window is None:
            lo = inner = 0
        else:
            lo = jnp.maximum(q0 - window + 1, 0) // bk
            # first block whose first key every row of the block still sees
            inner = jnp.minimum(
                jnp.maximum(q0 + bq - window, 0) // bk
                + (jnp.maximum(q0 + bq - window, 0) % bk > 0), whole)
            inner = jnp.maximum(inner, lo)
        carry = jax.lax.fori_loop(lo, inner, masked, carry)
        carry = jax.lax.fori_loop(inner, whole, bare, carry)
        return jax.lax.fori_loop(whole, hi, masked, carry)

    def band(carries):
        upper = cols > local
        out = []
        for (q, c), carry in zip(chains, carries):
            edge = pl.multiple_of(q0 + c * bk - window, bk)
            diag = pl.multiple_of(q0 + c * bk, bk)
            m, l, acc, e = softmax_step(
                carry, jnp.where(upper, scores(q, edge), scores(q, diag)))
            p = e.astype(v_ref.dtype)
            zero = jnp.zeros_like(p)
            for part, k0 in ((jnp.where(upper, p, zero), edge),
                             (jnp.where(upper, zero, p), diag)):
                acc += jnp.dot(part, v_ref[0, pl.ds(k0, bk), :],
                               preferred_element_type=jnp.float32)
            out.append((m, l, acc))

        def inside(j, carries):  # the window's whole blocks between
            return tuple(step(q0 // bk + c - window // bk + j, carry, q,
                              None, masked=False)
                         for (q, c), carry in zip(chains, carries))

        return jax.lax.fori_loop(1, window // bk, inside, tuple(out))

    def early(carries):  # every key block up to the row block's last,
        def masked(j, carries):  # under the whole mask
            return tuple(step(j, carry, q, q0 + c * bk + local, masked=True)
                         for (q, c), carry in zip(chains, carries))

        return jax.lax.fori_loop(0, (q0 + bq) // bk, masked, carries)

    carries = tuple((jnp.full((rb, 1), MASKED, jnp.float32),
                     jnp.zeros((rb, 1), jnp.float32),
                     jnp.zeros((rb, LANES), jnp.float32)) for _ in chains)
    carries = (jax.lax.cond(q0 >= window, band, early, carries) if fold
               else (walk(*carries),))
    for (_, c), (_, l, acc) in zip(chains, carries):
        o_ref[0, c * rb:(c + 1) * rb] = (acc / l).astype(o_ref.dtype)


def blocked_attention(q, k, v, n_heads: int, n_kv_heads: int,
                      window: Optional[int] = None,
                      block_q: Optional[int] = None,
                      block_k: Optional[int] = None,
                      interpret: Optional[bool] = None,
                      rotary=None):
    """Causal softmax attention with grouped heads of width 128, token-major.

    ``q``: ``[B, T, n_heads * 128]``; ``k``, ``v``: ``[B, T, n_kv_heads *
    128]``, query head ``h`` reading key/value head ``h // (n_heads /
    n_kv_heads)``; with ``window`` query ``i`` sees keys ``i - window + 1 ...
    i``.  Returns ``[B, T, n_heads * 128]``.  A head is one 128-lane column
    block, so the ``BlockSpec`` index maps pick heads and groups out of the
    projections as the matmuls left them.  A grid step holds one block of
    query rows and the whole K and V of its key/value head (fetched once a
    head group: the block index does not change from one query head or row
    block to the next), and walks only the key blocks that lie under the
    diagonal and, with a window, inside the band.  T is padded to whole
    blocks: the padded keys come after every real row, and the padded rows
    are cut off.

    ``rotary`` = ``(cos, sin)``, each ``[T, rot/2]`` float32 (``rot`` <=
    128): q and k come unrotated and the kernel applies :func:`rotate`'s
    arithmetic, the same roundings in the same order.  A head's q rows in a
    step are one ``[block_q, 128]`` tile, so the rotation is a lane roll and
    two products against full-width tables (``_lane_tables``), whose row block
    arrives with q's; k is rotated once a (batch row, key/value head), a
    row block a step of the group's first head, into a VMEM scratch that
    every walk reads in k's place.  The grid runs a batch row's heads and
    row blocks in order for that (both ``arbitrary``).

    A window of whole key blocks is walked as ``_blocked_kernel`` says under
    ``fold``, its key block :data:`BAND_BLOCK_K` unless ``block_k`` is
    given.
    """
    b, t, _ = q.shape
    if interpret is None:
        interpret = _interpret()
    bq, bk, tp = _blocks(t, window, block_q, block_k)
    if tp != t:
        q, k, v = (jnp.pad(a, ((0, 0), (0, tp - t), (0, 0)))
                   for a in (q, k, v))
    group = n_heads // n_kv_heads
    itemsize = jnp.dtype(q.dtype).itemsize
    seen = tp * (tp + 1) // 2 if window is None else tp * min(window, tp)
    q_spec = pl.BlockSpec((1, bq, LANES), lambda i, h, r: (i, r, h))
    kv_spec = pl.BlockSpec((1, tp, LANES), lambda i, h, r: (i, 0, h // group))
    operands, in_specs = [q, k, v], [q_spec, kv_spec, kv_spec]
    scratch, half = [], None
    if rotary is not None:
        half = rotary[0].shape[-1]
        table_spec = pl.BlockSpec((bq, LANES), lambda i, h, r: (r, 0))
        operands += _lane_tables(*rotary, tp)
        in_specs += [table_spec, table_spec]
        scratch = [pltpu.VMEM((tp, LANES), k.dtype)]
    out = pl.pallas_call(
        functools.partial(_blocked_kernel, bq=bq, bk=bk, window=window,
                          group=group, half=half,
                          fold=_folds(window, bq, bk, tp)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(b, n_heads, tp // bq),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",
                                 "parallel" if rotary is None else "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * n_heads * seen * LANES,
            transcendentals=b * n_heads * seen,
            bytes_accessed=2 * b * tp * (n_heads + n_kv_heads) * LANES
            * itemsize),
        interpret=interpret,
        name=BLOCKED_KERNEL_NAME,
    )(*operands)
    return out[:, :t] if tp != t else out


def plain_grouped_attention(q, k, v, n_heads: int, n_kv_heads: int,
                            causal: bool = False,
                            window: Optional[int] = None):
    """``full_attention`` with the explicit mask over separate projections:
    the key/value heads repeated over their groups, as XLA lowers it."""
    from ..parallel.ring_attention import full_attention

    b, t, _ = q.shape
    dh = q.shape[-1] // n_heads
    q = q.reshape(b, t, n_heads, dh)
    k, v = (jnp.repeat(a.reshape(b, t, n_kv_heads, dh),
                       n_heads // n_kv_heads, axis=2) for a in (k, v))
    return full_attention(q, k, v, causal=causal,
                          window=window).reshape(b, t, n_heads * dh)


# -- one primitive, its lowerings (``ops/lowering.py`` chooses) -------------

attention_p = Primitive("nns_full_attention")


def attention(q, n_heads: int, causal: bool = False, k=None, v=None,
              n_kv_heads: Optional[int] = None, window: Optional[int] = None,
              rotary=None):
    """Softmax attention, token-major.  Over the fused projection ``q`` =
    ``[B, T, 3*d]`` → ``[B, T, d]``, or with ``k`` and ``v`` over separate
    projections ``[B, T, n_heads*dh]`` and ``[B, T, n_kv_heads*dh]``
    (grouped heads), ``window`` keys back under ``causal``; with ``rotary``
    = ``(cos, sin)``, each ``[T, rot/2]`` float32, q and k come unrotated
    and the lowering applies :func:`rotate` to both.  See the module's
    docstring for which lowering a call gets."""
    if window is not None and not causal:
        raise ValueError("a window needs causal=True")
    if k is None:
        if (window is not None or rotary is not None
                or n_kv_heads not in (None, n_heads)):
            raise ValueError("the fused projection has neither a window, "
                             "grouped heads nor rotary tables")
        return attention_p.bind(q, n_heads=n_heads, n_kv_heads=n_heads,
                                causal=causal, window=None)
    return attention_p.bind(q, k, v, *(rotary or ()), n_heads=n_heads,
                            n_kv_heads=n_kv_heads or n_heads, causal=causal,
                            window=window)


def _plain(q, *rest, n_heads, n_kv_heads, causal, window):
    if not rest:
        return plain_attention(q, n_heads, causal)
    k, v, *tables = rest
    if tables:
        q, k = rotate(q, *tables, n_heads), rotate(k, *tables, n_kv_heads)
    return plain_grouped_attention(q, k, v, n_heads, n_kv_heads, causal,
                                   window)


def _abstract(q, *rest, **_):
    return q if rest else q.update(shape=(*q.shape[:-1], q.shape[-1] // 3))


attention_p.def_impl(jax.jit(
    lambda *operands, **kw: attention_p.bind(*operands, **kw),
    static_argnames=("n_heads", "n_kv_heads", "causal", "window")))
attention_p.def_abstract_eval(_abstract)


def count_attention(path: str, rotary: Optional[str] = None) -> None:
    """Count an attention call lowered, by path and, with tables, by where
    q is rotated (``ops/sparse_attention`` counts its calls here too)."""
    count("nnstpu_attention_lowerings_total",
          "attention calls lowered into a program, by the path chosen (fused "
          "= the whole-row Pallas kernel, blocked = the key-block walk with "
          "grouped heads, plain = full_attention through XLA; "
          "ops/sparse_attention: latent_sparse = the selected-keys kernel, "
          "latent_sparse_plain = its walk through XLA, latent[_plain] = "
          "the same over every causal key, index_select[_plain] = a "
          "selection's scoring pass)", path=path)
    if rotary is not None:
        count("nnstpu_attention_rotary_total",
              "attention calls lowered with rotary tables, by where q (and, "
              "for the blocked kernel, k) is rotated (kernel = on the "
              "kernel's own blocks in VMEM, outside = rotate() through XLA "
              "before the attention)",
              where=rotary)


def _lower_plain(avals, **kw):
    count_attention("plain", "outside" if len(avals) == 5 else None)
    return functools.partial(_plain, **kw)


def _lower_kernel(avals, *, n_heads, n_kv_heads, causal, window):
    if len(avals) == 1 and tiles(avals[0].shape, avals[0].dtype, n_heads):
        count_attention("fused")
        return lambda a: fused_attention(a, n_heads, causal,
                                         interpret=False)
    rotary = len(avals) == 5
    if len(avals) >= 3 and blocked_tiles(
            avals[0].shape, avals[1].shape, avals[0].dtype, n_heads,
            n_kv_heads, causal, avals[3].shape if rotary else None):
        count_attention("blocked", "kernel" if rotary else None)
        if window is not None:
            count("nnstpu_attention_band_walk_total",
                  "windowed blocked-kernel calls lowered, by how a row "
                  "block past the window walks the band's edge and "
                  "diagonal key blocks (folded = as one tile of scores, "
                  "split = as two masked steps)",
                  walk="folded" if _folds(window, *_blocks(
                      avals[0].shape[1], window)) else "split")
        return lambda q, k, v, *tables: blocked_attention(
            q, k, v, n_heads, n_kv_heads, window, interpret=False,
            rotary=tables or None)
    return None


register(attention_p, _lower_plain, _lower_kernel)


def _jvp(primals, tangents, **kw):
    # derivatives are full_attention's: training runs the plain path
    return jax.jvp(functools.partial(_plain, **kw), primals,
                   tuple(ad.instantiate_zeros(t) for t in tangents))


def _batch(args, dims, **kw):
    # a mapped axis is more batch rows; rotary tables are every row's
    if any(d is not None for d in dims[3:]):
        raise NotImplementedError("attention: mapped rotary tables")
    moved = [jnp.moveaxis(a, d, 0) for a, d in zip(args[:3], dims)]
    lead = moved[0].shape[:2]
    out = attention_p.bind(*(a.reshape(-1, *a.shape[2:]) for a in moved),
                           *args[3:], **kw)
    return out.reshape(*lead, *out.shape[1:]), 0


ad.primitive_jvps[attention_p] = _jvp
batching.primitive_batchers[attention_p] = _batch
