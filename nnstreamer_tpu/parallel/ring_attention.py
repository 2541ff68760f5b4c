"""Ring attention: sequence-parallel attention over a mesh axis.

Long-context support beyond the reference's envelope (survey §5: the
reference's "sequence" machinery is temporal windowing only).  Streams can
carry sequences far longer than one chip's HBM by sharding the sequence
dimension across the mesh; attention then runs **blockwise**, rotating K/V
shards around the ring with ``jax.lax.ppermute`` over ICI while each device
accumulates its queries' output with an online (streaming) softmax — the
communication pattern of Ring Attention (Liu et al., 2023), expressed the
JAX way: ``shard_map`` over a ``Mesh``, XLA overlapping the permute with
the per-block compute.

No torch/NCCL analog is ported: the collective is compiled by XLA over
ICI/DCN exactly like every other sharded op in this framework.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _online_block(q, k, v, m, l, acc, q_pos, k_pos, scale, causal):
    """One blockwise-attention step with streaming-softmax accumulators.

    q: (B, Tq, H, D); k/v: (B, Tk, H, D); m/l: (B, H, Tq); acc like q
    (but (B, H, Tq, D)); q_pos/k_pos: global positions for masking.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = k_pos[None, None, None, :] > q_pos[None, None, :, None]
        s = jnp.where(mask, -jnp.inf, s)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows: exp(-inf - -inf) — keep them zeroed
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    if causal:
        p = jnp.where(mask, 0.0, p)
    corr = jnp.exp(jnp.where(jnp.isneginf(m), m_new, m) - m_safe)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v)
    return m_new, l_new, acc_new


def full_attention(q, k, v, causal: bool = False, window=None):
    """Reference single-device attention (the golden path for tests).
    ``window`` (with ``causal``): query i sees keys i - window + 1 ... i."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        rows, cols = jnp.arange(tq)[:, None], jnp.arange(tk)[None, :]
        mask = cols > rows
        if window is not None:
            mask |= cols <= rows - window
        s = jnp.where(mask[None, None], -jnp.inf, s)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def ring_attention(
    q,
    k,
    v,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
):
    """Attention over sequences sharded on ``axis`` of ``mesh``.

    q/k/v: (B, T, H, D) with T sharded over ``axis`` (global T = sum of the
    shards).  Returns (B, T, H, D) sharded the same way.  Peak memory per
    device is O(T/n · T/n) per block pair instead of O(T²).
    """
    n = mesh.shape[axis]
    scale = q.shape[-1] ** -0.5

    def shard_fn(q, k, v):
        # block-local sizes; global positions from the ring index
        t_q = q.shape[1]
        t_k = k.shape[1]
        idx = jax.lax.axis_index(axis)
        q_pos = idx * t_q + jnp.arange(t_q)

        b, _, h, d = q.shape
        m = jnp.full((b, h, t_q), -jnp.inf, jnp.float32)
        l = jnp.zeros((b, h, t_q), jnp.float32)
        acc = jnp.zeros((b, h, t_q, d), jnp.float32)

        perm = [(j, (j + 1) % n) for j in range(n)]

        def block(i, m, l, acc, k, v):
            # the kv block now resident arrived from device (idx - i) mod n
            src = (idx - i) % n
            k_pos = src * t_k + jnp.arange(t_k)
            return _online_block(
                q.astype(jnp.float32),
                k.astype(jnp.float32),
                v.astype(jnp.float32),
                m, l, acc, q_pos, k_pos, scale, causal,
            )

        def body(i, carry):
            m, l, acc, k, v = carry
            m, l, acc = block(i, m, l, acc, k, v)
            # rotate kv one step around the ring (overlaps with next block
            # compute under XLA's async collectives)
            k = jax.lax.ppermute(k, axis, perm)
            v = jax.lax.ppermute(v, axis, perm)
            return m, l, acc, k, v

        # n-1 rotations; the final block consumes the last shard in place
        # (no dead ppermute on the hot path)
        m, l, acc, k, v = jax.lax.fori_loop(0, n - 1, body, (m, l, acc, k, v))
        m, l, acc = block(n - 1, m, l, acc, k, v)
        del k, v
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros
        out = (acc / l[..., None]).astype(q.dtype)
        return jnp.transpose(out, (0, 2, 1, 3))  # (B, Tq, H, D)

    spec = P(None, axis, None, None)
    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def sequence_sharding(mesh: Mesh, rank: int = 4, axis: str = "sp") -> NamedSharding:
    """NamedSharding placing the sequence dim (axis 1 of (B,T,...) inputs)
    on ``axis``."""
    spec = [None] * rank
    spec[1] = axis
    return NamedSharding(mesh, P(*spec))
