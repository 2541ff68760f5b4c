"""Ulysses-style sequence parallelism: all-to-all head redistribution.

The complement of :mod:`.ring_attention` (DeepSpeed-Ulysses pattern,
Jacobs et al. 2023): activations arrive sharded on the **sequence** axis;
an all-to-all re-shards them on the **head** axis so each device runs full
-sequence attention for its heads, and a second all-to-all restores
sequence sharding.  Two collectives per layer, compiled by XLA over ICI —
preferable to the ring when head count ≥ mesh size and the sequence fits
per-device once re-sharded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .ring_attention import full_attention


def ulysses_attention(
    q,
    k,
    v,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
):
    """Attention with inputs/outputs (B, T, H, D) sharded on T over
    ``axis``; requires H divisible by the axis size."""
    n = mesh.shape[axis]
    h = q.shape[2]
    if h % n != 0:
        raise ValueError(f"heads {h} not divisible by mesh axis {axis}={n}")

    def shard_fn(q, k, v):
        # (B, T/n, H, D) → (B, T, H/n, D): gather sequence, scatter heads
        def seq_to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
        out = full_attention(qh, kh, vh, causal=causal)
        return heads_to_seq(out)

    spec = P(None, axis, None, None)
    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
