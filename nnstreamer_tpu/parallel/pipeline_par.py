"""Pipeline parallelism over the ``pp`` mesh axis (GPipe-style microbatch
rotation with ``ppermute``).

Stages live on successive devices along ``pp``; microbatches enter stage 0
and hop one stage per tick over the ICI ring.  A batch of M microbatches
through S stages takes M + S - 1 ticks (the classic fill/drain bubble).
All shapes are static; the schedule is a ``lax.scan`` inside ``shard_map``,
so XLA sees one compiled program per device with explicit collective
permutes — the TPU-native equivalent of the reference's process-pipeline
(queue-decoupled elements), scaled to model layers instead of stream
elements.

Contract: ``stage_fn(stage_params, x) -> y`` with ``y.shape == x.shape``
(homogeneous stages — transformer blocks, MLP trunks).  ``stage_params``
is a pytree whose leaves carry a leading stage dim of size S; device ``i``
computes with slice ``i``.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage_params):
    """[stage0_tree, stage1_tree, ...] → one tree with leading stage dim."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage_params)


def gpipe_apply(
    stage_fn: Callable,
    stage_params,
    x,
    mesh: Mesh,
    axis: str = "pp",
    microbatches: int | None = None,
):
    """Run ``x`` (leading batch dim) through S pipelined stages.

    ``microbatches`` defaults to S (bubble fraction (S-1)/(M+S-1)); the
    batch must divide evenly.  Returns the same shape as ``x``.
    """
    s = mesh.shape[axis]
    m = microbatches or s
    b = x.shape[0]
    if b % m:
        raise ValueError(f"batch {b} not divisible into {m} microbatches")
    xs = x.reshape(m, b // m, *x.shape[1:])

    # The microbatch list replicates to all stages (only stage 0 reads it):
    # the simple layout for a streaming-inference pipeline, where activations
    # — not inputs — dominate per-device memory.  Pre-shard the batch over m
    # upstream before reaching for a scatter here.
    in_specs = (
        jax.tree.map(lambda _: P(axis), stage_params),
        P(),
    )

    # The scan carry starts replicated (zeros) but becomes device-varying
    # after the first tick; relax the varying-axes check.
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=in_specs, out_specs=P(axis),
        check_vma=False,
    )
    def run(params_local, xs_all):
        # leading stage dim is 1 on-device: drop it
        p_local = jax.tree.map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(axis)
        ticks = m + s - 1
        perm = [(i, i + 1) for i in range(s - 1)]  # stage i → i+1

        def tick(carry, t):
            prev_out, outbuf = carry
            recv = jax.lax.ppermute(prev_out, axis, perm)
            feed = xs_all[jnp.clip(t, 0, m - 1)]
            inp = jnp.where(idx == 0, feed, recv)
            out = stage_fn(p_local, inp)
            # last stage emits microbatch t-(s-1)
            mb = t - (s - 1)
            write = (idx == s - 1) & (mb >= 0)
            upd = jax.lax.dynamic_update_slice(
                outbuf,
                out[None].astype(outbuf.dtype),
                (jnp.clip(mb, 0, m - 1),) + (0,) * out.ndim,
            )
            outbuf = jnp.where(write, upd, outbuf)
            return (out, outbuf), None

        zero = jnp.zeros_like(xs_all[0])
        outbuf0 = jnp.zeros_like(xs_all)
        (_, outbuf), _ = jax.lax.scan(
            tick, (zero, outbuf0), jnp.arange(ticks)
        )
        # per-stage output shard; only the last stage's is valid — the
        # caller slices it, so no cross-ring all-reduce is paid
        return outbuf

    stacked = run(stage_params, xs)  # (s*m, b//m, ...): per-stage buffers
    out = stacked[(s - 1) * m:]  # the last stage's (valid) buffer
    return out.reshape(b, *x.shape[1:])
