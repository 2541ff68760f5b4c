"""The one place a primitive's call chooses a Pallas kernel or XLA
(``ops/lowering.py``), driven through every primitive that registers there.

Each case is one call at shapes its kernel tiles, lowered from this CPU
host three ways: for the CPU, for a TPU program GSPMD partitions over four
devices (the operands of rows sharded by them), and for a TPU as the body
of a ``shard_map`` over every axis of a four-device mesh.  Only the last may
take the kernel, and each lowering is counted under the path it took.
"""

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from nnstreamer_tpu.obs.metrics import REGISTRY
from nnstreamer_tpu.ops import fused_attention as fa
from nnstreamer_tpu.ops import grouped_experts, ssm_scan
from nnstreamer_tpu.ops import sparse_attention as sa
from nnstreamer_tpu.parallel import moe
from nnstreamer_tpu.parallel.mesh import make_mesh

ATTENTION = "nnstpu_attention_lowerings_total"
DEVICES = 4


class Case(NamedTuple):
    fn: object
    shapes: Tuple  # (shape, dtype, split by rows over the mesh)
    counter: str
    kernel: str    # the kernel path's label
    plain: str     # the plain path's label
    name: str      # the kernel's name in the program


def f32(*shape, split=True):
    return shape, jnp.float32, split


CASES = {
    "attention_fused": Case(
        lambda qkv: fa.attention(qkv, 4, False),
        (f32(DEVICES, 384, 384),), ATTENTION, "fused", "plain",
        fa.KERNEL_NAME),
    "attention_blocked": Case(
        lambda q, k, v: fa.attention(q, 2, True, k=k, v=v, n_kv_heads=1),
        (f32(DEVICES, 256, 256), f32(DEVICES, 256, 128),
         f32(DEVICES, 256, 128)), ATTENTION, "blocked", "plain",
        fa.BLOCKED_KERNEL_NAME),
    "select_keys": Case(
        lambda q_i, k_i, w: sa.select_keys(q_i, k_i, w, 100),
        (f32(DEVICES, 1024, 256), f32(DEVICES, 1024, 128),
         f32(DEVICES, 1024, 2)), ATTENTION, "index_select",
        "index_select_plain", sa.INDEX_KERNEL_NAME),
    "latent_with_a_mask": Case(
        lambda q, k_n, k_r, v, mask: sa.latent_sparse_attention(
            q, k_n, k_r, v, mask, 2),
        (f32(DEVICES, 1024, 256), f32(DEVICES, 1024, 128),
         f32(DEVICES, 1024, 64), f32(DEVICES, 1024, 256),
         ((DEVICES, 1024, 1024), jnp.int8, True)), ATTENTION,
        "latent_sparse", "latent_sparse_plain", sa.KERNEL_NAME),
    "latent_without_a_mask": Case(
        lambda q, k_n, k_r, v: sa.latent_sparse_attention(
            q, k_n, k_r, v, None, 2),
        (f32(DEVICES, 1024, 384), f32(DEVICES, 1024, 256),
         f32(DEVICES, 1024, 64), f32(DEVICES, 1024, 256)), ATTENTION,
        "latent", "latent_plain", sa.LATENT_KERNEL_NAME),
    "ssd_scan": Case(
        functools.partial(ssm_scan.ssd_scan, chunk=128, n_groups=1),
        (f32(DEVICES, 256, 256), f32(DEVICES, 256, 2), f32(2, split=False),
         f32(DEVICES, 256, 128), f32(DEVICES, 256, 128),
         f32(2, split=False)), "nnstpu_ssm_scan_lowerings_total", "kernel",
        "plain", ssm_scan.KERNEL_NAME),
    # 512 tokens a device, top-2 of 4 experts of 128 x 128, 2 held: a pass
    # of 1024 rows, one row tile an expert
    "a_shares_experts": Case(
        functools.partial(moe.routed_experts, first=0, total=4),
        (f32(DEVICES * 512, 128), f32(DEVICES * 512, 2),
         ((DEVICES * 512, 2), jnp.int32, True), f32(2, 128, 256, split=False),
         f32(2, 128, 128, split=False)), "nnstpu_moe_lowerings_total",
        "fused", "grouped", grouped_experts.KERNEL_NAME),
    "all_experts": Case(
        moe.routed_experts,
        (f32(DEVICES * 512, 128), f32(DEVICES * 512, 2),
         ((DEVICES * 512, 2), jnp.int32, True), f32(2, 128, 256, split=False),
         f32(2, 128, 128, split=False)), "nnstpu_moe_lowerings_total",
        "fused", "grouped", grouped_experts.KERNEL_NAME),
}


def counts(name):
    metric = REGISTRY.get(name)
    if metric is None:
        return {}
    return {key[0]: int(child.value) for key, child in metric.children()}


def lowered(case, jitted, platform):
    """The program's text and what its lowering added to the case's
    counter."""
    before = counts(case.counter)
    text = jitted.trace(*(jax.ShapeDtypeStruct(shape, dtype)
                          for shape, dtype, _ in case.shapes)).lower(
        lowering_platforms=(platform,)).as_text()
    return text, {path: n - before.get(path, 0)
                  for path, n in counts(case.counter).items()
                  if n != before.get(path, 0)}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_only_a_one_device_tpu_program_takes_the_kernel(case):
    mesh = make_mesh((DEVICES,), ("dp",), devices=jax.devices()[:DEVICES])
    specs = tuple(P("dp") if split else P() for _, _, split in case.shapes)

    text, counted = lowered(case, jax.jit(case.fn), "cpu")
    assert "tpu_custom_call" not in text and counted == {case.plain: 1}

    partitioned = jax.jit(case.fn, in_shardings=tuple(
        NamedSharding(mesh, spec) for spec in specs))
    text, counted = lowered(case, partitioned, "tpu")
    assert "tpu_custom_call" not in text and counted == {case.plain: 1}

    body = jax.jit(jax.shard_map(case.fn, mesh=mesh, in_specs=specs,
                                 out_specs=P("dp"), check_vma=False))
    text, counted = lowered(case, body, "tpu")
    assert case.name in text and counted == {case.kernel: 1}
