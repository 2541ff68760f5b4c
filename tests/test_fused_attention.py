"""The fused attention kernel (``ops/fused_attention.py``) and the lowering
rule that selects it.

The kernel runs here in Pallas interpret mode against ``full_attention``.
Which lowering a ``full`` attention call gets is decided when its program is
lowered, so the selection is driven by lowering one trace for ``tpu`` and for
``cpu`` from this CPU host, and by compiling for a described v5e (no chip
attached): one device, and four with the batch sharded over them.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from nnstreamer_tpu.models import transformer, vit
from nnstreamer_tpu.obs.metrics import REGISTRY
from nnstreamer_tpu.ops import fused_attention as fa
from nnstreamer_tpu.parallel.mesh import make_mesh

COUNTER = "nnstpu_attention_lowerings_total"


def lowerings(name=COUNTER):
    """A one-label counter's values by label, nothing before its first count."""
    metric = REGISTRY.get(name)
    if metric is None:
        return {}
    return {key[0]: int(child.value) for key, child in metric.children()}


@pytest.fixture
def counted():
    """Counts of this test alone: ``counted()`` is the rise since it began."""
    before = lowerings()

    def since():
        return {path: n - before.get(path, 0)
                for path, n in lowerings().items() if n - before.get(path, 0)}

    return since


@pytest.fixture
def kernel_everywhere(monkeypatch):
    """The plain lowering runs the kernel too, interpreted: what a program
    computes through the fused path, on a host with no TPU."""
    monkeypatch.setattr(fa, "plain_attention", lambda qkv, n_heads, causal:
                        fa.fused_attention(qkv, n_heads, causal,
                                           interpret=True))


def lowered_for(platform, fn, *args):
    """StableHLO of ``fn`` lowered for ``platform`` from this host."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=(platform,)).as_text()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_heads", [4, 16])
@pytest.mark.parametrize("head_width", [32, 96, 128])
@pytest.mark.parametrize("t", [16, 72, 200])
def test_kernel_matches_full_attention(t, head_width, n_heads, dtype, causal):
    qkv = jax.random.normal(jax.random.PRNGKey(t + head_width + n_heads),
                            (1, t, 3 * n_heads * head_width),
                            jnp.float32).astype(dtype)
    want = fa.plain_attention(qkv.astype(jnp.float32), n_heads, causal)
    got = fa.fused_attention(qkv, n_heads, causal=causal)
    assert got.dtype == qkv.dtype and got.shape == want.shape
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(want), atol=tol, rtol=tol)


def test_kernel_refuses_heads_that_do_not_group():
    with pytest.raises(ValueError, match="does not tile"):
        fa.fused_attention(jnp.zeros((1, 16, 3 * 2 * 32)), n_heads=2)


@pytest.mark.parametrize("shape,dtype,n_heads,want", [
    ((48, 576, 4608), "bfloat16", 16, True),    # the benchmark's cell
    ((1, 576, 4608), "float32", 16, True),
    ((2, 384, 384), "bfloat16", 4, True),       # 4 heads of 32: one group
    ((2, 400, 2304), "bfloat16", 12, True),     # T need not tile
    ((2, 1024, 3072), "bfloat16", 8, True),     # the longest T of 128 wide
    ((2, 383, 384), "bfloat16", 4, False),      # under MIN_TOKENS
    ((48, 196, 2304), "bfloat16", 12, False),   # ViT-B/16 at 224: XLA ahead
    ((2, 384, 192), "float32", 2, False),       # 2 heads of 32: half a group
    ((2, 384, 384), "float32", 8, False),       # head width 16
    ((2, 2048, 4608), "bfloat16", 16, False),   # a row of scores past VMEM
    ((2, 384, 384), "float16", 4, False),
    ((384, 384), "bfloat16", 4, False),
])
def test_tiles_is_the_tiling_rule(shape, dtype, n_heads, want):
    assert fa.tiles(shape, jnp.dtype(dtype), n_heads) is want


def small_vit(n_heads=4, n_layers=3, d_model=128, batch=2, build=vit.build,
              dtype=jnp.float32, image_size=140):
    """A fresh model (so no trace or lowering of another test is cached for
    it) whose heads tile, and a batch of frames."""
    model = build(num_classes=10, image_size=image_size, patch=7,
                  d_model=d_model, n_heads=n_heads, n_layers=n_layers,
                  batch=batch, dtype=dtype, seed=3)  # 140 / 7: 400 tokens
    frames = np.random.default_rng(5).standard_normal(
        (batch, image_size, image_size, 3)).astype(np.float32)
    return model, frames


def test_cpu_build_lowers_every_layer_plain(counted):
    model, frames = small_vit()
    jax.jit(model.fn())(frames)
    assert counted() == {"plain": 3}


def test_shape_discovery_lowers_and_counts_nothing(counted):
    model, frames = small_vit()
    assert jax.eval_shape(model.fn(), frames).shape == (2, 10)
    jax.jit(model.fn()).trace(frames)
    assert counted() == {}


def test_one_trace_lowers_fused_for_a_tpu_and_plain_for_a_cpu(counted):
    """The backend's ``cpu_fallback`` retry on a TPU host lowers the same
    program for the CPU: the choice is the lowering's, not the host's."""
    model, frames = small_vit()
    traced = jax.jit(model.fn()).trace(frames)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 3 and fa.KERNEL_NAME in text
    assert counted() == {"fused": 3}
    text = traced.lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in text
    assert counted() == {"fused": 3, "plain": 3}


def test_the_kernel_gives_a_vit_the_same_logits(counted, monkeypatch):
    model, frames = small_vit()
    want = np.asarray(jax.jit(model.fn())(frames))
    monkeypatch.setattr(fa, "plain_attention", lambda qkv, n_heads, causal:
                        fa.fused_attention(qkv, n_heads, causal,
                                           interpret=True))
    model, _ = small_vit()
    got = np.asarray(jax.jit(model.fn())(frames))
    assert counted() == {"plain": 6}
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_the_kernel_gives_a_causal_encoder_the_same_result(kernel_everywhere):
    params = transformer.init_params(jax.random.PRNGKey(0), d_model=128,
                                     n_heads=4, n_layers=2, d_in=8, n_out=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 384, 8))
    got = jax.jit(lambda a: transformer.apply(params, a, causal=True))(x)
    want = transformer.apply(params, x, attn="ulysses", causal=True,
                             mesh=make_mesh((1,), ("sp",),
                                            devices=jax.devices()[:1]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_quantized_build_takes_the_same_path(counted):
    model, frames = small_vit(n_layers=2, build=vit.build_quantized,
                              dtype=jnp.bfloat16)
    assert lowered_for("tpu", model.fn(), frames).count("tpu_custom_call") == 2
    assert counted() == {"fused": 2}


@pytest.mark.parametrize("shape", [
    dict(n_heads=2, d_model=64),  # two heads of 32 are half a lane tile
    dict(image_size=28),          # 16 tokens: XLA is ahead under MIN_TOKENS
], ids=["half_a_head_group", "short_sequence"])
def test_a_shape_that_does_not_tile_stays_plain_for_a_tpu(shape, counted):
    model, frames = small_vit(n_layers=2, **shape)
    assert "tpu_custom_call" not in lowered_for("tpu", model.fn(), frames)
    assert counted() == {"plain": 2}


def test_forty_layers_lowered_once_count_forty(counted):
    model, frames = small_vit(n_layers=40, batch=1, dtype=jnp.bfloat16)
    traced = jax.jit(model.fn()).trace(frames)
    traced.lower(lowering_platforms=("tpu",))
    assert counted() == {"fused": 40}
    traced.lower(lowering_platforms=("cpu",))
    assert counted() == {"fused": 40, "plain": 40}


def test_sequence_parallel_modes_are_not_counted(counted):
    params = transformer.init_params(jax.random.PRNGKey(0), d_model=32,
                                     n_heads=4, n_layers=1, d_in=8, n_out=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 8))
    mesh = make_mesh((4,), ("sp",), devices=jax.devices()[:4])
    transformer.apply(params, x, attn="ring", mesh=mesh, causal=False)
    assert counted() == {}


# -- programs over more than one device: Mosaic refuses what GSPMD partitions

def batch_over_a_mesh(model, frames):
    mesh = make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    return jax.jit(model.fn(), in_shardings=NamedSharding(mesh, P("dp"))), (
        frames,)


def committed_sharded_input(model, frames):
    mesh = make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    return jax.jit(model.fn()), (
        jax.device_put(frames, NamedSharding(mesh, P("dp"))),)


def manual_over_one_of_two_axes(model, frames):
    mesh = make_mesh((2, 2), ("dp", "tp"), devices=jax.devices()[:4])
    return jax.jit(jax.shard_map(
        model.fn(), mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        axis_names={"dp"}, check_vma=False)), (frames,)


@pytest.mark.parametrize("program", [
    batch_over_a_mesh, committed_sharded_input, manual_over_one_of_two_axes])
def test_a_partitioned_tpu_program_stays_plain(program, counted):
    jitted, args = program(*small_vit(batch=8))
    text = jitted.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in text
    assert counted() == {"plain": 3}


def test_the_body_of_a_whole_mesh_shard_map_is_fused(counted):
    """Mosaic lowers inside a ``shard_map`` over every axis of its mesh:
    each device runs the kernel on its own rows."""
    model, frames = small_vit(batch=8)
    mesh = make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    rows = jax.shard_map(model.fn(), mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"), check_vma=False)
    assert lowered_for("tpu", rows, frames).count("tpu_custom_call") == 3
    assert counted() == {"fused": 3}


def test_an_expert_parallel_encoder_stays_plain_for_a_tpu(counted):
    """``moe_mesh`` makes GSPMD partition the program through ``moe_ffn``'s
    sharding constraints; nothing on the way marks the trace."""
    ep = make_mesh((4,), ("ep",), devices=jax.devices()[:4])
    model = transformer.build(seq_len=384, d_in=8, n_out=4, d_model=128,
                              n_heads=4, n_layers=2, attn="full",
                              moe_experts=4, moe_mesh=ep, batch=4)
    x = np.zeros((4, 384, 8), np.float32)
    assert "tpu_custom_call" not in lowered_for("tpu", model.fn(), x)
    assert counted() == {"plain": 2}


def test_mesh_sharded_filter_compiles_its_program_plain(counted):
    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc

    model, frames = small_vit(n_layers=2, batch=8)
    p = Pipeline()
    src = p.add(DataSrc(data=[frames]))
    filt = p.add(TensorFilter(framework="jax-sharded", model=model,
                              custom="devices=8,axis=dp"))
    sink = p.add(TensorSink(collect=True))
    p.link_chain(src, filt, sink)
    p.run(timeout=60)
    assert len(sink.frames[0].tensor(0).sharding.device_set) == 8
    assert counted() == {"plain": 2}


# -- under jax's transformations -------------------------------------------

def test_derivatives_are_full_attentions():
    qkv = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 384))
    got = jax.grad(lambda a: (fa.attention(a, 4, True) ** 2).sum())(qkv)
    want = jax.grad(lambda a: (fa.plain_attention(a, 4, True) ** 2).sum())(qkv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_a_mapped_axis_is_more_batch_rows(counted):
    qkv = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 384, 384))
    mapped = jax.vmap(lambda a: fa.attention(a, 4, False), in_axes=1,
                      out_axes=1)
    assert lowered_for("tpu", mapped, qkv).count("tpu_custom_call") == 1
    assert counted() == {"fused": 1}
    np.testing.assert_allclose(
        np.asarray(mapped(qkv)[:, 1]),
        np.asarray(fa.plain_attention(qkv[:, 1], 4)), atol=1e-5)


# -- compiled for a described v5e, no chip attached -------------------------

@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a chip that is not attached can be written to the
    # persistent cache but not read back: keep these compiles out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("shape,dtype,n_heads,causal", [
    ((48, 576, 4608), "bfloat16", 16, False),   # siglip2_gopt16_384.mux48
    ((2, 576, 4608), "float32", 16, True),
    ((2, 384, 768), "bfloat16", 4, True),       # 2 heads of 64 a group
])
def test_mosaic_compiles_the_kernel(v5e_2x2, shape, dtype, n_heads, causal,
                                    counted):
    from jax.sharding import SingleDeviceSharding

    qkv = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                               sharding=SingleDeviceSharding(v5e_2x2[0]))
    compiled = jax.jit(lambda a: fa.attention(a, n_heads, causal)).lower(
        qkv).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and fa.KERNEL_NAME in text
    assert counted() == {"fused": 1}


def test_four_chips_compile_a_batch_sharded_tower_plain(v5e_2x2, counted):
    """A Mosaic kernel in a program GSPMD partitions is refused by jax
    ("Mosaic kernels cannot be automatically partitioned"): the benchmark
    tower's widths, two layers, its batch sharded over the four chips."""
    from jax.sharding import Mesh

    model = vit.build(num_classes=10, image_size=96, patch=16, d_model=1536,
                      n_heads=16, n_layers=2, batch=8,
                      dtype=jnp.bfloat16, seed=3)
    mesh = Mesh(np.array(v5e_2x2), ("dp",))
    frames = jax.ShapeDtypeStruct((8, 96, 96, 3), jnp.float32,
                                  sharding=NamedSharding(mesh, P("dp")))
    text = jax.jit(model.fn()).lower(frames).compile().as_text()
    assert "tpu_custom_call" not in text
    assert counted() == {"plain": 2}


def test_no_weight_argument_is_prefetched_across_program_runs(v5e_2x2):
    """What ``TPU_COMPILER_OPTIONS`` is for: with its weights as arguments
    the tower's program would hold two of them in VMEM from one run to the
    next, and in-step prefetches of other weights lose their room (the
    benchmark's widths, two layers; PERF.md has the 40-layer count)."""
    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.backends.jax_backend import (TPU_COMPILER_OPTIONS,
                                                     split_params)

    model = vit.build(num_classes=1000, image_size=384, patch=16,
                      d_model=1536, n_heads=16, n_layers=2, batch=48,
                      dtype=jnp.bfloat16, seed=3)
    arrays, merge = split_params(model.params)
    one = SingleDeviceSharding(v5e_2x2[0])
    weights = [jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=one)
               for a in arrays]  # as the benchmark's checkpoint holds them
    frames = jax.ShapeDtypeStruct((48, 384, 384, 3), jnp.bfloat16,
                                  sharding=one)

    def entry(w, x):
        return model.apply(merge(w), x)

    plain = jax.jit(entry).lower(weights, frames).compile().as_text()
    ours = jax.jit(entry, compiler_options=TPU_COMPILER_OPTIONS).lower(
        weights, frames).compile().as_text()
    assert "cross_program_prefetch" in plain
    assert "cross_program_prefetch" not in ours


# -- Laguna-XS.2's published widths (the cell laguna_xs2_l5.ctx16x4k) --------
#
# Compile-only, beside the tower's above because one process describes the
# topology: a second file of such tests could land on another worker.

def laguna_config():
    from nnstreamer_tpu.models import laguna

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return laguna.load_config(os.path.join(root, "benchmark", "configs",
                                           "laguna_xs2_l5.json"))


def rotary_counts():
    return lowerings("nnstpu_attention_rotary_total")


@pytest.mark.parametrize("rotary", [False, True], ids=["rotated_outside",
                                                       "with_the_tables"])
@pytest.mark.parametrize("heads,window,kind", [
    (48, None, "full_attention"),        # rot 64 of 128, YaRN frequencies
    (64, 512, "sliding_attention"),      # rot 128
], ids=["full_attention", "sliding_attention"])
def test_mosaic_compiles_the_blocked_kernel_at_16_windows_of_4096(
        v5e_2x2, heads, window, kind, rotary, counted):
    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.models import laguna

    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((16, 4096, heads * 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((16, 4096, 8 * 128), jnp.bfloat16, sharding=one)
    tables = (laguna.rotary_tables(laguna_config()["rope_parameters"][kind],
                                   128, 4096) if rotary else None)
    before = rotary_counts()
    walks = lowerings("nnstpu_attention_band_walk_total")
    compiled = jax.jit(lambda q, k, v: fa.attention(
        q, heads, True, k=k, v=v, n_kv_heads=8, window=window,
        rotary=tables)).lower(q, kv, kv).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and fa.BLOCKED_KERNEL_NAME in text
    assert counted() == {"blocked": 1}
    assert (rotary_counts().get("kernel", 0)
            == before.get("kernel", 0) + int(rotary))
    assert rotary_counts().get("outside", 0) == before.get("outside", 0)
    # the sliding layer's window is one 512-row block: its band is folded
    risen = {w: n - walks.get(w, 0) for w, n in lowerings(
        "nnstpu_attention_band_walk_total").items() if n - walks.get(w, 0)}
    assert risen == ({"folded": 1} if window else {})
    # the scores never reach HBM: no temporary of the [16, heads, T, T] kind
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("t,dtype", [(8704, jnp.bfloat16), (4096, jnp.float32)],
                         ids=["bf16", "f32"])
def test_mosaic_compiles_the_band_at_the_longest_t_it_tiles(v5e_2x2, t, dtype):
    """The band's two chains hold their tiles in straight-line code beside
    the whole K, V and rotated K: at the longest T ``blocked_tiles`` admits
    with a window and tables they still fit VMEM."""
    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.models import laguna

    one = SingleDeviceSharding(v5e_2x2[0])
    q, kv = (jax.ShapeDtypeStruct((1, t, h * 128), dtype, sharding=one)
             for h in (64, 8))
    tables = laguna.rotary_tables(
        laguna_config()["rope_parameters"]["sliding_attention"], 128, t)
    assert fa.blocked_tiles(q.shape, kv.shape, dtype, 64, 8, True,
                            tables[0].shape)
    assert not fa.blocked_tiles((1, t + 512, 64 * 128), (1, t + 512, 1024),
                                dtype, 64, 8, True, (t + 512, 64))
    text = jax.jit(lambda q, k, v: fa.attention(
        q, 64, True, k=k, v=v, n_kv_heads=8, window=512,
        rotary=tables)).lower(q, kv, kv).compile().as_text()
    assert fa.BLOCKED_KERNEL_NAME in text


@pytest.mark.parametrize("layer", [0, 1], ids=["full_attention",
                                               "sliding_attention"])
def test_no_projection_goes_through_float32_or_a_view_by_heads(v5e_2x2, layer):
    """``models/laguna.layer`` at the published widths (16 windows of 4096;
    after the attention half a dense MLP of a width no projection has),
    compiled for the chip: q and k
    reach the kernel as the products left them.  Rotated through XLA, the
    program held a float32 copy of each projection, a re-tiling of
    ``[tokens, heads * 128]`` to ``[heads, 128]`` and arrays of half heads
    (``f32[16,4096,8192]``, ``copy f32[8192,8,64,128]``,
    ``bf16[16,4096,64,64]``, ``bf16[16,4096,48,32]``): 137 ms of the token
    cell's 721 ms step (PERF.md)."""
    import re

    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.models import laguna

    cfg = dict(laguna_config(), mlp_layer_types=["dense"] * 5,
               intermediate_size=1536)
    heads = cfg["num_attention_heads_per_layer"][layer]
    kind = cfg["layer_types"][layer]
    assert (heads, kind) == ((48, "full_attention"),
                             (64, "sliding_attention"))[layer]
    one = SingleDeviceSharding(v5e_2x2[0])

    def struct(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    p = {"attn_norm": struct(d), "wq": struct(d, heads * 128),
         "wk": struct(d, 1024), "wv": struct(d, 1024),
         "wo": struct(heads * 128, d), "mlp_norm": struct(d),
         "mlp": {"w_in": struct(d, 2 * ff), "w_out": struct(ff, d)}}
    tables = {kind: laguna.rotary_tables(cfg["rope_parameters"][kind], 128,
                                         4096)}
    text = jax.jit(lambda p, x: laguna.layer(cfg, layer, p, x, tables)).lower(
        p, struct(16, 4096, d)).compile().as_text()
    assert fa.BLOCKED_KERNEL_NAME in text
    assert f"bf16[16,4096,{heads * 128}]" in text
    # no float32 array as wide as a projection, and no array whose trailing
    # dims split the projections' columns into heads or half heads
    assert not re.search(r"f32\[16,4096,(1024|6144|8192)\]", text)
    assert not re.search(r"\[(\d+,)+(8|48|64),(32|64|128)\]", text)


def moe_lowerings():
    return lowerings("nnstpu_moe_lowerings_total")


def test_the_chip_compiles_the_grouped_expert_product_at_65536_x_8(v5e_2x2):
    """The expert layer as the cell runs it: 65 536 tokens in chunks of
    32 768, top-8 of 256 SwiGLU experts of width 512 and the shared one,
    its 262 144 (token, expert) rows a chunk through the grouped kernel, the
    experts' hidden rows in VMEM."""
    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.ops import grouped_experts
    from nnstreamer_tpu.parallel import moe

    one = SingleDeviceSharding(v5e_2x2[0])

    def struct(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = {"router": struct(2048, 256), "w_in": struct(256, 2048, 1024),
              "w_out": struct(256, 512, 2048),
              "shared": {"w_in": struct(2048, 1024),
                         "w_out": struct(512, 2048)}}
    before = moe_lowerings()
    compiled = jax.jit(lambda p, x: moe.moe_top_k(p, x, 8, 2.5, 32768)).lower(
        params, struct(65536, 2048)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and grouped_experts.KERNEL_NAME in text
    assert "ragged-dot" not in text
    after = moe_lowerings()
    assert after["fused"] == before.get("fused", 0) + 1
    assert after.get("grouped", 0) == before.get("grouped", 0)
    # 2.079 GiB through ragged_dot (PR 34): the peak is the gathered rows
    # beside the kernel's result and the unsorted copy, which stay; the two
    # hidden arrays (0.75 GB) were never alive at that peak, so it falls by
    # megabytes, not by them.  Still far under the 6 GiB beside 7.74 GB.
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2.08 * 2 ** 30


def test_a_partitioned_program_and_a_toy_take_xlas_grouped_product(v5e_2x2):
    """Shapes the kernel tiles in a program GSPMD partitions over the four
    chips (a Mosaic kernel cannot be partitioned), and a ``[8, 32]`` toy on
    one chip: both lower the ``ragged_dot`` code."""
    from jax.sharding import Mesh, SingleDeviceSharding

    from nnstreamer_tpu.parallel import moe

    mesh = Mesh(np.array(v5e_2x2), ("dp",))

    def layer(d, f, e, place):
        return {"router": jax.ShapeDtypeStruct((d, e), jnp.bfloat16,
                                               sharding=place),
                "w_in": jax.ShapeDtypeStruct((e, d, 2 * f), jnp.bfloat16,
                                             sharding=place),
                "w_out": jax.ShapeDtypeStruct((e, f, d), jnp.bfloat16,
                                              sharding=place)}

    run = jax.jit(lambda p, x: moe.moe_top_k(p, x, 2))
    before = moe_lowerings()
    text = run.lower(
        layer(128, 128, 2, NamedSharding(mesh, P())),
        jax.ShapeDtypeStruct((1024, 128), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))
    ).compile().as_text()
    assert "ragged-dot" in text or "ragged_dot" in text
    assert "nns_grouped_experts" not in text
    one = SingleDeviceSharding(v5e_2x2[0])
    text = run.lower(layer(32, 16, 4, one), jax.ShapeDtypeStruct(
        (8, 32), jnp.bfloat16, sharding=one)).compile().as_text()
    assert "nns_grouped_experts" not in text
    after = moe_lowerings()
    assert after["grouped"] == before.get("grouped", 0) + 2
    assert after.get("fused", 0) == before.get("fused", 0)


def test_mosaic_compiles_the_selection_and_the_attention_under_it(v5e_2x2):
    """GLM-5.2's widths at two windows of 16 384 tokens
    (``ops/sparse_attention``): one layer's selection and its attention
    lower their kernels for one v5e chip, counted by path; with the layer's
    rotary tables the attention kernel rotates q itself, and the program
    holds q and the keys a head a row nowhere."""
    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.ops import sparse_attention as sa

    b, t, h = 2, 16384, 64

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=SingleDeviceSharding(v5e_2x2[0]))

    def paths():
        metric = REGISTRY.get("nnstpu_attention_lowerings_total")
        got = {k[0]: c.value for k, c in metric.children()} if metric else {}
        return got.get("latent_sparse", 0), got.get("index_select", 0)

    def rotated():
        metric = REGISTRY.get("nnstpu_attention_rotary_total")
        return dict(metric.children())[("kernel",)].value if metric else 0

    before = paths()
    operands = (shape(b, t, h * 256), shape(b, t, h * 192), shape(b, t, 64),
                shape(b, t, h * 256), shape(b, t, t, dtype=jnp.int8))
    attend = jax.jit(lambda *a: sa.latent_sparse_attention(*a, h)).lower(
        *operands).compile()
    assert sa.KERNEL_NAME in attend.as_text()
    in_kernel = rotated()
    table = shape(t, 32, dtype=jnp.float32)
    attend = jax.jit(lambda *a: sa.latent_sparse_attention(
        *a[:5], h, rotary=a[5:])).lower(*operands, table, table).compile()
    assert sa.KERNEL_NAME in attend.as_text()
    assert f"[{b},{t},{h},256]" not in attend.as_text()
    assert rotated() == in_kernel + 1
    before = before[0] + 1, before[1]
    select = jax.jit(lambda *a: sa.select_keys(*a, 2048)).lower(
        shape(b, t, 32 * 128), shape(b, t, 128),
        shape(b, t, 32, dtype=jnp.float32)).compile()
    assert sa.INDEX_KERNEL_NAME in select.as_text()
    assert "sort" not in select.as_text()
    assert paths() == (before[0] + 1, before[1] + 1)



def test_mosaic_compiles_the_latent_attention_without_a_selection(v5e_2x2):
    """A.X-K1's widths at two windows of 16 384 tokens
    (``ops/sparse_attention``): 64 heads of 128 | 64 with values of 128, no
    selection.  One layer's attention lowers ``nns_latent_attention`` for
    one v5e chip, with the layer's YaRN tables (q rotated in the kernel) and
    without; no mask is an operand, and the program holds neither a ``T x
    T`` array nor q or the keys a head a row."""
    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.ops import sparse_attention as sa

    b, t, h = 2, 16384, 64

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=SingleDeviceSharding(v5e_2x2[0]))

    def counted(name, label):
        metric = REGISTRY.get(name)
        got = {k[0]: c.value for k, c in metric.children()} if metric else {}
        return got.get(label, 0)

    paths, rotary = ("nnstpu_attention_lowerings_total",
                     "nnstpu_attention_rotary_total")
    before = counted(paths, "latent"), counted(rotary, "kernel")
    operands = (shape(b, t, h * 192), shape(b, t, h * 128), shape(b, t, 64),
                shape(b, t, h * 128))
    attend = jax.jit(lambda *a: sa.latent_sparse_attention(
        *a, None, h, scale=0.130861)).lower(*operands).compile()
    assert sa.LATENT_KERNEL_NAME in attend.as_text()
    table = shape(t, 32, dtype=jnp.float32)
    attend = jax.jit(lambda *a: sa.latent_sparse_attention(
        *a[:4], None, h, rotary=a[4:], scale=0.130861)).lower(
        *operands, table, table).compile()
    text = attend.as_text()
    assert sa.LATENT_KERNEL_NAME in text and sa.KERNEL_NAME not in text
    assert f"[{b},{t},{h},192]" not in text and f"{t},{t}]" not in text
    assert (counted(paths, "latent"), counted(rotary, "kernel")) == (
        before[0] + 2, before[1] + 1)


@pytest.mark.parametrize("d", [7168, 6144], ids=["axk1", "glm"])
def test_mosaic_compiles_the_way_back_of_a_shares_pass(v5e_2x2, d):
    """A pass of 8192 rows into a chunk of 8192 tokens at the two share
    cells' widths (``ops/combine_rows``): the sort by token, the gather of
    the pass's own rows and ``nns_combine_rows`` compile for one v5e chip,
    with the float32 rows aliased in and out."""
    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.ops import combine_rows

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=SingleDeviceSharding(v5e_2x2[0]))

    n = rows = 8192
    assert combine_rows.tiles((n, d), rows, jnp.bfloat16)
    compiled = jax.jit(
        lambda *a: combine_rows.combine_rows(*a, interpret=False),
        donate_argnums=0).lower(
        shape(n, d, dtype=jnp.float32), shape(rows, d),
        shape(rows, dtype=jnp.int32), shape(rows, dtype=jnp.float32)).compile()
    assert combine_rows.KERNEL_NAME in compiled.as_text()
    # the gathered rows, and no second copy of the tokens' float32 rows
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * rows * d * 2


def test_the_chip_compiles_a_share_without_an_array_of_every_routed_pair(
        v5e_2x2):
    """A.X-K1's share as ``routed_experts`` lowers it for one v5e chip (8192
    tokens, top-8, 12 of 192 experts of 7168 x 2048): the kernel behind
    XLA's two ``ragged_dot``s, counted, and neither a ``[65536, d]`` nor an
    ``[8192, 8, d]`` array in the program."""
    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.ops import combine_rows
    from nnstreamer_tpu.parallel import moe

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=SingleDeviceSharding(v5e_2x2[0]))

    n, d, name = 8192, 7168, "nnstpu_moe_share_combine_total"
    before = lowerings(name)
    text = jax.jit(lambda *a: moe.routed_experts(
        *a, first=0, total=192)).lower(
        shape(n, d), shape(n, 8, dtype=jnp.float32),
        shape(n, 8, dtype=jnp.int32), shape(12, d, 4096),
        shape(12, 2048, d)).compile().as_text()
    assert combine_rows.KERNEL_NAME in text and "ragged-dot" in text
    assert f"[{n * 8},{d}]" not in text and f"[{n},8,{d}]" not in text
    after = lowerings(name)
    assert after["kernel"] == before.get("kernel", 0) + 1
    assert after.get("plain", 0) == before.get("plain", 0)


def test_the_chip_compiles_a_relu2_share_with_its_products_in_the_kernel(
        v5e_2x2):
    """Nemotron-3-Super's share as ``routed_experts`` lowers it for one v5e
    chip (a chunk of 8192 tokens in the latent 1024, top-22, 128 of 512
    relu² experts of 1024 x 2688): a pass's products in
    ``nns_grouped_experts``, its way back in ``nns_combine_rows``, no
    ``ragged-dot``, counted ``fused``, and no pass's hidden rows (``[90112,
    2688]``) in the program."""
    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.ops import combine_rows, grouped_experts
    from nnstreamer_tpu.parallel import moe

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=SingleDeviceSharding(v5e_2x2[0]))

    n, lat, f, k = 8192, 1024, 2688, 22
    rows = moe.share_rows(n * k, 128, 512)
    assert rows == 90112
    before = moe_lowerings()
    text = jax.jit(lambda *a: moe.routed_experts(
        *a, first=0, total=512, act="relu2", latent=True)).lower(
        shape(n, lat), shape(n, k, dtype=jnp.float32),
        shape(n, k, dtype=jnp.int32), shape(128, lat, f),
        shape(128, f, lat)).compile().as_text()
    assert grouped_experts.KERNEL_NAME in text
    assert combine_rows.KERNEL_NAME in text and "ragged-dot" not in text
    assert f"[{rows},{f}]" not in text
    after = moe_lowerings()
    assert after["fused"] == before.get("fused", 0) + 1
    assert after.get("grouped", 0) == before.get("grouped", 0)


# -- Falcon-H1-34B's published widths (the cell falconh1_34b_l4.ctx8x4k) ----

@pytest.mark.parametrize("low", [False, True], ids=["sound", "control"])
def test_mosaic_compiles_the_ssd_scan_at_8_windows_of_4096(v5e_2x2, low):
    """The chunked state-space scan (``ops/ssm_scan``) at the cell's shapes,
    32 heads of 128 over 2 groups of state 256 in chunks of 128: one layer
    lowers ``nns_ssd_scan`` for one v5e chip, counted, in the served form
    and the control's, and the program holds neither a chunk's states nor
    its decay masks in HBM."""
    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.ops import ssm_scan

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=SingleDeviceSharding(v5e_2x2[0]))

    b, t, name = 8, 4096, "nnstpu_ssm_scan_lowerings_total"
    before = lowerings(name)
    compiled = jax.jit(lambda *a: ssm_scan.ssd_scan(
        *a, chunk=128, n_groups=2, low=low)).lower(
        shape(b, t, 4096), shape(b, t, 32, dtype=jnp.float32),
        shape(32, dtype=jnp.float32), shape(b, t, 512), shape(b, t, 512),
        shape(32, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    assert ssm_scan.KERNEL_NAME in text
    assert ",32,128,256]" not in text and ",32,128,128]" not in text
    # the segment sums and Δ in both orientations, nothing of a chunk's
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * b * t * 32 * 4
    after = lowerings(name)
    assert after["kernel"] == before.get("kernel", 0) + 1
    assert after.get("plain", 0) == before.get("plain", 0)


# -- Nemotron-3-Super's published widths (nemotron3_super_l11_ep4.ctx8x4k) --

@pytest.mark.parametrize("low", [False, True], ids=["sound", "control"])
def test_mosaic_compiles_the_narrow_scan_at_8_windows_of_4096(v5e_2x2, low):
    """The scan's ``shared_tile`` body at the cell's shapes, 128 heads of 64
    (two a lane tile) over 8 groups of state 128 in chunks of 128: one
    Mamba layer lowers ``nns_ssd_scan`` for one v5e chip, counted under its
    body, in the served form and the control's, and the program holds
    neither a chunk's states nor its decay masks in HBM."""
    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.ops import ssm_scan

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=SingleDeviceSharding(v5e_2x2[0]))

    b, t = 8, 4096
    paths, bodies = ("nnstpu_ssm_scan_lowerings_total",
                     "nnstpu_ssm_scan_bodies_total")
    before = lowerings(paths), lowerings(bodies)
    compiled = jax.jit(lambda *a: ssm_scan.ssd_scan(
        *a, chunk=128, n_groups=8, low=low)).lower(
        shape(b, t, 8192), shape(b, t, 128, dtype=jnp.float32),
        shape(128, dtype=jnp.float32), shape(b, t, 1024), shape(b, t, 1024),
        shape(128, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    assert ssm_scan.KERNEL_NAME in text
    assert ",128,128,128]" not in text and ",128,64,128]" not in text
    # the segment sums and Δ in both orientations, nothing of a chunk's
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * b * t * 128 * 4
    after = lowerings(paths), lowerings(bodies)
    assert after[0]["kernel"] == before[0].get("kernel", 0) + 1
    assert after[0].get("plain", 0) == before[0].get("plain", 0)
    assert after[1]["shared_tile"] == before[1].get("shared_tile", 0) + 1
