"""The ``laguna`` token model against its plain reference
(``benchmark/references/laguna_plain.py``, float32 at ``highest``, nothing of
the program imported), at tiny widths on the CPU with seeded weights; its
expert layer against a masked dense sum; its attention lowering in interpret
mode against ``full_attention`` with the explicit mask; and token frames
through a launch-string pipeline."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import laguna_plain  # noqa: E402
from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.models import laguna  # noqa: E402
from nnstreamer_tpu.obs.metrics import REGISTRY  # noqa: E402
from nnstreamer_tpu.ops import fused_attention as fa  # noqa: E402
from nnstreamer_tpu.ops import grouped_experts  # noqa: E402
from nnstreamer_tpu.parallel import moe  # noqa: E402
from nnstreamer_tpu.parallel.ring_attention import full_attention  # noqa: E402
from nnstreamer_tpu.utils.checkpoint import save_state  # noqa: E402

FULL = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 16, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
SLIDING = {"rope_type": "default", "rope_theta": 10000,
           "partial_rotary_factor": 1}


def config(layers, kinds=None, mlps=None, heads=None):
    """The published keys at tiny widths; ``layers`` deep."""
    period = ["full_attention"] + ["sliding_attention"] * 3
    return {
        "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": layers, "num_key_value_heads": 2, "head_dim": 16,
        "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4,
        "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
        "sliding_window": 8, "moe_routed_scaling_factor": 2.5,
        "rope_parameters": {"full_attention": FULL,
                            "sliding_attention": SLIDING,
                            "original_max_position_embeddings": 16},
        "layer_types": kinds or (period * 3)[:max(layers, 8)],
        "mlp_layer_types": mlps or (["dense"] + ["sparse"] * 11),
        "num_attention_heads_per_layer": heads or [12, 16, 16, 16] * 3,
    }


CASES = {
    "a_full_layer_alone": config(1),
    "a_sliding_layer_alone": config(1, ["sliding_attention"], ["sparse"], [16]),
    "the_five_layer_stack": config(5),
}


def both(cfg, t, dtype, batch=3, seed=0):
    params = laguna.init_params(cfg, seed, dtype)
    model = laguna.build(cfg, seq=t, batch=batch, dtype=dtype, params=params)
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, t), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.fn())(ids))
    host = jax.tree_util.tree_map(np.asarray, params)
    want = laguna_plain.forward(dict(cfg, seq=t), {}, host, ids)
    return got, want


@pytest.mark.parametrize("t", [24, 6], ids=["past_the_window", "under_it"])
@pytest.mark.parametrize("case", CASES)
def test_float32_matches_the_plain_reference(case, t):
    got, want = both(CASES[case], t, jnp.float32)
    assert got.shape == want.shape == (3, 96) and got.dtype == np.float32
    assert np.abs(got - want).max() < 1e-4


@pytest.mark.parametrize("t", [24, 6], ids=["past_the_window", "under_it"])
@pytest.mark.parametrize("case", CASES)
def test_bfloat16_stays_near_the_plain_reference(case, t):
    """bf16 weights and activations against the float32 walk over the same
    bf16 weights: rounding, and at these widths now and then a top-4 choice
    that falls the other way on a near-tie, so the bound is loose."""
    got, want = both(CASES[case], t, jnp.bfloat16)
    assert np.isfinite(got).all()
    assert np.linalg.norm(got - want) < 0.3 * np.linalg.norm(want)


def test_the_depth_cuts_the_per_layer_lists_and_nothing_else():
    cfg = config(2)
    params = laguna.init_params(cfg, 0, jnp.float32)
    assert len(params["layers"]) == 2
    assert "mlp" in params["layers"][0] and "moe" in params["layers"][1]
    assert params["layers"][0]["wq"].shape == (32, 12 * 16)
    assert params["layers"][1]["wq"].shape == (32, 16 * 16)
    assert params["layers"][1]["moe"]["w_in"].shape == (16, 32, 32)
    assert params["layers"][1]["moe"]["w_out"].shape == (16, 16, 32)


# -- the expert layer ---------------------------------------------------------

def counted(name, path):
    """A labelled counter's value, 0 before its first count."""
    metric = REGISTRY.get(name)
    child = dict(metric.children()).get((path,)) if metric else None
    return child.value if child else 0


def moe_params(key, d=32, f=16, e=12, router=None):
    ks = jax.random.split(key, 6)
    return {"router": (jax.random.normal(ks[0], (d, e))
                       if router is None else router),
            "w_in": jax.random.normal(ks[1], (e, d, 2 * f)) * 0.2,
            "w_out": jax.random.normal(ks[2], (e, f, d)) * 0.2,
            "shared": {"w_in": jax.random.normal(ks[3], (d, 2 * f)) * 0.2,
                       "w_out": jax.random.normal(ks[4], (f, d)) * 0.2}}


def masked_dense_sum(p, x, k, scaling):
    """Every expert over every token, kept where the router chose it."""
    scores = jax.nn.sigmoid(x @ p["router"])
    chosen = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    w = top / top.sum(-1, keepdims=True) * scaling
    out = moe.swiglu(x, p["shared"]["w_in"], p["shared"]["w_out"])
    for e in range(p["w_in"].shape[0]):
        out += ((w * (chosen == e)).sum(-1)[:, None]
                * moe.swiglu(x, p["w_in"][e], p["w_out"][e]))
    return out, chosen


ROUTERS = {
    "seeded": None,
    # every token to the same three experts: the others get no rows at all
    "every_token_to_one_group": jnp.zeros((32, 12)).at[:, jnp.array([2, 7, 9])].set(
        jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (32, 3))) + 1),
    # all scores equal: top-k takes the lowest indices, as the stable sort does
    "ties": jnp.zeros((32, 12)),
}


@pytest.fixture
def kernel_everywhere(monkeypatch):
    """The XLA lowering runs the grouped kernel too, interpreted, in tiles of
    16 rows and blocks of 8: what a program computes through the fused path,
    on a host with no TPU."""
    monkeypatch.setattr(moe, "_routed_grouped", functools.partial(
        moe._routed_fused, interpret=True, tile_rows=16, block_rows=8))


@pytest.mark.parametrize("path", ["ragged_dot", "kernel"])
@pytest.mark.parametrize("chunk", [None, 10], ids=["whole", "in_chunks"])
@pytest.mark.parametrize("router", ROUTERS)
def test_top_k_routing_drops_nothing(router, chunk, path, request):
    """50 tokens x 3 are 150 rows, and a chunk's 10 x 3 are 30: neither is a
    whole number of the kernel's 16-row tiles."""
    if path == "kernel":
        request.getfixturevalue("kernel_everywhere")
    p = moe_params(jax.random.PRNGKey(0), router=ROUTERS[router])
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (50, 32)))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: moe.moe_top_k(p, x, 3, 2.5, chunk))(p, x)
        want, chosen = masked_dense_sum(p, x, 3, 2.5)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    if router == "every_token_to_one_group":
        assert set(np.asarray(chosen).ravel()) == {2, 7, 9}
    if router == "ties":
        assert set(np.asarray(chosen).ravel()) == {0, 1, 2}


@pytest.mark.parametrize("router", ROUTERS)
def test_the_kernel_in_bfloat16_is_within_rounding_of_the_ragged_dot_path(
        router, request):
    p = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        moe_params(jax.random.PRNGKey(0), router=ROUTERS[router]))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (50, 32),
                                  jnp.bfloat16))
    layer = lambda p, x: moe.moe_top_k(p, x, 3, 2.5)
    want = np.asarray(jax.jit(layer)(p, x), np.float32)
    request.getfixturevalue("kernel_everywhere")
    got = jax.jit(lambda p, x: layer(p, x))(p, x)
    assert got.dtype == jnp.bfloat16
    # gate|up and the weighted rows are rounded once where the ragged_dot
    # path rounds them twice: a few of bfloat16's 2**-8 steps apart
    assert (np.abs(np.asarray(got, np.float32) - want).max()
            < 2 ** -6 * np.abs(want).max())


def ragged_reference(rows, w_in, w_out, sizes, pair_weights):
    f = w_out.shape[1]
    gate_up = jax.lax.ragged_dot(rows, w_in, sizes,
                                 preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]).astype(rows.dtype)
    out = jax.lax.ragged_dot(h, w_out, sizes,
                             preferred_element_type=jnp.float32)
    return (out * pair_weights[:, None]).astype(rows.dtype)


@pytest.mark.parametrize("sizes,tile,block", [
    ([16, 0, 32, 16], 16, 16),          # every group ends on a tile's edge
    ([16, 0, 32, 16], 32, 8),           # and on a block's edge inside a tile
    ([5, 11, 0, 0, 21, 3, 10], 16, 8),  # 50 rows: the last tile is cut off
    ([0, 0, 37], 16, 8),                # only the last expert has rows
    ([37, 0, 0], 16, 8),                # only the first: the rest are skipped
    ([1, 1, 1, 1, 1, 1, 1, 1, 1], 8, 8),  # nine visits of one tile, then one
    ([3, 70, 2], 32, 16),               # a group over three tiles
], ids=["tile_edges", "block_edges", "cut_off", "last_expert_only",
        "first_expert_only", "one_row_each", "over_three_tiles"])
def test_the_grouped_kernel_in_interpret_mode(sizes, tile, block):
    m, e, d, f = sum(sizes), len(sizes), 32, 16
    ks = jax.random.split(jax.random.PRNGKey(m), 4)
    rows = jax.random.normal(ks[0], (m, d))
    w_in = jax.random.normal(ks[1], (e, d, 2 * f)) * 0.2
    w_out = jax.random.normal(ks[2], (e, f, d)) * 0.2
    pair_weights = jax.random.uniform(ks[3], (m,))
    sizes = jnp.asarray(sizes, jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(
            grouped_experts.grouped_experts, tile_rows=tile, block_rows=block,
            interpret=True))(rows, w_in, w_out, sizes, pair_weights)
        want = ragged_reference(rows, w_in, w_out, sizes, pair_weights)
    assert got.shape == rows.shape and got.dtype == rows.dtype
    assert np.abs(np.asarray(got - want)).max() < 1e-5


def test_the_visits_cover_every_row_once_and_nothing_else():
    """The grid's metadata by hand: tiles of 4 rows, groups of 6, 0, 2, 5."""
    offsets, expert, tile, count = grouped_experts.visits(
        jnp.array([6, 0, 2, 5], jnp.int32), 13, 4)
    assert list(np.asarray(offsets)) == [0, 6, 6, 8, 13]
    assert int(count[0]) == 5 and expert.shape == (4 + 4 - 1,)
    # expert 0 over tiles 0 and 1, expert 2 the rest of tile 1, expert 3
    # tiles 2 and 3; the steps left over repeat the last visit
    assert list(np.asarray(expert)) == [0, 0, 2, 3, 3, 3, 3]
    assert list(np.asarray(tile)) == [0, 1, 1, 2, 3, 3, 3]


def test_derivatives_are_the_ragged_dot_paths():
    """The primitive under ``grad``: what the layer gave before it was one."""
    p = moe_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (20, 32))
    w, experts = moe.route_top_k(x, p["router"], 3, 2.5)

    def through(fn):
        return lambda x, w: fn(x, w, experts, p["w_in"], p["w_out"]).sum()

    got = jax.grad(through(moe.routed_experts), argnums=(0, 1))(x, w)
    want = jax.grad(through(moe._routed_grouped), argnums=(0, 1))(x, w)
    for g, h in zip(got, want):
        assert np.abs(np.asarray(h)).max() > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(h), rtol=1e-6)


def test_router_arithmetic_is_float32_whatever_the_tokens_are():
    p = moe_params(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (20, 32), jnp.bfloat16)
    w, experts = moe.route_top_k(x, p["router"].astype(jnp.bfloat16), 3, 2.5)
    assert w.dtype == jnp.float32 and experts.shape == (20, 3)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-6)


def test_the_choice_is_float32s_wherever_the_scores_lie_apart():
    """The routing itself, held apart from any logits: over bfloat16 tokens
    and a plain ``N(0, 1/d)`` bfloat16 router, the experts chosen are those
    of the exact scores of the same values wherever the 8th and the 9th
    score lie further apart than float32 rounds.  A router or a top-k in
    bfloat16 would choose otherwise for some tokens, as the last lines show:
    that is the fault a comparison of logits under decisive routers
    (``benchmark/model_kinds/laguna.init_weights``) cannot see."""
    d, e, k = 64, 256, 8
    x = jax.random.normal(jax.random.PRNGKey(7), (4096, d), jnp.bfloat16)
    router = (jax.random.normal(jax.random.PRNGKey(8), (d, e))
              * d ** -0.5).astype(jnp.bfloat16)
    _, experts = jax.jit(lambda x, r: moe.route_top_k(x, r, k))(x, router)
    exact = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    order = np.argsort(-exact, axis=-1, kind="stable")
    ranked = np.take_along_axis(exact, order, axis=-1)
    clear = ranked[:, k - 1] - ranked[:, k] > 1e-3
    assert clear.mean() > 0.9
    want = np.sort(order[:, :k], axis=-1)
    assert np.array_equal(np.sort(np.asarray(experts), axis=-1)[clear],
                          want[clear])
    _, low = jax.lax.top_k(jax.nn.sigmoid(x @ router), k)  # all in bfloat16
    assert not np.array_equal(np.sort(np.asarray(low), axis=-1)[clear],
                              want[clear])


def test_expert_layers_are_counted_by_path():
    """By the lowering rule that chose: a trace counts nothing, a program
    for this host takes XLA's grouped product, and one trace lowered for a
    TPU takes the kernel where its shapes tile and XLA's where they do not."""
    name = "nnstpu_moe_lowerings_total"
    p = moe_params(jax.random.PRNGKey(0))
    before = counted(name, "grouped"), counted(name, "fused")
    traced = jax.jit(lambda x: moe.moe_top_k(p, x, 3)).trace(jnp.ones((8, 32)))
    assert "nns_routed_experts" in str(traced.jaxpr)
    assert (counted(name, "grouped"), counted(name, "fused")) == before
    traced.lower()
    traced.lower(lowering_platforms=("tpu",))  # [24, 32] rows do not tile
    assert counted(name, "grouped") == before[0] + 2
    assert counted(name, "fused") == before[1]
    big = moe_params(jax.random.PRNGKey(0), d=128, f=128, e=2)
    traced = jax.jit(lambda x: moe.moe_top_k(big, x, 2, token_chunk=512)).trace(
        jnp.ones((1024, 128)))
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "ragged_dot" not in text
    assert "tpu_custom_call" not in traced.lower().as_text()
    # the chunks' scan is lowered once: one layer, one count a program
    assert counted(name, "fused") == before[1] + 1
    assert counted(name, "grouped") == before[0] + 3
    sw = moe.init_moe_params(jax.random.PRNGKey(0), 8, 16, 4)
    before = counted(name, "switch")
    jax.jit(lambda x: moe.moe_ffn(sw, x)).lower(jnp.ones((8, 8)))
    assert counted(name, "switch") == before + 1


# -- the attention lowering ---------------------------------------------------

def qkv(t, hq, hkv, dtype=jnp.float32, b=2):
    ks = jax.random.split(jax.random.PRNGKey(t + hq), 3)
    return (jax.random.normal(ks[0], (b, t, hq * 128), dtype),
            jax.random.normal(ks[1], (b, t, hkv * 128), dtype),
            jax.random.normal(ks[2], (b, t, hkv * 128), dtype))


def masked_reference(q, k, v, hq, hkv, window):
    b, t, _ = q.shape
    q = q.reshape(b, t, hq, 128)
    k, v = (jnp.repeat(a.reshape(b, t, hkv, 128), hq // hkv, axis=2)
            for a in (k, v))
    return full_attention(q, k, v, causal=True,
                          window=window).reshape(b, t, hq * 128)


@pytest.mark.parametrize("t,hq,hkv,window,bq,bk", [
    (300, 6, 1, None, 128, 128),    # groups of 6, T no multiple of the block
    (300, 8, 1, 100, 128, 128),     # groups of 8, a window across blocks
    (640, 12, 2, 96, 256, 128),     # two key/value heads, unequal blocks
    (200, 8, 1, 512, None, None),   # T under the window: the band is all
    (700, 6, 1, None, None, None),  # the module's own blocks, T padded
    (640, 8, 1, 128, 128, 128),     # window = block: a folded step a block
    (700, 8, 1, 256, 128, 128),     # two blocks: folded edges, one bare
    (1100, 8, 1, 512, None, None),  # the module's band: two 256-row
                                    # chains a row block, T padded
    (1024, 6, 1, 256, None, None),  # a window under the row block: its
                                    # first block walks under the mask
])
def test_blocked_attention_in_interpret_mode(t, hq, hkv, window, bq, bk):
    q, k, v = qkv(t, hq, hkv)
    with jax.default_matmul_precision("highest"):
        got = fa.blocked_attention(q, k, v, hq, hkv, window, bq, bk,
                                   interpret=True)
        want = masked_reference(q, k, v, hq, hkv, window)
    assert got.shape == q.shape
    assert np.abs(np.asarray(got - want)).max() < 2e-5


def test_grouped_attention_is_one_primitive_whose_lowering_chooses():
    q, k, v = qkv(256, 6, 1, jnp.bfloat16, b=1)
    fn = jax.jit(lambda q, k, v: fa.attention(q, 6, True, k=k, v=v,
                                              n_kv_heads=1, window=64))
    traced = fn.trace(q, k, v)
    assert "nns_full_attention" in str(traced.jaxpr)
    count = lambda path: counted("nnstpu_attention_lowerings_total", path)
    blocked, plain = count("blocked"), count("plain")
    on_tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in on_tpu and fa.BLOCKED_KERNEL_NAME in on_tpu
    assert count("blocked") == blocked + 1
    on_cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in on_cpu and count("plain") == plain + 1
    with jax.default_matmul_precision("highest"):
        got = np.asarray(fn(q, k, v), np.float32)
        want = np.asarray(masked_reference(q, k, v, 6, 1, 64), np.float32)
    assert np.abs(got - want).max() < 0.05


def test_what_the_blocked_kernel_does_not_tile_lowers_plain():
    assert fa.blocked_tiles((16, 4096, 48 * 128), (16, 4096, 8 * 128),
                            jnp.bfloat16, 48, 8, True)
    assert not fa.blocked_tiles((16, 4096, 48 * 128), (16, 4096, 8 * 128),
                                jnp.bfloat16, 48, 8, False)   # not causal
    assert not fa.blocked_tiles((16, 4096, 48 * 96), (16, 4096, 8 * 96),
                                jnp.bfloat16, 48, 8, True)    # heads of 96
    assert not fa.blocked_tiles((1, 65536, 128), (1, 65536, 128),
                                jnp.bfloat16, 1, 1, True)     # K, V past VMEM
    with pytest.raises(ValueError):
        fa.attention(jnp.ones((1, 8, 128)), 1, False, k=jnp.ones((1, 8, 128)),
                     v=jnp.ones((1, 8, 128)), window=4)


@pytest.mark.parametrize("t,window,walk", [
    (1024, 512, "folded"),   # the module's 512-row blocks, a window of one
    (1536, 1024, "folded"),  # a window of two: one bare block between
    (1024, 384, "split"),    # a window of no whole number of blocks
    (512, 512, "split"),     # no row block lies past the window
    (1024, None, None),      # no window: nothing to count
])
def test_the_band_walk_is_counted_by_whether_it_folds(t, window, walk):
    """One count a windowed call lowered to the kernel, by how its band's
    edge and diagonal blocks are walked; a CPU's program counts nothing."""
    q, k, v = qkv(t, 2, 1, jnp.bfloat16, b=1)
    traced = jax.jit(lambda q, k, v: fa.attention(
        q, 2, True, k=k, v=v, n_kv_heads=1, window=window)).trace(q, k, v)
    walks = lambda: {w: counted("nnstpu_attention_band_walk_total", w)
                     for w in ("folded", "split")}
    before = walks()
    assert fa.BLOCKED_KERNEL_NAME in traced.lower(
        lowering_platforms=("tpu",)).as_text()
    risen = {w: n - before[w] for w, n in walks().items() if n - before[w]}
    assert risen == ({walk: 1} if walk else {})
    traced.lower(lowering_platforms=("cpu",))
    assert walks() == {w: before[w] + risen.get(w, 0) for w in before}


def test_a_window_of_whole_key_blocks_folds_and_takes_the_band_block():
    assert fa._folds(512, 512, 512, 4096)
    assert fa._folds(512, 512, 256, 4096)        # two chains a row block
    assert not fa._folds(512, 256, 512, 4096)    # rows no whole key blocks
    assert not fa._folds(384, 256, 256, 4096)    # window no whole blocks
    assert not fa._folds(512, 512, 512, 512)     # nothing past the window
    assert not fa._folds(None, 512, 512, 4096)
    assert fa._blocks(4096, 512) == (512, fa.BAND_BLOCK_K, 4096)
    assert fa._blocks(4096) == fa._blocks(4096, 384) == (512, 512, 4096)
    assert fa._blocks(700) == (512, 512, 1024)
    assert fa._blocks(200, 512) == (256, 256, 256)
    assert fa._blocks(4096, 512, 128, 128) == (128, 128, 4096)


# -- rotary inside the lowering ------------------------------------------------

def tables(rope, t):
    return laguna.rotary_tables(rope, 128, t)


ROTARY_CASES = {
    # t, hq, hkv, window, bq, bk, rope
    "the_whole_head_under_a_window": (300, 8, 1, 100, 128, 128, SLIDING),
    "half_the_head_and_no_window": (300, 6, 1, None, 128, 128, FULL),
    "two_key_value_heads_unequal_blocks": (640, 12, 2, 96, 256, 128, SLIDING),
    "key_blocks_longer_than_row_blocks": (640, 12, 2, None, 128, 256, FULL),
    "the_modules_own_blocks_t_padded": (700, 6, 1, None, None, None, FULL),
    "window_of_one_block_folded": (640, 8, 1, 128, 128, 128, SLIDING),
    "window_of_two_blocks_folded_t_padded": (700, 8, 1, 256, 128, 128,
                                             SLIDING),
    "t_under_the_window_never_folds": (200, 8, 1, 512, None, None, SLIDING),
    "the_modules_band_t_padded": (1100, 8, 1, 512, None, None, SLIDING),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ROTARY_CASES)
def test_the_blocked_kernel_rotates_as_rotate_does(case, dtype):
    """The kernel given the tables against the kernel over ``rotate``d q
    and k: the same roundings in the same order.  Op by op the two
    arithmetics are equal to the bit (the test below); inside a compiled
    program XLA's CPU backend contracts ``a * c + b * s`` to a fused
    multiply-add in one place and not in another, one float32 step apart,
    which moves one bfloat16 rounding in some ten thousand."""
    t, hq, hkv, window, bq, bk, rope = ROTARY_CASES[case]
    q, k, v = qkv(t, hq, hkv, jnp.dtype(dtype))
    cos, sin = tables(rope, t)
    kernel = functools.partial(fa.blocked_attention, n_heads=hq,
                               n_kv_heads=hkv, window=window, block_q=bq,
                               block_k=bk, interpret=True)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(functools.partial(
            kernel, rotary=(cos, sin)))(q, k, v), np.float32)
        rotated = lambda q, k: (fa.rotate(q, cos, sin, hq),
                                fa.rotate(k, cos, sin, hkv))
        want = np.asarray(jax.jit(lambda q, k, v: kernel(*rotated(q, k), v))(
            q, k, v), np.float32)
        ref = np.asarray(masked_reference(*rotated(q, k), v, hq, hkv, window),
                         np.float32)
    assert got.shape == q.shape
    if dtype == "float32":
        assert np.abs(got - want).max() < 2e-6
        assert np.abs(got - ref).max() < 2e-5
    else:
        assert (got != want).mean() < 1e-2
        assert np.abs(got - want).max() <= 2 ** -7 * np.abs(want).max()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rope", [SLIDING, FULL], ids=["rot_128", "rot_64"])
def test_the_lane_tables_are_rotates_arithmetic_to_the_bit(rope, dtype):
    """``x * C + partner * S`` over whole 128-lane heads, evaluated op by op
    as the kernel writes it (``jnp.roll`` for Mosaic's lane roll)."""
    t, heads = 40, 3
    x = jax.random.normal(jax.random.PRNGKey(9), (2, t, heads * 128),
                          jnp.dtype(dtype))
    cos, sin = tables(rope, t)
    half = cos.shape[-1]
    c, s = fa._lane_tables(cos, sin, 48)
    assert c.shape == s.shape == (48, 128) and c.dtype == jnp.float32
    assert not np.asarray(c[t:]).any() and not np.asarray(s[t:]).any()
    with jax.disable_jit():
        h = x.reshape(2, t, heads, 128).astype(jnp.float32)
        partner = jnp.where(jnp.arange(128) < half,
                            jnp.roll(h, 128 - half, -1), jnp.roll(h, half, -1))
        got = (h * c[:t, None] + partner * s[:t, None]).astype(x.dtype)
        want = fa.rotate(x, cos, sin, heads)
    assert np.array_equal(np.asarray(got.reshape(x.shape), np.float32),
                          np.asarray(want, np.float32))


def rotary_counts():
    return tuple(counted(name, label) for name, label in (
        ("nnstpu_attention_rotary_total", "kernel"),
        ("nnstpu_attention_rotary_total", "outside"),
        ("nnstpu_attention_lowerings_total", "blocked"),
        ("nnstpu_attention_lowerings_total", "plain")))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rope,window", [(SLIDING, 64), (FULL, None)],
                         ids=["rot_128_window", "rot_64"])
def test_a_call_with_tables_lowers_plain_here_and_is_rotate_then_attention(
        rope, window, dtype):
    """On this host the primitive with tables is ``rotate`` on q and k and
    the plain path, as ``models/laguna.layer`` wrote it out before: the same
    bits."""
    q, k, v = qkv(96, 6, 2, jnp.dtype(dtype))
    cos, sin = tables(rope, 96)
    before = rotary_counts()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda q, k, v: fa.attention(
            q, 6, True, k=k, v=v, n_kv_heads=2, window=window,
            rotary=(cos, sin)))(q, k, v)
        assert rotary_counts() == (before[0], before[1] + 1, before[2],
                                   before[3] + 1)
        want = jax.jit(lambda q, k, v: fa.attention(
            fa.rotate(q, cos, sin, 6), 6, True, k=fa.rotate(k, cos, sin, 2),
            v=v, n_kv_heads=2, window=window))(q, k, v)
    # a call without tables counts no rotation
    assert rotary_counts() == (before[0], before[1] + 1, before[2],
                               before[3] + 2)
    assert got.dtype == q.dtype
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


def test_rotations_are_counted_by_where_they_run():
    """One trace lowered for a TPU rotates in the kernel, for this host
    outside; heads the kernel does not tile (96 wide) go outside on a TPU
    too, and the fused projection takes no tables at all."""
    q, k, v = qkv(256, 6, 1, jnp.bfloat16, b=1)
    cos, sin = tables(FULL, 256)
    call = lambda q, k, v: jax.jit(lambda q, k, v: fa.attention(
        q, 6, True, k=k, v=v, n_kv_heads=1, window=64,
        rotary=(cos, sin))).trace(q, k, v)
    traced = call(q, k, v)
    assert str(traced.jaxpr).count("nns_full_attention") == 1
    before = rotary_counts()
    on_tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert fa.BLOCKED_KERNEL_NAME in on_tpu
    assert rotary_counts() == (before[0] + 1, before[1], before[2] + 1,
                               before[3])
    assert "tpu_custom_call" not in traced.lower(
        lowering_platforms=("cpu",)).as_text()
    assert rotary_counts() == (before[0] + 1, before[1] + 1, before[2] + 1,
                               before[3] + 1)
    narrow = call(q[..., :6 * 96], k[..., :96], v[..., :96])
    assert "tpu_custom_call" not in narrow.lower(
        lowering_platforms=("tpu",)).as_text()
    assert rotary_counts() == (before[0] + 1, before[1] + 2, before[2] + 1,
                               before[3] + 2)
    with pytest.raises(ValueError):
        fa.attention(jnp.ones((1, 8, 384)), 1, True, rotary=(cos, sin))


def test_tables_count_in_what_the_blocked_kernel_tiles():
    shapes = ((16, 4096, 48 * 128), (16, 4096, 8 * 128), jnp.bfloat16, 48, 8,
              True)
    assert fa.blocked_tiles(*shapes, (4096, 32))
    assert fa.blocked_tiles(*shapes, (4096, 64))
    assert not fa.blocked_tiles(*shapes, (4096, 96))   # rot past the head
    assert not fa.blocked_tiles(*shapes, (2048, 64))   # not every position
    long = ((1, 10240, 128), (1, 10240, 128), jnp.bfloat16, 1, 1, True)
    assert fa.blocked_tiles(*long)                      # K and V fit alone
    assert not fa.blocked_tiles(*long, (10240, 64))     # not with K's scratch


def test_tables_are_every_mapped_rows_and_derivatives_the_plain_paths():
    q, k, v = qkv(48, 4, 2, b=3)
    cos, sin = tables(SLIDING, 48)

    def layer(q, k, v, rotary=(cos, sin)):
        return fa.attention(q, 4, True, k=k, v=v, n_kv_heads=2, window=16,
                            rotary=rotary)

    def written_out(q, k, v):
        return fa.plain_grouped_attention(
            fa.rotate(q, cos, sin, 4), fa.rotate(k, cos, sin, 2), v, 4, 2,
            True, 16)

    stacked = [jnp.stack([a, a[::-1]], 1) for a in (q, k, v)]
    mapped = jax.vmap(layer, in_axes=1, out_axes=1)(*stacked)
    np.testing.assert_allclose(np.asarray(mapped[:, 0]),
                               np.asarray(layer(q, k, v)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(mapped[:, 1]),
                               np.asarray(layer(*(a[::-1] for a in (q, k, v)))),
                               atol=1e-6)
    with pytest.raises(NotImplementedError):
        jax.vmap(lambda c: layer(q, k, v, (c, sin)))(jnp.stack([cos, cos]))
    got = jax.grad(lambda *a: (layer(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: (written_out(*a) ** 2).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert np.abs(np.asarray(w)).max() > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6)


# -- the streaming path -------------------------------------------------------

def test_token_frames_through_a_launch_string_at_batch_n_equal_n_single(tmp_path):
    """``tensor_filter framework=jax`` opens the model from a checkpoint and
    the published config by the builder's name, like the other zoo models;
    a batch of N windows gives the N rows that N single windows give."""
    cfg = config(5)
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    params = laguna.init_params(cfg, 3, jnp.float32)
    save_state(params, str(tmp_path / "laguna.npz"))
    ids = np.random.default_rng(4).integers(0, 96, (4, 24), dtype=np.int32)

    def run(frames, custom):
        got = []
        p = parse_launch(
            "datasrc name=s ! tensor_filter framework=jax name=f "
            f"model={tmp_path / 'laguna.npz'} custom={custom} "
            "! tensor_sink name=out")
        p["s"].data = [f.copy() for f in frames]
        p["out"].connect("new-data",
                         lambda f: got.append(np.asarray(f.tensor(0))))
        p.run(timeout=120)
        return got

    custom = (f"builder=laguna:build,config={tmp_path / 'config.json'},"
              "seq=24,dtype=float32")
    with jax.default_matmul_precision("highest"):
        singles = run(list(ids), custom)
        batched, = run([ids], custom + ",batch=4")
    assert batched.shape == (4, 96) and batched.dtype == np.float32
    assert [s.shape for s in singles] == [(96,)] * 4
    np.testing.assert_allclose(np.stack(singles), batched, atol=1e-5)
    want = laguna_plain.forward(dict(cfg, seq=24), {},
                                jax.tree_util.tree_map(np.asarray, params), ids)
    assert np.abs(batched - want).max() < 1e-4
