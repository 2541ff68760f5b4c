"""The ``axk1`` token model against its plain reference
(``benchmark/references/axk1_plain.py``, float32 at ``highest``, nothing of
the program imported), at tiny widths on the CPU with seeded weights: dense
causal latent attention, YaRN tables and the softmax scale, the
group-limited choice of experts (where the limit binds, and on ties), the
shares of an uncut layer against the whole, the kernel without a selection
in interpret mode (compiled by Mosaic for a described v5e in
``tests/test_fused_attention.py``), which lowering a trace gets, and token
frames through a launch-string pipeline."""

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

from benchmark.references import axk1_plain  # noqa: E402
from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.models import axk1, glm_dsa, laguna  # noqa: E402
from nnstreamer_tpu.obs.metrics import REGISTRY  # noqa: E402
from nnstreamer_tpu.ops import sparse_attention as sa  # noqa: E402
from nnstreamer_tpu.ops.fused_attention import rotate  # noqa: E402
from nnstreamer_tpu.parallel import moe  # noqa: E402
from nnstreamer_tpu.utils.checkpoint import save_state  # noqa: E402

YARN = {"type": "yarn", "factor": 8, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8, "beta_fast": 32,
        "beta_slow": 1}


def config(layers=None, held=None, **over):
    """The published keys at tiny widths: one leading dense layer, then
    sparse ones; 16 experts in 4 groups of which 2 are kept, top-4."""
    cfg = {
        "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 5, "num_attention_heads": 4, "q_lora_rank": 24,
        "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
        "v_head_dim": 8, "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "moe_intermediate_size": 16, "n_routed_experts": 16, "n_group": 4,
        "topk_group": 2, "n_shared_experts": 1, "num_experts_per_tok": 4,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "topk_method": "none",
        "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": YARN,
    }
    if layers:
        cfg["layers"] = layers
    if held:
        cfg["experts_held"] = held
    return dict(cfg, **over)


CASES = {
    "the_dense_layer": config([0]),
    "a_sparse_layer": config([3]),
    "the_first_five_layers": config(),
    "the_cells_five_layers_and_its_share": config([0, 1, 2, 3, 4], [0, 2]),
    "a_share_in_the_middle": config([0, 1], [6, 4]),
}


def both(cfg, t, dtype, batch=3, seed=0):
    params = glm_dsa.init_params(axk1.latent_config(cfg), seed, dtype)
    model = axk1.build(cfg, seq=t, batch=batch, dtype=dtype, params=params)
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, t), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.fn())(ids))
    host = jax.tree_util.tree_map(np.asarray, params)
    return got, axk1_plain.forward(dict(cfg, seq=t), {}, host, ids)


@pytest.mark.parametrize("t", [40, 6], ids=["past_the_original_context",
                                            "inside_it"])
@pytest.mark.parametrize("case", CASES)
def test_float32_matches_the_plain_reference(case, t):
    got, want = both(CASES[case], t, jnp.float32)
    assert got.shape == want.shape == (3, 96) and got.dtype == np.float32
    assert np.abs(got - want).max() < 1e-4


@pytest.mark.parametrize("case", CASES)
def test_bfloat16_stays_near_the_plain_reference(case):
    """bf16 weights and activations against the float32 walk over the same
    bf16 weights: rounding, and at these sizes now and then an expert of 4
    that falls the other way on a near-tie, so the bound is loose."""
    got, want = both(CASES[case], 40, jnp.bfloat16)
    assert np.isfinite(got).all()
    assert np.linalg.norm(got - want) < 0.3 * np.linalg.norm(want)


def test_the_model_is_named_and_holds_neither_indexer_nor_bias():
    model = axk1.build(config([0, 1], [4, 4]), seq=8, seed=1)
    assert model.name == "axk1_32x2"
    dense, sparse = model.params["layers"]
    assert "indexer" not in dense and "indexer" not in sparse
    assert set(sparse["moe"]) == {"w_in", "w_out", "router", "shared"}
    assert sparse["moe"]["w_in"].shape == (4, 32, 32)
    assert sparse["moe"]["router"].shape == (32, 16)
    assert dense["w_uq"].shape == (24, 4 * 16) and "mlp" in dense


# -- what it shares -----------------------------------------------------------

def test_the_layer_body_is_glm_dsas_and_nothing_is_copied():
    assert axk1.glm_dsa is glm_dsa and not hasattr(axk1, "layer")
    assert axk1.load_config is laguna.load_config
    assert axk1.quantize_weights is laguna.quantize_weights
    cfg = axk1.latent_config(config())
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert "indexer_types" not in cfg
    # a cut that names published indices past its own depth is read by them
    deep = axk1.latent_config(config([0, 7], num_hidden_layers=2,
                                     moe_layer_freq=2))
    assert deep["mlp_layer_types"] == ["dense", "dense", "sparse", "dense",
                                       "sparse", "dense", "sparse", "dense"]


def test_the_control_leaves_the_router_and_the_routed_experts_as_they_are():
    from nnstreamer_tpu.ops.quant import QuantizedWeight

    control = axk1.build_quantized(config=config([0, 1], [0, 4]), seq=24,
                                   seed=1)
    dense, sparse = control.params["layers"]
    for p in (dense, sparse):
        assert all(isinstance(p[k], QuantizedWeight)
                   for k in ("w_dq", "w_uq", "w_dkv", "w_uk", "w_uv", "wo"))
    assert isinstance(dense["mlp"]["w_in"], QuantizedWeight)
    assert not any(isinstance(sparse["moe"][k], QuantizedWeight)
                   for k in ("w_in", "w_out", "router"))
    assert isinstance(sparse["moe"]["shared"]["w_out"], QuantizedWeight)
    assert not isinstance(control.params["embed"], QuantizedWeight)
    assert isinstance(control.params["head"], QuantizedWeight)


# -- YaRN ---------------------------------------------------------------------

PUBLISHED = {"type": "yarn", "factor": 32, "mscale": 1, "mscale_all_dim": 1,
             "original_max_position_embeddings": 4096, "beta_fast": 32,
             "beta_slow": 1}


@pytest.mark.parametrize("scaling,t", [(PUBLISHED, 16384), (YARN, 40),
                                       (dict(PUBLISHED, mscale=0.707), 64)],
                         ids=["published", "tiny", "mscale_apart"])
def test_the_yarn_tables_and_the_scale_are_the_references(scaling, t):
    cfg = config(rope_scaling=scaling, qk_nope_head_dim=128,
                 qk_rope_head_dim=64)
    read = axk1.latent_config(cfg)
    cos, sin = laguna.rotary_tables(read["rope_parameters"], 64, t)
    want_cos, want_sin = axk1_plain.rotary(cfg, t)
    np.testing.assert_allclose(np.asarray(cos), np.asarray(want_cos),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin), np.asarray(want_sin),
                               atol=1e-6)
    assert read["softmax_scale"] == pytest.approx(
        axk1_plain.softmax_scale(cfg), rel=1e-12)


def test_the_published_yarn_numbers():
    """``m(1) = 0.1 ln 32 + 1``; the score scale ``192^-1/2 m^2``; cos and
    sin unscaled; pairs 0-10 keep their frequency, 23-31 are slowed 32-fold
    and the pairs between blend."""
    cfg = axk1.latent_config(config(rope_scaling=PUBLISHED,
                                    qk_nope_head_dim=128,
                                    qk_rope_head_dim=64))
    assert axk1.yarn_scale(PUBLISHED, "mscale") == pytest.approx(1.34657,
                                                                 abs=1e-5)
    assert cfg["softmax_scale"] == pytest.approx(0.130861, abs=1e-6)
    assert cfg["rope_parameters"]["attention_factor"] == 1.0
    cos, sin = laguna.rotary_tables(cfg["rope_parameters"], 64, 3)
    angle = np.arctan2(np.asarray(sin, np.float64)[1],
                       np.asarray(cos, np.float64)[1])
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(angle[:11], plain[:11], rtol=1e-3)
    np.testing.assert_allclose(angle[23:], plain[23:] / 32, rtol=1e-3)
    assert np.all(angle[11:23] < plain[11:23])
    assert np.all(angle[11:23] > plain[11:23] / 32)
    # no rope_scaling: plain frequencies and the plain scale
    bare = axk1.latent_config(config(rope_scaling=None))
    assert bare["rope_parameters"]["rope_type"] == "default"
    assert bare["softmax_scale"] == 16 ** -0.5


# -- the router ---------------------------------------------------------------

def reference_choice(x, router, k, groups, kept, scaling=2.5):
    gates = np.asarray(axk1_plain.route(jnp.asarray(x), jnp.asarray(router),
                                        k, groups, kept, scaling))
    return gates


def program_gates(x, router, k, groups, kept, scaling=2.5, bias=None):
    w, experts = moe.route_top_k(jnp.asarray(x), jnp.asarray(router), k,
                                 scaling, bias, groups, kept)
    gates = np.zeros((x.shape[0], router.shape[1]), np.float32)
    np.put_along_axis(gates, np.asarray(experts), np.asarray(w), 1)
    return gates, np.asarray(experts)


@pytest.mark.parametrize("e,groups,kept,k", [(16, 4, 2, 4), (192, 8, 4, 8),
                                             (24, 8, 4, 8), (16, 2, 1, 4)])
def test_the_group_limited_choice_is_the_references(e, groups, kept, k):
    x = jax.random.normal(jax.random.PRNGKey(e), (300, 32))
    router = jax.random.normal(jax.random.PRNGKey(k), (32, e))
    with jax.default_matmul_precision("highest"):
        got, experts = program_gates(x, router, k, groups, kept)
        want = reference_choice(x, router, k, groups, kept)
    assert np.array_equal(got != 0, want != 0)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # every pick lies in one of ``kept`` groups a token
    assert (np.array([len(set(row // (e // groups))) for row in experts])
            <= kept).all()
    # and the limit binds: the k highest of all experts are other ones
    free, _ = program_gates(x, router, k, None, None)
    assert not np.array_equal(got != 0, free != 0)


def test_where_the_limit_binds_the_strong_expert_of_a_weak_group_is_left_out():
    """Six experts in three groups of two, two groups kept, top-2: expert 4
    has the token's highest score, but its group's two scores sum to less
    than either other group's, so it is not among the choice."""
    scores = np.array([[0.6, 0.5, 0.55, 0.5, 0.9, 0.05]])
    logits = np.log(scores / (1 - scores)).astype(np.float32)
    eye = np.eye(6, dtype=np.float32)
    with jax.default_matmul_precision("highest"):
        got, experts = program_gates(logits, eye, 2, 3, 2, 1.0)
        free, free_experts = program_gates(logits, eye, 2, None, None, 1.0)
        want = reference_choice(logits, eye, 2, 3, 2, 1.0)
    assert sorted(experts[0]) == [0, 2] and 4 in free_experts[0]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[0, [0, 2]], [0.6 / 1.15, 0.55 / 1.15],
                               rtol=1e-5)


def test_on_ties_the_earlier_group_and_the_earlier_expert_are_taken():
    """Every score equal: groups 0 and 1 of 4 are kept and experts 0-3
    chosen, on both sides; the weights are a quarter each."""
    x = np.ones((5, 8), np.float32)
    router = np.zeros((8, 16), np.float32)
    got, experts = program_gates(x, router, 4, 4, 2, 1.0)
    want = reference_choice(x, router, 4, 4, 2, 1.0)
    assert np.array_equal(np.sort(experts, -1),
                          np.tile(np.arange(4), (5, 1)))
    np.testing.assert_allclose(got, want)
    np.testing.assert_allclose(got[:, :4], 0.25)


def test_a_bias_steers_groups_and_experts_and_stays_out_of_the_weights():
    x = jax.random.normal(jax.random.PRNGKey(1), (100, 32))
    router = jax.random.normal(jax.random.PRNGKey(2), (32, 16))
    bias = jnp.zeros(16).at[12:].set(5.0)  # group 3 always kept
    _, experts = program_gates(x, router, 4, 4, 2, bias=bias)
    assert all((row >= 12).sum() >= 2 for row in experts)
    w, _ = moe.route_top_k(x, router, 4, 2.5, bias, 4, 2)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)


def test_one_group_or_none_is_the_choice_among_all_op_for_op():
    """Laguna's and GLM's routers: no ``n_group``, or the published
    ``n_group: 1``, trace the ops they had."""
    x, router = jnp.ones((8, 32)), jnp.ones((32, 16))
    bias = jnp.zeros(16)

    def ops(*args):
        return str(jax.make_jaxpr(lambda x, r: moe.route_top_k(
            x, r, 4, 2.5, *args))(x, router))

    assert ops() == ops(None, None, None) == ops(None, 1, 1)
    assert ops(bias) == ops(bias, 1, 1)
    assert ops(None, 4, 2) != ops()
    assert not moe.group_limited(None) and not moe.group_limited(1)
    assert moe.group_limited(8)


# -- the experts: the shares of a group-limited layer ---------------------------

def moe_params(key, d=32, f=16, e=16):
    ks = jax.random.split(key, 5)
    return {"router": jax.random.normal(ks[0], (d, e)),
            "w_in": jax.random.normal(ks[1], (e, d, 2 * f)) * 0.2,
            "w_out": jax.random.normal(ks[2], (e, f, d)) * 0.2,
            "shared": {"w_in": jax.random.normal(ks[3], (d, 2 * f)) * 0.2,
                       "w_out": jax.random.normal(ks[4], (f, d)) * 0.2}}


def share_of(p, first, count):
    return dict(p, w_in=p["w_in"][first:first + count],
                w_out=p["w_out"][first:first + count])


@pytest.mark.parametrize("chunk", [None, 10], ids=["whole", "in_chunks"])
@pytest.mark.parametrize("shares", [4, 16, 8])
def test_the_shares_add_up_to_the_uncut_references_layer(shares, chunk):
    """What ``shares`` chips give under the group-limited choice, each told
    which of the 16 experts it holds (half a group, a group, a single
    expert), summed with the shared expert counted once, is the uncut
    *reference's* layer; a token none of whose groups is held here gets the
    shared expert alone from that chip."""
    p = moe_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (50, 32))
    held = 16 // shares
    s = {"mlp_layer_types": None, "first_k_dense_replace": 0,
         "rms_norm_eps": 1e-6, "num_experts_per_tok": 4, "n_group": 4,
         "topk_group": 2, "routed_scaling_factor": 2.5,
         "n_routed_experts": 16}
    host = jax.tree_util.tree_map(np.asarray, p)
    with jax.default_matmul_precision("highest"):
        whole = axk1_plain.mlp_layer(
            s, 0, {"mlp_norm": np.ones(32, np.float32), "moe": host},
            x[None])[0] - x
        # the reference norms before its experts: hand the program the same
        h = axk1_plain.rms_norm(x, jnp.ones(32), 1e-6)
        shared = moe.swiglu(h, p["shared"]["w_in"], p["shared"]["w_out"])
        parts = [jax.jit(lambda p, h, first=first: moe.moe_top_k(
            p, h, 4, 2.5, chunk, first, 4, 2))(share_of(p, first, held), h)
            for first in range(0, 16, held)]
    total = sum(parts) - (shares - 1) * shared
    assert np.abs(np.asarray(total - whole)).max() < 1e-4
    assert np.abs(np.asarray(parts[0] - whole)).max() > 1e-2
    # tokens whose two groups exclude group 0 send nothing to its experts
    _, experts = moe.route_top_k(h, p["router"], 4, 2.5, None, 4, 2)
    away = ~(np.asarray(experts) < 4).any(-1)
    first_group = sum(parts[:4 // held]) - (4 // held - 1) * shared
    assert away.any() and np.abs(
        np.asarray(first_group - shared))[away].max() < 1e-5


def counted(name, label):
    metric = REGISTRY.get(name)
    child = dict(metric.children()).get((label,)) if metric else None
    return child.value if child else 0


def test_the_router_is_counted_by_its_choice_where_the_layer_is_lowered():
    name = "nnstpu_moe_routing_total"
    p = share_of(moe_params(jax.random.PRNGKey(0)), 0, 2)
    before = counted(name, "group_limited"), counted(name, "global")
    limited = jax.jit(lambda x: moe.moe_top_k(
        p, x, 4, first=0, n_group=4, topk_group=2)).trace(jnp.ones((8, 32)))
    free = jax.jit(lambda x: moe.moe_top_k(
        p, x, 4, first=0, n_group=1, topk_group=1)).trace(jnp.ones((8, 32)))
    assert (counted(name, "group_limited"), counted(name, "global")) == before
    limited.lower()
    limited.lower(lowering_platforms=("tpu",))
    free.lower()
    assert counted(name, "group_limited") == before[0] + 2
    assert counted(name, "global") == before[1] + 1
    assert counted("nnstpu_moe_held_experts", "16") == 2


# -- the attention ------------------------------------------------------------

def attention_operands(t, heads=2, dn=128, dv=128, b=2, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(t + heads), 4)
    return (jax.random.normal(ks[0], (b, t, heads * (dn + 64)), dtype),
            jax.random.normal(ks[1], (b, t, heads * dn), dtype),
            jax.random.normal(ks[2], (b, t, 64), dtype),
            jax.random.normal(ks[3], (b, t, heads * dv), dtype))


def written_out(q, k_n, k_r, v, heads, scale):
    """The definition, a head at a time, over every causal key."""
    b, t, _ = q.shape
    qh = q.reshape(b, t, heads, -1)
    dn = k_n.shape[-1] // heads
    seen = jnp.tril(jnp.ones((t, t), bool))
    out = []
    for h in range(heads):
        s = (jnp.einsum("btd,bsd->bts", qh[:, :, h, :dn],
                        k_n.reshape(b, t, heads, dn)[:, :, h])
             + jnp.einsum("btd,bsd->bts", qh[:, :, h, dn:], k_r))
        s = jnp.where(seen, s * scale, -jnp.inf)
        out.append(jnp.einsum("bts,bsd->btd", jax.nn.softmax(s, -1),
                              v.reshape(b, t, heads, -1)[:, :, h]))
    return jnp.concatenate(out, -1)


def tables(t, rot=64):
    cfg = axk1.latent_config(config(qk_rope_head_dim=rot))
    return laguna.rotary_tables(cfg["rope_parameters"], rot, t)


BLOCKS = pytest.mark.parametrize(
    "blocks", [(128, 128), (128, 64), (256, 128), (64, 128)],
    ids=lambda b: f"{b[0]}x{b[1]}")
# heads, a head's unrotated dims, the value's, the dims the tables rotate
HEADS = pytest.mark.parametrize("heads,dn,dv,rot", [
    (2, 128, 128, 64),   # the published head: 128 | 64 with values of 128
    (4, 128, 128, 64),   # two pairs
    (2, 256, 256, 64),   # two tiles of unrotated dims a head
    (2, 128, 256, 32),   # tables that rotate half of the rotary dims
], ids=["2x128_64", "4x128_64", "2x256_64", "2x128_rot32"])


@BLOCKS
@pytest.mark.parametrize("scale", [None, 0.13], ids=["default_scale",
                                                     "the_callers"])
def test_the_kernel_without_a_selection_in_interpret_mode(blocks, scale):
    """No mask anywhere: causal by position, q and ``k_rope`` handed over
    rotated (no tables), against the definition and the plain walk."""
    q, k_n, k_r, v = attention_operands(256)
    with jax.default_matmul_precision("highest"):
        got = sa.latent_attention_kernel(
            q, k_n, k_r, v, 2, None, scale, block_q=blocks[0],
            block_k=blocks[1], interpret=True)
        want = written_out(q, k_n, k_r, v, 2, scale or 192 ** -0.5)
        plain = sa.latent_sparse_attention(q, k_n, k_r, v, None, 2,
                                           scale=scale)
    assert got.shape == want.shape == (2, 256, 256)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert np.abs(np.asarray(plain - want)).max() < 1e-5


@BLOCKS
@HEADS
def test_the_kernel_rotates_q_as_rotate_does_at_float32(heads, dn, dv, rot,
                                                        blocks):
    q, k_n, k_r, v = attention_operands(256, heads, dn, dv)
    cos, sin = tables(256, rot)
    with jax.default_matmul_precision("highest"):
        got = sa.latent_attention_kernel(
            q, k_n, k_r, v, heads, (cos, sin), 0.13, block_q=blocks[0],
            block_k=blocks[1], interpret=True)
        turned = rotate(k_r, cos, sin, 1)
        want = written_out(rotate(q, cos, sin, heads, dn), k_n, turned, v,
                           heads, 0.13)
    assert got.shape == want.shape == (2, 256, heads * dv)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    # and it does rotate: the unrotated q gives another answer
    assert np.abs(np.asarray(written_out(q, k_n, turned, v, heads, 0.13)
                             - want)).max() > 1e-2


@BLOCKS
@HEADS
def test_the_kernel_rotates_q_to_rotates_bits_at_bfloat16(heads, dn, dv, rot,
                                                          blocks):
    """bf16: the rotation in the kernel rounds where ``rotate()`` rounds, so
    the kernel with tables equals the kernel handed a rotated q to the last
    bit, but where this host's compiler contracts ``x * C + partner * S``
    to a fused multiply-add in one program and not in the other; the plain
    lowering lies within a bf16 step of the output."""
    q, k_n, k_r, v = attention_operands(256, heads, dn, dv,
                                        dtype=jnp.bfloat16)
    cos, sin = tables(256, rot)
    kernel = functools.partial(sa.latent_attention_kernel, scale=0.13,
                               block_q=blocks[0], block_k=blocks[1],
                               interpret=True)
    inside = kernel(q, k_n, k_r, v, heads, (cos, sin))
    outside = kernel(rotate(q, cos, sin, heads, dn), k_n,
                     rotate(k_r, cos, sin, 1), v, heads)
    plain = sa.latent_sparse_attention(q, k_n, k_r, v, None, heads,
                                       rotary=(cos, sin), scale=0.13)
    assert inside.dtype == plain.dtype == jnp.bfloat16
    inside, outside, plain = (np.asarray(a, np.float32)
                              for a in (inside, outside, plain))
    step = 2 ** -7 * np.abs(plain).max()
    assert (inside != outside).mean() < 1e-3
    assert np.abs(inside - outside).max() <= step
    assert np.abs(inside - plain).max() <= step


def test_the_plain_walk_takes_row_blocks_and_makes_no_t_by_t_array(
        monkeypatch):
    q, k_n, k_r, v = attention_operands(96, dn=16, dv=16)
    with jax.default_matmul_precision("highest"):
        whole = sa._plain(q, k_n, k_r, v, None, n_heads=2, scale=0.2)
        monkeypatch.setattr(sa, "SELECT_ROWS", 32)
        walk = jax.jit(functools.partial(sa._plain, mask=None, n_heads=2,
                                         scale=0.2))
        blocks = walk(q, k_n, k_r, v)
        text = walk.lower(q, k_n, k_r, v).as_text()
    assert np.abs(np.asarray(whole - blocks)).max() < 1e-5
    assert "x96x96x" not in text and "x32x96x" in text


def lowering_counts():
    return tuple(counted(name, label) for name, label in (
        ("nnstpu_attention_rotary_total", "kernel"),
        ("nnstpu_attention_rotary_total", "outside"),
        ("nnstpu_attention_lowerings_total", "latent"),
        ("nnstpu_attention_lowerings_total", "latent_plain"),
        ("nnstpu_attention_lowerings_total", "latent_sparse"),
        ("nnstpu_attention_lowerings_total", "latent_sparse_plain")))


@pytest.mark.parametrize("heads,dn,dr,dv,rot,why", [
    (2, 128, 64, 128, 64, None),
    (64, 128, 64, 128, 64, None),
    (2, 256, 64, 128, 32, None),
    (3, 128, 64, 128, 64, "an odd number of heads pairs off no last one"),
    (2, 192, 64, 256, 64, "unrotated dims that end half a tile in: the "
                          "kernel under a selection's shape"),
    (2, 64, 64, 128, 64, "no whole tile of unrotated dims"),
    (2, 128, 32, 128, 32, "a pair's q columns are no whole lane tiles"),
    (2, 128, 64, 64, 64, "values of half a tile"),
], ids=["the_pair", "the_published_64", "dn_256_rot_32", "odd_heads",
        "dn_192", "dn_64", "dr_32", "dv_64"])
def test_what_the_kernel_without_a_selection_tiles(heads, dn, dr, dv, rot,
                                                   why):
    t = 2 * sa.LATENT_BLOCK_Q
    shapes = ((1, t, heads * (dn + dr)), (1, t, heads * dn), (1, t, dr),
              (1, t, heads * dv))
    assert sa.latent_tiles(*shapes, jnp.bfloat16, heads, (t, rot // 2)) \
        == (why is None), why
    assert sa.latent_tiles(*shapes, jnp.float32, heads) == (why is None)
    assert not sa.latent_tiles(*shapes, jnp.int8, heads, (t, rot // 2))
    # tables of other positions, or that rotate more than the rotary dims
    assert not sa.latent_tiles(*shapes, jnp.bfloat16, heads, (t // 2, 32))
    assert not sa.latent_tiles(*shapes, jnp.bfloat16, heads, (t, dr))
    # a window that is no whole number of blocks
    odd = tuple((1, t + 128, s[-1]) for s in shapes)
    assert not sa.latent_tiles(*odd, jnp.bfloat16, heads)
    # and where it does not, a TPU's program rotates outside and walks
    q, k_n, k_r, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes)
    table = jax.ShapeDtypeStruct((t, rot // 2), jnp.float32)
    before = lowering_counts()
    text = jax.jit(lambda *a: sa.latent_sparse_attention(
        *a[:4], None, heads, rotary=a[4:], scale=0.13)).trace(
        q, k_n, k_r, v, table, table).lower(
        lowering_platforms=("tpu",)).as_text()
    assert (sa.LATENT_KERNEL_NAME in text) == (why is None)
    assert sa.KERNEL_NAME not in text  # never the kernel under a selection
    kernel = int(why is None)
    assert lowering_counts() == (
        before[0] + kernel, before[1] + 1 - kernel, before[2] + kernel,
        before[3] + 1 - kernel, before[4], before[5])


def test_one_trace_lowers_the_kernel_for_a_tpu_and_the_walk_here():
    """Which lowering a call gets is the lowering rule's choice: the same
    trace holds the kernel for a TPU and the walk for this host.  The
    model's layers each count one attention and one rotation, in the kernel
    for a TPU and outside here; a TPU's program holds no ``[B, T, T]``
    array, no q or keys a head a row for XLA to re-tile, and counts its
    sparse layers under the group-limited choice."""
    q, k_n, k_r, v = attention_operands(1024)
    attend = jax.jit(lambda *a: sa.latent_sparse_attention(
        *a, None, 2)).trace(q, k_n, k_r, v)
    before = lowering_counts()
    assert sa.LATENT_KERNEL_NAME in attend.lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in attend.lower().as_text()
    assert lowering_counts() == (before[0], before[1], before[2] + 1,
                                 before[3] + 1, before[4], before[5])
    cfg = config([0, 1, 2], [0, 2], qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128)
    model = axk1.build(cfg, seq=1024, batch=2, seed=1)
    program = jax.jit(model.fn()).trace(
        jax.ShapeDtypeStruct((2, 1024), jnp.int32))
    by_head = "tensor<2x1024x4x192x"  # q or the keys, a head a row
    square = "x1024x1024x"
    routed = counted("nnstpu_moe_routing_total", "group_limited")
    before = lowering_counts()
    on_tpu = program.lower(lowering_platforms=("tpu",)).as_text()
    assert on_tpu.count(sa.LATENT_KERNEL_NAME) >= 3
    assert by_head not in on_tpu and square not in on_tpu
    assert sa.KERNEL_NAME not in on_tpu
    assert lowering_counts() == (before[0] + 3, before[1], before[2] + 3,
                                 before[3], before[4], before[5])
    assert counted("nnstpu_moe_routing_total",
                   "group_limited") == routed + 2
    here = program.lower().as_text()
    assert "tpu_custom_call" not in here and by_head in here
    assert square not in here
    assert lowering_counts() == (before[0] + 3, before[1] + 3, before[2] + 3,
                                 before[3] + 3, before[4], before[5])


def test_a_selection_still_lowers_the_kernel_under_it():
    """GLM's call, a mask and heads of 192 | 64, gets the kernel it had and
    is counted as it was; handed no mask it has no kernel and walks."""
    b, t, h = 1, 2 * sa.BLOCK_Q, 2
    shapes = ((b, t, h * 256), (b, t, h * 192), (b, t, 64), (b, t, h * 256))
    q, k_n, k_r, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes)
    mask = jax.ShapeDtypeStruct((b, t, t), jnp.int8)
    before = lowering_counts()
    text = jax.jit(lambda *a: sa.latent_sparse_attention(*a, h)).trace(
        q, k_n, k_r, v, mask).lower(lowering_platforms=("tpu",)).as_text()
    assert sa.KERNEL_NAME in text and sa.LATENT_KERNEL_NAME not in text
    text = jax.jit(lambda *a: sa.latent_sparse_attention(
        *a, None, h)).trace(q, k_n, k_r, v).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in text
    after = lowering_counts()
    assert (after[2], after[3], after[4], after[5]) == (
        before[2], before[3] + 1, before[4] + 1, before[5])


# -- the streaming path -------------------------------------------------------

def test_token_frames_through_a_launch_string_at_batch_n_equal_n_single(tmp_path):
    """``tensor_filter framework=jax`` opens the model from a checkpoint and
    the published config by the builder's name, like the other zoo models;
    a batch of N windows gives the N rows that N single windows give."""
    cfg = config([0, 1, 2, 3, 4], [0, 2])
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    params = glm_dsa.init_params(axk1.latent_config(cfg), 3, jnp.float32)
    save_state(params, str(tmp_path / "axk1.npz"))
    ids = np.random.default_rng(4).integers(0, 96, (4, 24), dtype=np.int32)

    def run(frames, custom):
        got = []
        p = parse_launch(
            "datasrc name=s ! tensor_filter framework=jax name=f "
            f"model={tmp_path / 'axk1.npz'} custom={custom} "
            "! tensor_sink name=out")
        p["s"].data = [f.copy() for f in frames]
        p["out"].connect("new-data",
                         lambda f: got.append(np.asarray(f.tensor(0))))
        p.run(timeout=120)
        return got

    custom = (f"builder=axk1:build,config={tmp_path / 'config.json'},"
              "seq=24,dtype=float32")
    with jax.default_matmul_precision("highest"):
        singles = run(list(ids), custom)
        batched, = run([ids], custom + ",batch=4")
    assert batched.shape == (4, 96) and batched.dtype == np.float32
    assert [s.shape for s in singles] == [(96,)] * 4
    np.testing.assert_allclose(np.stack(singles), batched, atol=1e-5)
    want = axk1_plain.forward(dict(cfg, seq=24), {},
                              jax.tree_util.tree_map(np.asarray, params),
                              ids)
    assert np.abs(batched - want).max() < 1e-4
