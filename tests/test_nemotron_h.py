"""The ``nemotron_h`` sequential hybrid token model at tiny widths on the
CPU: the model against its plain reference
(``benchmark/references/nemotron_h_plain.py``, float32 at ``highest``,
nothing of the program imported) through a launch string and
``tensor_filter``; the pattern read into layer kinds; the scan kernel's
``shared_tile`` body (heads of 64, two a lane tile) in interpret mode
against the recurrence; relu² experts through a share's passes and XLA's
grouped product against a dense loop; four shares of a latent expert layer
against the uncut layer; the router reading x and not its latent; and what
a TPU lowering counts beside a CPU lowering; the grouped kernel's relu²
body against each expert's feed-forward, a share's pass through it against
XLA's, and which shares a TPU lowering sends to it.  The kernels compiled by
Mosaic for a described v5e are in ``tests/test_fused_attention.py``."""

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

from benchmark.references import falcon_h1_plain, nemotron_h_plain  # noqa: E402
from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.models import nemotron_h  # noqa: E402
from nnstreamer_tpu.obs.metrics import REGISTRY  # noqa: E402
from nnstreamer_tpu.ops import grouped_experts, ssm_scan  # noqa: E402
from nnstreamer_tpu.parallel import moe  # noqa: E402
from nnstreamer_tpu.utils.checkpoint import save_state  # noqa: E402

PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def config(**over):
    """The published keys at tiny widths: one of each layer kind and a
    second expert layer, as the pattern spells them."""
    cfg = {
        "vocab_size": 96, "hidden_size": 32, "hybrid_override_pattern": "MEM*E",
        "num_hidden_layers": 5, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "mamba_num_heads": 8,
        "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 8,
        "conv_kernel": 4, "use_conv_bias": True, "chunk_size": 8,
        "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5, "n_routed_experts": 16,
        "num_experts_per_tok": 4, "routed_scaling_factor": 5.0,
        "moe_intermediate_size": 12, "moe_latent_size": 16,
        "moe_shared_expert_intermediate_size": 24, "n_group": 1,
        "topk_group": 1, "norm_topk_prob": True, "rope_theta": 10000,
    }
    return dict(cfg, **over)


CASES = {
    "one_of_each_kind": config(),
    "a_share_of_the_experts": config(experts_held=[4, 4]),
    "published_layers_by_index": config(
        hybrid_override_pattern=PUBLISHED, num_hidden_layers=88,
        layers=[33, 34, 35, 36], experts_held=[8, 8]),
}


def counts(name):
    metric = REGISTRY.get(name)
    if metric is None:
        return {}
    return {key: child.value for key, child in metric.children()}


def both(cfg, t, dtype, batch=3, seed=0):
    params = nemotron_h.init_params(cfg, seed, dtype)
    model = nemotron_h.build(cfg, seq=t, batch=batch, dtype=dtype,
                             params=params)
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, t), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.fn())(ids))
    host = jax.tree_util.tree_map(np.asarray, params)
    return got, nemotron_h_plain.forward(dict(cfg, seq=t), {}, host, ids)


# -- the model ----------------------------------------------------------------

@pytest.mark.parametrize("t", [24, 5], ids=["three_chunks", "inside_one"])
@pytest.mark.parametrize("case", CASES)
def test_float32_matches_the_plain_reference(case, t):
    got, want = both(CASES[case], t, jnp.float32)
    assert got.shape == want.shape == (3, 96) and got.dtype == np.float32
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("case", CASES)
def test_bfloat16_stays_near_the_plain_reference(case):
    """bf16 weights and activations against the float32 walk over the same
    bf16 weights: rounding, and at these sizes now and then an expert of 4
    that falls the other way on a near-tie, so the bound is loose."""
    got, want = both(CASES[case], 24, jnp.bfloat16)
    assert np.isfinite(got).all()
    assert np.linalg.norm(got - want) < 0.1 * np.linalg.norm(want)


def test_the_pattern_is_read_into_layer_kinds():
    cut = config(hybrid_override_pattern=PUBLISHED, num_hidden_layers=88,
                 layers=list(range(27, 38)))
    assert "".join(nemotron_h.layer_kinds(cut)) == "MEMEMEMEM*E"
    # the slice alone, one character a layer built
    assert nemotron_h.layer_kinds(dict(
        cut, hybrid_override_pattern="MEMEMEMEM*E")) == list("MEMEMEMEM*E")
    assert nemotron_h.layer_kinds(config()) == list("MEM*E")
    with pytest.raises(ValueError, match="layer kinds"):
        nemotron_h.layer_kinds(config(hybrid_override_pattern="M-M*E"))
    # the build says how many layers of each kind it made
    nemotron_h.build(dict(cut, hidden_size=32), seq=8, params={})
    assert counts("nnstpu_hybrid_layers") == {
        ("mamba",): 5, ("attention",): 1, ("experts",): 5}


def test_the_mixer_is_falcons_over_this_configs_widths():
    """``mixer_config`` hands ``falcon_h1.mixer`` the published widths, and
    the family's config holds no multiplier for it to read."""
    published = config(hidden_size=4096, mamba_num_heads=128,
                       mamba_head_dim=64, n_groups=8, ssm_state_size=128,
                       chunk_size=128)
    keys = nemotron_h.mixer_config(published)
    assert not any("multiplier" in k for k in keys)
    w = nemotron_h.widths(keys)
    assert (w["d_ssm"], w["bc"], w["conv"], w["in"]) == (
        8192, 1024, 10240, 18560)


def test_the_control_is_lower_and_further_from_the_reference():
    cfg = config(chunk_size=16)
    params = nemotron_h.init_params(cfg, 4, jnp.bfloat16)
    ids = np.random.default_rng(4).integers(0, 96, (4, 64), dtype=np.int32)
    host = jax.tree_util.tree_map(np.asarray, params)
    want = nemotron_h_plain.forward(dict(cfg, seq=64), {}, host, ids)

    def run(build):
        model = build(config=cfg, seq=64, batch=4, params=params,
                      low=build is nemotron_h.build_quantized)
        return np.asarray(jax.jit(model.fn())(ids))

    sound, control = run(nemotron_h.build), run(nemotron_h.build_quantized)
    assert np.abs(control - want).max() > np.abs(sound - want).max()


# -- the scan at heads of 64 --------------------------------------------------

def recurrence(x, dt, a, b, c, d, groups):
    bt, t, hp = x.shape
    heads = dt.shape[-1]
    return np.asarray(falcon_h1_plain.recurrence(
        x.reshape(bt, t, heads, hp // heads), dt, a,
        b.reshape(bt, t, groups, -1), c.reshape(bt, t, groups, -1), d,
        groups)).reshape(bt, t, hp)


def narrow_operands(t, heads=8, p=64, groups=2, n=128, batch=2, seed=0):
    """Heads of 64, each with its own decay (A from -16 to -0.05: gone
    within a few tokens, or whole windows back), Δ and D, so that a head's
    lanes read another's only if the body mixes them up."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((batch, t, heads * p)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (batch, t, heads)))
    a = -np.geomspace(16.0, 0.05, heads).astype(np.float32)
    b, c = (rng.standard_normal((batch, t, groups * n)).astype(np.float32)
            * 0.3 for _ in range(2))
    d = np.linspace(0.0, 2.0, heads).astype(np.float32)
    return [jnp.asarray(v, jnp.float32) for v in (x, dt, a, b, c, d)]


@pytest.mark.parametrize("chunks", [1, 3, 5])
def test_two_heads_a_lane_tile_are_the_recurrence(chunks):
    ops = narrow_operands(chunks * 128)
    assert ssm_scan.scan_tiles(ops[0].shape, ops[1].shape, ops[3].shape,
                               jnp.float32, 2, 128)
    assert ssm_scan.body(64) == "shared_tile"
    want = recurrence(*ops, 2)
    with jax.default_matmul_precision("highest"):
        got = ssm_scan.ssd_scan_kernel(*ops, 128, 2, interpret=True)
    err = np.abs(np.asarray(got) - want).max() / np.abs(want).max()
    assert err < 2e-5, err


def test_a_padded_window_of_narrow_heads():
    ops = narrow_operands(2 * 128 - 37, heads=4, groups=1, batch=1, seed=3)
    want = recurrence(*ops, 1)
    with jax.default_matmul_precision("highest"):
        got = ssm_scan.ssd_scan_kernel(*ops, 128, 1, interpret=True)
    assert got.shape == want.shape
    assert np.abs(np.asarray(got) - want).max() < 2e-5 * np.abs(want).max()


def test_the_narrow_control_rounds_as_the_plain_scan_does():
    ops = narrow_operands(4 * 128, seed=5)
    want = recurrence(*ops, 2)
    plain = np.asarray(ssm_scan.plain_scan(*ops, 128, 2, low=True))
    kernel = np.asarray(ssm_scan.ssd_scan_kernel(*ops, 128, 2, low=True,
                                                 interpret=True))
    scale = np.abs(want).max()
    assert np.abs(plain - kernel).max() < 1e-5 * scale
    assert np.abs(plain - want).max() > 1e-2 * scale


@pytest.mark.parametrize("x,dt,b,groups,body", [
    ((8, 4096, 8192), (8, 4096, 128), (8, 4096, 1024), 8, "shared_tile"),
    ((8, 4096, 4096), (8, 4096, 32), (8, 4096, 512), 2, "whole_tile"),
], ids=["nemotron_published", "falcon_published"])
def test_both_bodies_tile_their_published_shapes(x, dt, b, groups, body):
    assert ssm_scan.scan_tiles(x, dt, b, jnp.bfloat16, groups, 128)
    assert ssm_scan.body(x[-1] // dt[-1]) == body


# -- relu² experts, a latent width --------------------------------------------

def moe_params(key, d=32, lat=None, f=12, e=16, fs=24):
    """relu² experts: ``w_in`` without a gate half; in ``lat`` where
    given."""
    ks = jax.random.split(key, 9)
    width = lat or d
    p = {"router": jax.random.normal(ks[0], (d, e)),
         "bias": jax.random.normal(ks[1], (e,)) * 0.3,
         "w_in": jax.random.normal(ks[2], (e, width, f)) * 0.3,
         "w_out": jax.random.normal(ks[3], (e, f, width)) * 0.3,
         "shared": {"w_in": jax.random.normal(ks[4], (d, fs)) * 0.2,
                    "w_out": jax.random.normal(ks[5], (fs, d)) * 0.2}}
    if lat:
        p.update(latent_in=jax.random.normal(ks[6], (d, lat)) * 0.2,
                 latent_out=jax.random.normal(ks[7], (lat, d)) * 0.2)
    return p


def dense_relu2(p, x, top_k, scaling, experts=range(16)):
    """The layer as a plain loop: every expert over every token, weighed by
    the token's weight for it (zero where it did not choose it)."""
    w, chosen = moe.route_top_k(x, p["router"], top_k, scaling, p["bias"])
    rows = x @ p["latent_in"] if "latent_in" in p else x
    out = jnp.zeros_like(rows)
    for i, e in enumerate(experts):
        mine = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        out += mine[:, None] * (jnp.square(jax.nn.relu(
            rows @ p["w_in"][i])) @ p["w_out"][i])
    if "latent_out" in p:
        out = out @ p["latent_out"]
    return out + jnp.square(jax.nn.relu(x @ p["shared"]["w_in"])) \
        @ p["shared"]["w_out"]


def share_of(p, first, count):
    return dict(p, w_in=p["w_in"][first:first + count],
                w_out=p["w_out"][first:first + count])


@pytest.mark.parametrize("lat", [None, 16], ids=["model_width", "latent"])
@pytest.mark.parametrize("held", [None, (4, 4)], ids=["all_held", "a_share"])
def test_relu2_experts_are_the_dense_loop(held, lat):
    """All experts held: XLA's grouped product (``_routed_grouped``); a
    share: its passes (``_routed_share``); both against the loop."""
    p = moe_params(jax.random.PRNGKey(0), lat=lat)
    x = jax.random.normal(jax.random.PRNGKey(1), (50, 32))
    first = None if held is None else held[0]
    if held is not None:
        p = share_of(p, *held)
    experts = range(16) if held is None else range(held[0], sum(held))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: moe.moe_top_k(
            p, x, 4, 5.0, None, first, act="relu2"))(p, x)
        want = dense_relu2(p, x, 4, 5.0, experts)
    assert np.abs(np.asarray(got - want)).max() < 1e-4 * np.abs(
        np.asarray(want)).max()


@pytest.mark.parametrize("chunk", [None, 10], ids=["whole", "in_chunks"])
def test_four_shares_add_up_to_the_uncut_latent_layer(chunk):
    """What four chips give, each told which 4 of the 16 experts it holds,
    summed with the shared expert counted once, is the layer that holds
    them all: the latent projections are linear, so each share's part of
    the latent sum projected back adds up to the whole sum's."""
    p = moe_params(jax.random.PRNGKey(0), lat=16)
    x = jax.random.normal(jax.random.PRNGKey(1), (50, 32))
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda p, x: moe.moe_top_k(
            p, x, 4, 5.0, chunk, act="relu2"))(p, x)
        shared = moe.expert_mlp(x, p["shared"]["w_in"], p["shared"]["w_out"],
                                "relu2")
        parts = [jax.jit(lambda p, x, first=first: moe.moe_top_k(
            p, x, 4, 5.0, chunk, first, act="relu2"))(share_of(p, first, 4), x)
            for first in range(0, 16, 4)]
    total = sum(parts) - 3 * shared
    assert np.abs(np.asarray(total - whole)).max() < 1e-4
    assert np.abs(np.asarray(parts[0] - whole)).max() > 1e-2


def test_the_router_reads_x_and_the_experts_its_latent(monkeypatch):
    seen = {}
    route = moe.route_top_k

    def recorded(x, router, *rest):
        seen["router_input"] = x.shape[-1]
        return route(x, router, *rest)

    monkeypatch.setattr(moe, "route_top_k", recorded)
    p = moe_params(jax.random.PRNGKey(2), lat=16)
    x = jax.random.normal(jax.random.PRNGKey(3), (20, 32))
    with jax.default_matmul_precision("highest"):
        got = moe.moe_top_k(p, x, 4, 5.0, act="relu2")
        # the same layer routed on its latent z instead: another answer
        z_routed = dict(p, router=p["latent_in"] @ jnp.linalg.pinv(
            p["latent_in"]) @ p["router"])
        other = moe.moe_top_k(z_routed, x, 4, 5.0, act="relu2")
    assert seen["router_input"] == 32
    assert np.abs(np.asarray(got - dense_relu2(p, x, 4, 5.0))).max() < 1e-4
    assert np.abs(np.asarray(got - other)).max() > 1e-3


def gathered(x, router, k, scaling, bias, n_group, topk_group):
    """The router with its chosen scores read by ``take_along_axis``, as
    ``route_top_k`` read them before the masked select."""
    _, experts = moe.route_top_k(x, router, k, scaling, bias, n_group,
                                 topk_group)
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    top = jnp.take_along_axis(scores, experts, axis=-1)
    return top / top.sum(axis=-1, keepdims=True) * scaling, experts


def router_cases():
    """bias or none x group-limited or not x f32 / bf16 x E x k, where the
    choice stands: top-22 of 16 experts is none."""
    for biased in (False, True):
        for grouped in (False, True):
            for dtype in ("float32", "bfloat16"):
                for e in (16, 64, 192, 512):
                    for k in (1, 8, 22):
                        if k <= (8 if e == 16 and grouped else e):
                            yield pytest.param(
                                biased, grouped, dtype, e, k,
                                id=f"{'bias' if biased else 'nobias'}-"
                                   f"{'grouped' if grouped else 'global'}-"
                                   f"{dtype}-e{e}-k{k}")


@pytest.mark.parametrize("biased,grouped,dtype,e,k", router_cases())
def test_the_masked_select_is_take_along_axis_bit_for_bit(
        biased, grouped, dtype, e, k):
    """Every pick's weight and expert as the gather gave them, to the bit:
    ``top_k``'s indices are distinct, so the select keeps one score a pick."""
    ks = jax.random.split(jax.random.PRNGKey(e * 100 + k), 3)
    x = jax.random.normal(ks[0], (64, 24)).astype(dtype)
    router = (jax.random.normal(ks[1], (24, e)) * 0.5).astype(dtype)
    bias = jax.random.normal(ks[2], (e,)) * 0.3 if biased else None
    groups = ((4, 2) if e == 16 else (8, 4)) if grouped else (None, None)
    args = (x, router, k, 5.0, bias, *groups)
    got_w, got = jax.jit(moe.route_top_k, static_argnums=(2, 3, 5, 6))(*args)
    want_w, want = jax.jit(gathered, static_argnums=(2, 3, 5, 6))(*args)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(got_w).view(np.uint32),
                          np.asarray(want_w).view(np.uint32))


def test_a_biased_router_is_counted_where_it_is_traced_and_an_unbiased_not():
    """One biased expert layer traced: the count rises by one; the same
    layer without its bias (Laguna's router): it stays."""
    name = "nnstpu_moe_biased_routers_total"
    p = share_of(moe_params(jax.random.PRNGKey(0)), 0, 4)
    x = jnp.ones((8, 32))
    before = counts(name).get(("select",), 0)
    jax.jit(lambda x: moe.moe_top_k(p, x, 4, 5.0, first=0,
                                    act="relu2")).trace(x)
    assert counts(name)[("select",)] == before + 1
    unbiased = {key: v for key, v in p.items() if key != "bias"}
    jax.jit(lambda x: moe.moe_top_k(unbiased, x, 4, 5.0, first=0,
                                    act="relu2")).trace(x)
    assert counts(name)[("select",)] == before + 1


def test_the_grouped_kernel_refuses_an_activation_it_has_no_body_for():
    shapes = ((65536, 2048), (8, 2048, 1024), (8, 512, 2048))
    assert grouped_experts.tiles(*shapes, jnp.bfloat16)
    assert not grouped_experts.tiles(*shapes, jnp.bfloat16, "gelu")
    with pytest.raises(ValueError, match="body"):
        grouped_experts.grouped_experts(
            jnp.zeros((8, 8)), jnp.zeros((2, 8, 4)), jnp.zeros((2, 4, 8)),
            jnp.array([4, 4], jnp.int32), jnp.ones(8), act="gelu")
    with pytest.raises(ValueError, match="activation"):
        moe.routed_experts(jnp.zeros((4, 8)), jnp.ones((4, 2)),
                           jnp.zeros((4, 2), jnp.int32), jnp.zeros((2, 8, 4)),
                           jnp.zeros((2, 4, 8)), act="gelu")


@pytest.mark.parametrize("act,halves,admitted", [
    ("swiglu", 2, True),    # [gate | up]
    ("swiglu", 1, False),   # no gate half: no SwiGLU reading of it
    ("relu2", 1, True),     # relu²'s own w_in
    ("relu2", 2, False),    # a SwiGLU w_in is no relu² expert
], ids=["swiglu_gate_up", "swiglu_without_a_gate",
        "relu2_one_half", "relu2_of_a_gate_up"])
def test_the_grouped_kernel_tiles_each_activation_with_its_own_w_in(
        act, halves, admitted):
    rows, d, f, e = 65536, 2048, 512, 8
    assert grouped_experts.tiles((rows, d), (e, d, halves * f), (e, f, d),
                                 jnp.bfloat16, act) is admitted


@pytest.mark.parametrize("shape,act,admitted", [
    # a Nemotron pass: 90 112 rows over 128 of 1024 x 2688, ~28.8 MB a visit
    (((90112, 1024), (128, 1024, 2688), (128, 2688, 1024)), "relu2", True),
    # GLM's share: one expert is 75.5 MB
    (((8192, 6144), (16, 6144, 4096), (16, 2048, 6144)), "swiglu", False),
    # A.X-K1's: 88.1 MB
    (((8192, 7168), (12, 7168, 4096), (12, 2048, 7168)), "swiglu", False),
], ids=["nemotron_pass", "glm_share", "axk1_share"])
def test_one_experts_weights_against_vmem_choose_the_kernel(
        shape, act, admitted):
    """relu² counts its one half of ``w_in``: a Nemotron expert's visit fits
    the budget; a GLM or A.X-K1 expert is refused by its size alone."""
    assert grouped_experts.tiles(*shape, jnp.bfloat16, act) is admitted
    (_, d), _, (_, f, _) = shape
    need = grouped_experts.vmem_bytes(d, f, 2, act)
    assert (need <= grouped_experts.VMEM_BUDGET) is admitted
    if act == "relu2":
        assert need < grouped_experts.vmem_bytes(d, f, 2, "swiglu")


def relu2_rows(rows, w_in, w_out, sizes, pair_weights):
    """Row by row, each through its own expert's ``moe.expert_mlp``."""
    ends = np.cumsum(sizes)
    out = []
    for r in range(int(ends[-1])):
        e = int(np.searchsorted(ends, r, side="right"))
        out.append(moe.expert_mlp(rows[r:r + 1], w_in[e], w_out[e], "relu2")
                   * pair_weights[r])
    return jnp.concatenate(out)


@pytest.mark.parametrize("sizes,m,tile,block", [
    ([16, 0, 32, 0, 16], 64, 16, 8),     # empty experts, groups whole blocks
    ([5, 11, 0, 21, 3, 10], 50, 16, 8),  # groups that are no whole blocks
    ([7, 0, 13, 9], 64, 16, 8),          # 29 live rows of 64: the rest unread
    ([0, 0, 37], 48, 32, 16),            # one group over two tiles, then none
], ids=["empty_experts", "partial_blocks", "fewer_than_m", "last_only"])
def test_the_relu2_body_is_each_experts_mlp_row_for_row(sizes, m, tile, block):
    e, d, f = len(sizes), 32, 24
    ks = jax.random.split(jax.random.PRNGKey(m + e), 4)
    rows = jax.random.normal(ks[0], (m, d))
    w_in = jax.random.normal(ks[1], (e, d, f)) * 0.3
    w_out = jax.random.normal(ks[2], (e, f, d)) * 0.3
    pair_weights = jax.random.uniform(ks[3], (m,))
    live = sum(sizes)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: grouped_experts.grouped_experts(
            *a, tile_rows=tile, block_rows=block, interpret=True,
            act="relu2"))(rows, w_in, w_out, jnp.asarray(sizes, jnp.int32),
                          pair_weights)
        want = relu2_rows(rows, w_in, w_out, sizes, pair_weights)
    assert got.shape == rows.shape and got.dtype == rows.dtype
    assert np.abs(np.asarray(got[:live] - want)).max() < 1e-5 * np.abs(
        np.asarray(want)).max()


def pass_operands(dtype, skewed, n=400, k=4, held=4, total=16, seed=0):
    """``n`` tokens' top-``k`` of ``total`` relu² experts, the first ``held``
    of them held; skewed, every token picks the held ones, so that the held
    pairs (1600) outrun a pass (``share_rows``: 1024) and take two."""
    d, f = 32, 24
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, d)).astype(dtype)
    router = jax.random.normal(ks[1], (d, total))
    bias = jnp.zeros(total).at[:held].set(100.0 if skewed else 0.0)
    w, experts = moe.route_top_k(x, router, k, 5.0, bias)
    w_in = (jax.random.normal(ks[2], (held, d, f)) * 0.3).astype(dtype)
    w_out = (jax.random.normal(ks[3], (held, f, d)) * 0.3).astype(dtype)
    return x, w, experts, w_in, w_out


@pytest.mark.parametrize("skewed", [False, True], ids=["one_pass",
                                                      "two_passes"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_shares_pass_through_the_kernel_is_the_ragged_dot_pass(
        dtype, skewed):
    """``_routed_share`` with the pass's products in the kernel (unit pair
    weights, the rows' weights applied on the way back as before) against
    XLA's two ``ragged_dot``s: the rows past a pass's held pairs are never
    visited and nothing of them comes back."""
    ops = pass_operands(dtype, skewed)
    n_held = int(np.sum(np.asarray(ops[2]) < 4))
    assert n_held == (1600 if skewed else n_held) and n_held > 0
    assert (n_held > moe.share_rows(1600, 4, 16)) is skewed
    kernel = functools.partial(moe._products_kernel, interpret=True,
                               tile_rows=16, block_rows=8)
    with jax.default_matmul_precision("highest"):
        got, want = (np.asarray(jax.jit(lambda *a, p=p: moe._routed_share(
            *a, first=0, total=16, act="relu2", products=p))(*ops),
            np.float32) for p in (kernel, moe._products_xla))
    scale = np.abs(want).max()
    assert np.isfinite(got).all() and scale > 0
    if dtype == jnp.float32:
        np.testing.assert_array_equal(got, want)
    else:
        # the XLA path rounds the product with w_in to bf16 before relu²,
        # the kernel squares it in float32: a few of bf16's 2**-8 steps
        assert np.abs(got - want).max() < 2 ** -6 * scale


# -- lowering -----------------------------------------------------------------

def test_a_tpu_lowering_counts_what_a_cpu_lowering_does_and_the_kernels():
    """One trace of a model whose mixer tiles (4 heads of 64 over 2 groups
    of state 128, chunks of 128) and whose share is 4 of 16 relu² experts
    in a latent: lowered for a TPU, the scan takes the kernel's
    ``shared_tile`` body; for the CPU the plain path; the expert layers are
    counted alike, and say what they hold."""
    cfg = config(mamba_num_heads=4, mamba_head_dim=64, ssm_state_size=128,
                 chunk_size=128, experts_held=[0, 4])
    shapes = jax.eval_shape(lambda: nemotron_h.init_params(cfg, 0))
    ids = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    traced = jax.jit(lambda p, x: nemotron_h.apply(cfg, p, x)).trace(
        shapes, ids)
    names = ("nnstpu_ssm_scan_lowerings_total", "nnstpu_ssm_scan_bodies_total",
             "nnstpu_moe_experts_total", "nnstpu_moe_lowerings_total")

    def lowered(platform):
        before = [counts(n) for n in names]
        text = traced.lower(lowering_platforms=(platform,)).as_text()
        return text, [{k: v - b.get(k, 0) for k, v in counts(n).items()
                       if v != b.get(k, 0)} for n, b in zip(names, before)]

    tpu, (scans, bodies, experts, paths) = lowered("tpu")
    assert ssm_scan.KERNEL_NAME in tpu
    assert scans == {("kernel",): 2} and bodies == {("shared_tile",): 2}
    assert experts == {("relu2", "latent"): 2}
    # experts of 16 x 12: no whole lane tiles, so XLA's products either way
    assert paths == {("grouped",): 2}
    assert grouped_experts.KERNEL_NAME not in tpu
    assert counts("nnstpu_moe_held_experts")[("16",)] == 4
    cpu, (scans, bodies, experts, paths) = lowered("cpu")
    assert ssm_scan.KERNEL_NAME not in cpu
    assert scans == {("plain",): 2} and bodies == {}
    assert experts == {("relu2", "latent"): 2}
    assert paths == {("grouped",): 2}


@pytest.mark.parametrize("act,d,f,held,total,k,path", [
    ("relu2", 1024, 2688, 128, 512, 22, "fused"),    # Nemotron's latent share
    ("swiglu", 6144, 2048, 16, 256, 8, "grouped"),   # GLM's share
], ids=["nemotron_relu2_share", "glm_swiglu_share"])
def test_a_shares_products_take_the_kernel_where_one_expert_fits_vmem(
        act, d, f, held, total, k, path):
    """A chunk of 8192 tokens' share, traced here and lowered for a TPU: a
    Nemotron expert (11 MB) runs in ``nns_grouped_experts`` and no
    ``ragged_dot`` is left; a GLM expert (75.5 MB) keeps XLA's two
    ``ragged_dot``s.  Either way the pass goes back by ``nns_combine_rows``,
    and the layer is counted under the path its products took."""
    n, wide = 8192, (2 if act == "swiglu" else 1)

    def struct(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    before = counts("nnstpu_moe_lowerings_total")
    text = jax.jit(lambda *a: moe.routed_experts(
        *a, first=0, total=total, act=act, latent=act == "relu2")).trace(
        struct(n, d), struct(n, k, dtype=jnp.float32),
        struct(n, k, dtype=jnp.int32), struct(held, d, wide * f),
        struct(held, f, d)).lower(lowering_platforms=("tpu",)).as_text()
    risen = {key: v - before.get(key, 0) for key, v in counts(
        "nnstpu_moe_lowerings_total").items() if v != before.get(key, 0)}
    assert risen == {(path,): 1}
    assert "nns_combine_rows" in text
    assert (grouped_experts.KERNEL_NAME in text) is (path == "fused")
    assert ("ragged_dot" in text) is (path == "grouped")


# -- the streaming path -------------------------------------------------------

def test_token_frames_through_a_launch_string_at_batch_n_equal_n_single(
        tmp_path):
    """``tensor_filter framework=jax`` opens the model from a checkpoint and
    the published config by its ``builder=`` name, like the other zoo models;
    a batch of N windows gives the N rows that N single windows give, and
    the reference's."""
    cfg = config(experts_held=[4, 4])
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    params = nemotron_h.init_params(cfg, 3, jnp.float32)
    save_state(params, str(tmp_path / "nemotron_h.npz"))
    ids = np.random.default_rng(4).integers(0, 96, (4, 20), dtype=np.int32)

    def run(frames, custom):
        got = []
        p = parse_launch(
            "datasrc name=s ! tensor_filter framework=jax name=f "
            f"model={tmp_path / 'nemotron_h.npz'} custom={custom} "
            "! tensor_sink name=out")
        p["s"].data = [f.copy() for f in frames]
        p["out"].connect("new-data",
                         lambda f: got.append(np.asarray(f.tensor(0))))
        p.run(timeout=120)
        return got

    custom = (f"builder=nemotron_h:build,config={tmp_path / 'config.json'},"
              "seq=20,dtype=float32,token_chunk=40")
    with jax.default_matmul_precision("highest"):
        singles = run(list(ids), custom)
        batched, = run([ids], custom + ",batch=4")
    assert batched.shape == (4, 96) and batched.dtype == np.float32
    assert [s.shape for s in singles] == [(96,)] * 4
    np.testing.assert_allclose(np.stack(singles), batched, atol=1e-5)
    want = nemotron_h_plain.forward(
        dict(cfg, seq=20), {}, jax.tree_util.tree_map(np.asarray, params), ids)
    assert np.abs(batched - want).max() < 2e-5 * np.abs(want).max()
