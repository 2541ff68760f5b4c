"""The ``glm_dsa`` token model against its plain reference
(``benchmark/references/glm_dsa_plain.py``, float32 at ``highest``, nothing
of the program imported), at tiny widths on the CPU with seeded weights and
a window longer than ``index_topk``, so that the selection cuts; the
selection itself against the reference's; a ``shared`` layer's use of it; a
share of the experts against the uncut layer; the biased choice; the two
kernels in interpret mode (compiled by Mosaic for a described v5e in
``tests/test_fused_attention.py``, the one file that loads the TPU's
compiler); and token frames through a launch-string pipeline."""

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

from benchmark.references import glm_dsa_plain  # noqa: E402
from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.models import glm_dsa  # noqa: E402
from nnstreamer_tpu.obs.metrics import REGISTRY  # noqa: E402
from nnstreamer_tpu.ops import sparse_attention as sa  # noqa: E402
from nnstreamer_tpu.parallel import moe  # noqa: E402
from nnstreamer_tpu.utils.checkpoint import save_state  # noqa: E402

PERIOD = ["full", "full", "full"] + ["shared", "shared", "shared", "full"] * 2


def config(layers=None, held=None, topk=8, **over):
    """The published keys at tiny widths: three leading dense layers with
    indexers of their own, then sparse layers that share in periods of
    four, as published."""
    cfg = {
        "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 5, "num_attention_heads": 4, "q_lora_rank": 24,
        "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
        "v_head_dim": 16, "index_n_heads": 2, "index_head_dim": 8,
        "index_topk": topk, "indexer_types": PERIOD[:10],
        "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 7,
        "moe_intermediate_size": 16, "n_routed_experts": 16,
        "n_shared_experts": 1, "num_experts_per_tok": 4,
        "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    }
    if layers:
        cfg["layers"] = layers
    if held:
        cfg["experts_held"] = held
    return dict(cfg, **over)


CASES = {
    "a_dense_layer_that_selects": config([2]),
    "a_sparse_layer_that_selects": config([6]),
    "one_that_selects_and_one_that_shares": config([6, 7]),
    "the_first_five_layers": config(),
    "the_cells_five_layers_and_its_share": config([2, 6, 7, 8, 9], [4, 4]),
}


def both(cfg, t, dtype, batch=3, seed=0, keep=None):
    params = glm_dsa.init_params(cfg, seed, dtype)
    model = glm_dsa.build(cfg, seq=t, batch=batch, dtype=dtype, params=params)
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, t), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.fn())(ids))
    host = jax.tree_util.tree_map(np.asarray, params)
    want = glm_dsa_plain.forward(dict(cfg, seq=t), {}, host, ids, keep)
    return got, want


@pytest.mark.parametrize("t", [24, 6], ids=["the_selection_cuts", "under_it"])
@pytest.mark.parametrize("case", CASES)
def test_float32_matches_the_plain_reference(case, t):
    got, want = both(CASES[case], t, jnp.float32)
    assert got.shape == want.shape == (3, 96) and got.dtype == np.float32
    assert np.abs(got - want).max() < 1e-4


@pytest.mark.parametrize("case", CASES)
def test_bfloat16_stays_near_the_plain_reference(case):
    """bf16 weights and activations against the float32 walk over the same
    bf16 weights: rounding, and at these sizes now and then a key of 8 or an
    expert of 4 that falls the other way on a near-tie, so the bound is
    loose."""
    got, want = both(CASES[case], 24, jnp.bfloat16)
    assert np.isfinite(got).all()
    assert np.linalg.norm(got - want) < 0.3 * np.linalg.norm(want)


# -- what it shares -----------------------------------------------------------

@pytest.mark.parametrize("name,home", [
    ("rms_norm", "models.laguna"), ("rotary_tables", "models.laguna"),
    ("quantize_weights", "models.laguna"), ("load_config", "models.laguna"),
    ("rotate", "ops.fused_attention"), ("matmul", "parallel.moe"),
    ("swiglu", "parallel.moe"), ("moe_top_k", "parallel.moe")])
def test_a_shared_piece_is_imported_not_copied(name, home):
    import importlib

    module = importlib.import_module("nnstreamer_tpu." + home)
    assert getattr(glm_dsa, name) is getattr(module, name)


def test_the_control_leaves_the_indexer_and_the_routed_experts_as_they_are():
    from nnstreamer_tpu.ops.quant import QuantizedWeight

    cfg = config([2, 6], [0, 4])
    control = glm_dsa.build_quantized(config=cfg, seq=24, seed=1)
    dense, sparse = control.params["layers"]
    for p in (dense, sparse):
        assert all(isinstance(p[k], QuantizedWeight)
                   for k in ("w_dq", "w_uq", "w_dkv", "w_uk", "w_uv", "wo"))
        assert not any(isinstance(a, QuantizedWeight)
                       for a in jax.tree_util.tree_leaves(
                           p["indexer"], is_leaf=lambda a: isinstance(
                               a, QuantizedWeight)))
    assert isinstance(dense["mlp"]["w_in"], QuantizedWeight)
    moe_ = sparse["moe"]
    assert not any(isinstance(moe_[k], QuantizedWeight)
                   for k in ("w_in", "w_out", "router", "bias"))
    assert isinstance(moe_["shared"]["w_out"], QuantizedWeight)
    assert not isinstance(control.params["embed"], QuantizedWeight)
    assert isinstance(control.params["head"], QuantizedWeight)


# -- the selection -----------------------------------------------------------

def first_selection(cfg, t, seed=0, batch=2):
    """The program's selection in the first layer built, over the embedded
    ids, beside the reference's."""
    params = glm_dsa.init_params(cfg, seed, jnp.float32)
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, t), dtype=np.int32)
    keep = []
    with jax.default_matmul_precision("highest"):
        glm_dsa_plain.forward(dict(cfg, seq=t), {}, jax.tree_util.tree_map(
            np.asarray, params), ids, keep)
        split = glm_dsa.split_rotary_pairs(cfg, params)
        p = split["layers"][0]
        x = split["embed"][ids]
        eps = cfg["rms_norm_eps"]
        h = glm_dsa.rms_norm(x, p["attn_norm"], eps)
        c_q = glm_dsa.rms_norm(h @ p["w_dq"], p["q_norm"], eps)
        got = glm_dsa.select(cfg, p["indexer"], h, c_q,
                             glm_dsa.rotary_tables(
                                 cfg["rope_parameters"],
                                 cfg["qk_rope_head_dim"], t))
    first = glm_dsa.layer_ids(cfg)[0]
    want = np.stack([m for i, _, m in keep if i == first])
    return np.asarray(got), want


@pytest.mark.parametrize("t,topk", [(24, 8), (40, 8), (24, 23), (6, 8)])
def test_the_selection_is_the_references(t, topk):
    """The set ``S_t`` itself, at float32: ``min(t + 1, index_topk)`` keys a
    query, none above the diagonal, the same ones (two indexer heads leave
    a quarter of the scores exactly 0, so equal scores are met and the
    earlier key is taken on both sides)."""
    got, want = first_selection(config([2], topk=topk), t)
    assert got.dtype == np.int8 and got.shape == want.shape == (2, t, t)
    assert np.array_equal(got != 0, want)
    assert np.array_equal(got.sum(-1)[0],
                          np.minimum(np.arange(t) + 1, min(topk, t)))
    assert not np.triu(got[0], 1).any()
    if topk < t:
        # it cuts, and not to the most recent keys alone
        recent = np.tril(np.ones((t, t), np.int8)) - np.tril(
            np.ones((t, t), np.int8), -topk)
        assert not np.array_equal(got[0], recent)


def test_equal_scores_take_the_earlier_key():
    q_i = jnp.zeros((1, 12, 2 * 8))
    k_i = jnp.ones((1, 12, 8))
    got = np.asarray(sa.select_keys(q_i, k_i, jnp.ones((1, 12, 2)), 5))[0]
    want = np.tril(np.ones((12, 12), np.int8))
    want[:, 5:] = 0
    assert np.array_equal(got, want)


def test_the_selection_walks_row_blocks(monkeypatch):
    """Two blocks of 16 rows give what one block of 32 gives."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q_i = jax.random.normal(ks[0], (2, 32, 2 * 8))
    k_i = jax.random.normal(ks[1], (2, 32, 8))
    w = jax.random.normal(ks[2], (2, 32, 2))
    whole = np.asarray(sa.select_keys(q_i, k_i, w, 6))
    monkeypatch.setattr(sa, "SELECT_ROWS", 16)
    assert sa.row_blocks(32, sa.SELECT_ROWS) == 2
    blocks = np.asarray(jax.jit(
        lambda *a: sa.select_keys(*a, 6))(q_i, k_i, w))
    assert np.array_equal(whole, blocks)


def counted(name, label):
    metric = REGISTRY.get(name)
    child = dict(metric.children()).get((label,)) if metric else None
    return child.value if child else 0


def test_a_shared_layer_attends_under_the_full_layers_selection():
    """Layers 6 and 7 against the reference, which hands 7 what 6 selected;
    had 7 an indexer of its own the logits would be others; the program
    counts one layer of each role and two attention calls."""
    cfg = config([6, 7])
    roles = "nnstpu_indexer_lowerings_total"
    paths = "nnstpu_attention_lowerings_total"
    before = (counted(roles, "full"), counted(roles, "shared"),
              counted(paths, "latent_sparse_plain"))
    got, want = both(cfg, 24, jnp.float32)
    assert np.abs(got - want).max() < 1e-4
    assert (counted(roles, "full"), counted(roles, "shared"),
            counted(paths, "latent_sparse_plain")) == (
        before[0] + 1, before[1] + 1, before[2] + 2)
    own = dict(cfg, indexer_types=["full"] * 10)
    params = glm_dsa.init_params(own, 0, jnp.float32)
    ids = np.random.default_rng(0).integers(0, 96, (3, 24), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        other = glm_dsa_plain.forward(
            dict(own, seq=24), {},
            jax.tree_util.tree_map(np.asarray, params), ids)
    assert np.abs(other - want).max() > 1e-2
    with pytest.raises(ValueError, match="shares a selection"):
        glm_dsa.build(config([7]), seq=24, dtype=jnp.float32).fn()(ids[0])


# -- the attention ------------------------------------------------------------

def attention_operands(t, heads=2, dn=64, dr=64, dv=128, topk=48, b=2,
                       dtype=jnp.float32):
    """A head's ``dn | dr`` as the kernel pairs them: 64 rotary dims that
    fill the half lane tile the unrotated ones leave."""
    ks = jax.random.split(jax.random.PRNGKey(t), 7)
    q = jax.random.normal(ks[0], (b, t, heads * (dn + dr)), dtype)
    k_n = jax.random.normal(ks[1], (b, t, heads * dn), dtype)
    k_r = jax.random.normal(ks[2], (b, t, dr), dtype)
    v = jax.random.normal(ks[3], (b, t, heads * dv), dtype)
    mask = sa.select_keys(jax.random.normal(ks[4], (b, t, 2 * 128)),
                          jax.random.normal(ks[5], (b, t, 128)),
                          jax.random.normal(ks[6], (b, t, 2)), topk)
    return q, k_n, k_r, v, mask


def written_out(q, k_n, k_r, v, mask, heads):
    """The definition, a head at a time."""
    b, t, _ = q.shape
    qh = q.reshape(b, t, heads, -1)
    dn = k_n.shape[-1] // heads
    out = []
    for h in range(heads):
        s = (jnp.einsum("btd,bsd->bts", qh[:, :, h, :dn],
                        k_n.reshape(b, t, heads, dn)[:, :, h])
             + jnp.einsum("btd,bsd->bts", qh[:, :, h, dn:], k_r))
        s = jnp.where(mask != 0, s / qh.shape[-1] ** 0.5, -jnp.inf)
        out.append(jnp.einsum("bts,bsd->btd", jax.nn.softmax(s, -1),
                              v.reshape(b, t, heads, -1)[:, :, h]))
    return jnp.concatenate(out, -1)


BLOCKS = pytest.mark.parametrize(
    "blocks", [(128, 128), (128, 64), (256, 128)],
    ids=lambda b: f"{b[0]}x{b[1]}")


@BLOCKS
def test_the_attention_kernel_in_interpret_mode(blocks):
    q, k_n, k_r, v, mask = attention_operands(256)
    with jax.default_matmul_precision("highest"):
        got = sa.sparse_attention_kernel(
            q, k_n, k_r, v, mask, 2, block_q=blocks[0], block_k=blocks[1],
            interpret=True)
        want = written_out(q, k_n, k_r, v, mask, 2)
        plain = sa.latent_sparse_attention(q, k_n, k_r, v, mask, 2)
    assert got.shape == want.shape == (2, 256, 256)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert np.abs(np.asarray(plain - want)).max() < 1e-5


def rope_tables(t, rot):
    return glm_dsa.rotary_tables(config()["rope_parameters"], rot, t)


# heads, a head's unrotated dims, the value's, the dims the tables rotate
HEADS = pytest.mark.parametrize("heads,dn,dv,rot", [
    (2, 192, 256, 64),   # the published head: 192 | 64, a pair a grid step
    (4, 192, 128, 64),   # two pairs
    (4, 64, 128, 64),    # a head of one lane tile: the mixed tile alone
    (2, 192, 128, 32),   # tables that rotate half of the rotary dims
], ids=["2x192_64", "4x192_64", "4x64_64", "2x192_rot32"])


@BLOCKS
@HEADS
def test_the_kernel_rotates_q_as_rotate_does_at_float32(heads, dn, dv, rot,
                                                        blocks):
    """The projections as the products write them, and the tables: the
    kernel's rotation of q on its own blocks and its keys ``[k_n | k_r]``
    formed in VMEM against ``rotate()`` and the definition."""
    q, k_n, k_r, v, mask = attention_operands(256, heads, dn, 64, dv)
    cos, sin = rope_tables(256, rot)
    with jax.default_matmul_precision("highest"):
        got = sa.sparse_attention_kernel(
            q, k_n, k_r, v, mask, heads, (cos, sin), block_q=blocks[0],
            block_k=blocks[1], interpret=True)
        k_r = glm_dsa.rotate(k_r, cos, sin, 1)
        want = written_out(glm_dsa.rotate(q, cos, sin, heads, dn), k_n, k_r,
                           v, mask, heads)
    assert got.shape == want.shape == (2, 256, heads * dv)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    # and it does rotate: the unrotated q gives another answer
    assert np.abs(np.asarray(written_out(q, k_n, k_r, v, mask, heads)
                             - want)).max() > 1e-2


@BLOCKS
@HEADS
def test_the_kernel_rotates_q_to_rotates_bits_at_bfloat16(heads, dn, dv, rot,
                                                          blocks):
    """bf16: the rotation in the kernel rounds where ``rotate()`` rounds,
    so the kernel with tables equals the kernel handed a rotated q to the
    last bit, but where this host's compiler contracts ``x * C + partner *
    S`` to a fused multiply-add in one program and not in the other (a
    float32 step, which now and then rounds to another bf16); the plain
    lowering (float32 scores scaled after the product, a whole-row softmax)
    lies within a bf16 step of the output."""
    q, k_n, k_r, v, mask = attention_operands(256, heads, dn, 64, dv,
                                              dtype=jnp.bfloat16)
    cos, sin = rope_tables(256, rot)
    kernel = functools.partial(sa.sparse_attention_kernel, block_q=blocks[0],
                               block_k=blocks[1], interpret=True)
    inside = kernel(q, k_n, k_r, v, mask, heads, (cos, sin))
    outside = kernel(glm_dsa.rotate(q, cos, sin, heads, dn), k_n,
                     glm_dsa.rotate(k_r, cos, sin, 1), v, mask, heads)
    plain = sa.latent_sparse_attention(q, k_n, k_r, v, mask, heads,
                                       rotary=(cos, sin))
    assert inside.dtype == plain.dtype == jnp.bfloat16
    inside, outside, plain = (np.asarray(a, np.float32)
                              for a in (inside, outside, plain))
    step = 2 ** -7 * np.abs(plain).max()
    assert (inside != outside).mean() < 1e-3
    assert np.abs(inside - outside).max() <= step
    assert np.abs(inside - plain).max() <= step


@pytest.mark.parametrize("heads,dn,dr,rot,why", [
    (2, 192, 64, 64, None),
    (64, 192, 64, 64, None),
    (2, 192, 64, 32, None),
    (3, 192, 64, 64, "an odd number of heads pairs off no last one"),
    (2, 96, 32, 32, "a pair's 192 unrotated columns are no whole lane tiles"),
    (2, 128, 128, 128, "no half tile stands free beside whole ones"),
], ids=["the_pair", "the_published_64", "rot_32", "odd_heads", "dn_96",
        "dn_128"])
def test_what_the_kernel_tiles(heads, dn, dr, rot, why):
    t = 2 * sa.BLOCK_Q
    shapes = ((1, t, heads * (dn + dr)), (1, t, heads * dn), (1, t, dr),
              (1, t, heads * 128))
    assert sa.sparse_tiles(*shapes, jnp.bfloat16, heads, (t, rot // 2)) \
        == (why is None), why
    assert not sa.sparse_tiles(*shapes, jnp.int8, heads, (t, rot // 2))
    # tables of other positions, or that rotate more than the rotary dims
    assert not sa.sparse_tiles(*shapes, jnp.bfloat16, heads, (t // 2, 32))
    assert not sa.sparse_tiles(*shapes, jnp.bfloat16, heads, (t, dr))
    # and where it does not, a TPU's program rotates outside and walks
    q, k_n, k_r, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes)
    mask = jax.ShapeDtypeStruct((1, t, t), jnp.int8)
    table = jax.ShapeDtypeStruct((t, rot // 2), jnp.float32)
    before = rotary_counts()
    text = jax.jit(lambda *a: sa.latent_sparse_attention(
        *a[:5], heads, rotary=a[5:])).trace(
        q, k_n, k_r, v, mask, table, table).lower(
        lowering_platforms=("tpu",)).as_text()
    assert (sa.KERNEL_NAME in text) == (why is None)
    kernel = int(why is None)
    assert rotary_counts() == (before[0] + kernel, before[1] + 1 - kernel,
                               before[2] + kernel, before[3] + 1 - kernel)


@pytest.mark.parametrize("b,t,heads,top_k,rows,bk", [
    (2, 256, 2, 32, 64, 128),    # four row blocks, the later ones see more
    (1, 512, 3, 100, 128, 128),  # key blocks than the earlier
    (1, 256, 2, 200, 64, 256),   # most rows keep every causal key
], ids=["256_top32", "512_top100", "256_top200"])
def test_the_selection_kernel_in_interpret_mode(b, t, heads, top_k, rows, bk):
    """Counting a bit at a time selects what the sort selects, key for
    key."""
    ks = jax.random.split(jax.random.PRNGKey(t + top_k), 3)
    q_i = jax.random.normal(ks[0], (b, t, heads * 128))
    k_i = jax.random.normal(ks[1], (b, t, 128))
    w = jax.random.normal(ks[2], (b, t, heads))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(sa.select_keys(q_i, k_i, w, top_k))
        got = np.asarray(sa.index_select(q_i, k_i, w, top_k, rows=rows,
                                         block_k=bk, interpret=True))
    assert got.dtype == np.int8 and np.array_equal(got, want)


def test_the_selection_kernel_takes_the_earlier_of_equal_scores():
    """Every score equal (and 0.0 beside -0.0): the earliest keys."""
    q_i = jnp.zeros((1, 256, 2 * 128))
    k_i = jnp.ones((1, 256, 128))
    w = jnp.ones((1, 256, 2)).at[:, ::2].set(-1.0)
    want = np.tril(np.ones((256, 256), np.int8))
    want[:, 40:] = 0
    got = np.asarray(sa.index_select(q_i, k_i, w, 40, rows=64, block_k=128,
                                     interpret=True))[0]
    assert np.array_equal(got, want)
    assert np.array_equal(np.asarray(sa.select_keys(q_i, k_i, w, 40))[0], want)


def test_the_plain_walk_takes_row_blocks(monkeypatch):
    q, k_n, k_r, v, mask = attention_operands(64, topk=20)
    with jax.default_matmul_precision("highest"):
        whole = sa._plain(q, k_n, k_r, v, mask, n_heads=2)
        monkeypatch.setattr(sa, "SELECT_ROWS", 16)
        blocks = sa._plain(q, k_n, k_r, v, mask, n_heads=2)
    assert np.abs(np.asarray(whole - blocks)).max() < 1e-5


def rotary_counts():
    return tuple(counted(name, label) for name, label in (
        ("nnstpu_attention_rotary_total", "kernel"),
        ("nnstpu_attention_rotary_total", "outside"),
        ("nnstpu_attention_lowerings_total", "latent_sparse"),
        ("nnstpu_attention_lowerings_total", "latent_sparse_plain")))


def test_one_trace_lowers_the_kernels_for_a_tpu_and_the_walks_here():
    """Which lowering a call gets is the lowering rule's choice: the same
    trace holds both kernels for a TPU and neither for this host.  A call
    without tables counts no rotation; the model's layers each count one,
    in the kernel for a TPU and outside here, and a TPU's program holds no
    ``[B, T, H, dn + dr]`` array of q or of keys for XLA to re-tile."""
    q, k_n, k_r, v, mask = attention_operands(1024, topk=100)
    attend = jax.jit(lambda *a: sa.latent_sparse_attention(*a, 2)).trace(
        q, k_n, k_r, v, mask)
    before = rotary_counts()
    assert sa.KERNEL_NAME in attend.lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in attend.lower().as_text()
    assert rotary_counts() == (before[0], before[1], before[2] + 1,
                               before[3] + 1)
    cfg = config([2, 6, 7], qk_nope_head_dim=64, qk_rope_head_dim=64,
                 v_head_dim=128, index_head_dim=64)
    model = glm_dsa.build(cfg, seq=1024, batch=2, seed=1)
    program = jax.jit(model.fn()).trace(
        jax.ShapeDtypeStruct((2, 1024), jnp.int32))
    by_head = "tensor<2x1024x4x128x"  # q or the keys, a head a row
    before = rotary_counts()
    on_tpu = program.lower(lowering_platforms=("tpu",)).as_text()
    assert on_tpu.count(sa.KERNEL_NAME) >= 3 and by_head not in on_tpu
    assert rotary_counts() == (before[0] + 3, before[1], before[2] + 3,
                               before[3])
    here = program.lower().as_text()
    assert "tpu_custom_call" not in here and by_head in here
    assert rotary_counts() == (before[0] + 3, before[1] + 3, before[2] + 3,
                               before[3] + 3)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    select = jax.jit(lambda *a: sa.select_keys(*a, 100)).trace(
        jax.random.normal(ks[0], (1, 1024, 256)),
        jax.random.normal(ks[1], (1, 1024, 128)),
        jax.random.normal(ks[2], (1, 1024, 2)))
    assert sa.INDEX_KERNEL_NAME in select.lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in select.lower().as_text()


# -- the experts: a share, the bias ---------------------------------------------

def moe_params(key, d=32, f=16, e=16, router=None):
    ks = jax.random.split(key, 6)
    return {"router": (jax.random.normal(ks[0], (d, e))
                       if router is None else router),
            "bias": jax.random.normal(ks[5], (e,)) * 0.3,
            "w_in": jax.random.normal(ks[1], (e, d, 2 * f)) * 0.2,
            "w_out": jax.random.normal(ks[2], (e, f, d)) * 0.2,
            "shared": {"w_in": jax.random.normal(ks[3], (d, 2 * f)) * 0.2,
                       "w_out": jax.random.normal(ks[4], (f, d)) * 0.2}}


def share_of(p, first, count):
    return dict(p, w_in=p["w_in"][first:first + count],
                w_out=p["w_out"][first:first + count])


@pytest.mark.parametrize("chunk", [None, 10], ids=["whole", "in_chunks"])
@pytest.mark.parametrize("shares", [4, 16, 2])
def test_the_shares_add_up_to_the_uncut_layer(shares, chunk):
    """What ``shares`` chips give, each told which of the 16 experts it
    holds, summed with the shared expert counted once, is the layer that
    holds them all (Laguna's path: the whole range)."""
    p = moe_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (50, 32))
    held = 16 // shares
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda p, x: moe.moe_top_k(p, x, 4, 2.5, chunk))(p, x)
        shared = moe.swiglu(x, p["shared"]["w_in"], p["shared"]["w_out"])
        parts = [jax.jit(lambda p, x, first=first: moe.moe_top_k(
            p, x, 4, 2.5, chunk, first))(share_of(p, first, held), x)
            for first in range(0, 16, held)]
    total = sum(parts) - (shares - 1) * shared
    assert np.abs(np.asarray(total - whole)).max() < 1e-4
    # and a share is not the whole: most of a token's picks lie elsewhere
    assert np.abs(np.asarray(parts[0] - whole)).max() > 1e-2


def test_a_routing_in_which_every_pick_is_held_drops_nothing():
    """Every token's four picks fall on the four experts held: 200 pairs,
    four times what an even routing sends here, and the result is the
    masked dense sum over those experts."""
    router = jnp.zeros((32, 16)).at[:, 4:8].set(
        jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (32, 4))) + 1)
    p = dict(moe_params(jax.random.PRNGKey(0), router=router),
             bias=jnp.zeros(16))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (50, 32)))
    assert moe.share_rows(200, 4, 16) == 200  # a pass is never over the pairs
    assert moe.share_rows(65536, 16, 256) == 8192  # twice an even routing's
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: moe.moe_top_k(p, x, 4, 2.5, None, 4))(
            share_of(p, 4, 4), x)
        w, experts = moe.route_top_k(x, router, 4, 2.5)
        assert set(np.asarray(experts).ravel()) == {4, 5, 6, 7}
        want = moe.swiglu(x, p["shared"]["w_in"], p["shared"]["w_out"])
        for j in range(4):
            for e in range(4, 8):
                want += ((w[:, j] * (experts[:, j] == e))[:, None]
                         * moe.swiglu(x, p["w_in"][e], p["w_out"][e]))
    assert np.abs(np.asarray(got - want)).max() < 1e-4


def test_the_walk_takes_as_many_passes_as_the_routing_needs(monkeypatch):
    """The same with a pass of 64 rows: four passes over 200 held pairs."""
    monkeypatch.setattr(moe, "share_rows", lambda pairs, held, total: 64)
    router = jnp.zeros((32, 16)).at[:, 4:8].set(1.0)
    p = dict(moe_params(jax.random.PRNGKey(0), router=router),
             bias=jnp.zeros(16))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (50, 32)))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: moe.moe_top_k(p, x, 4, 1.0, None, 4))(
            share_of(p, 4, 4), x)
        want = moe.swiglu(x, p["shared"]["w_in"], p["shared"]["w_out"])
        for e in range(4, 8):  # equal scores: a quarter each
            want += 0.25 * moe.swiglu(x, p["w_in"][e], p["w_out"][e])
    assert np.abs(np.asarray(got - want)).max() < 1e-4


# -- the way back of a share's pass -------------------------------------------

def gather_share(x, weights, experts, w_in, w_out, first, total):
    """The way back as it was before it went by the pass's own rows (PR 38's
    ``_routed_share``): a gather over every routed pair, most of them reading
    a zero row, and a token's ``k`` rows weighed and summed.  Kept here as
    the plain reference of the way back."""
    n, k = experts.shape
    held = w_in.shape[0]
    pairs = n * k
    rows = moe.share_rows(pairs, held, total)
    local = experts.reshape(-1).astype(jnp.int32) - first
    by_expert, order = jax.lax.sort(
        (jnp.where((local >= 0) & (local < held), local, held),
         jnp.arange(pairs, dtype=jnp.int32)), num_keys=1, is_stable=True)
    starts = jnp.searchsorted(
        by_expert, jnp.arange(held + 1, dtype=jnp.int32)).astype(jnp.int32)
    n_held = starts[-1]
    back = jnp.argsort(order)
    order = jnp.pad(order, (0, rows))
    pair_weights = weights.astype(x.dtype)
    out = jnp.zeros((n, x.shape[-1]), jnp.float32)
    for c in range(int(-(-n_held // rows))):
        r0 = c * rows
        idx = order[r0:r0 + rows]
        sizes = jnp.diff(jnp.clip(starts, r0, r0 + rows))
        gate, up = jnp.split(REAL_RAGGED_DOT(x[idx // k], w_in, sizes), 2,
                             axis=-1)
        y = REAL_RAGGED_DOT((jax.nn.silu(gate) * up).astype(x.dtype), w_out,
                            sizes)
        y = jnp.concatenate([y, jnp.zeros((1, y.shape[-1]), y.dtype)])
        at = back - r0
        here = (at >= 0) & (at < rows) & (back < n_held)
        y = y[jnp.where(here, at, rows)].reshape(n, k, -1)
        out = out + jnp.einsum("nkd,nk->nd", y, pair_weights,
                               preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


REAL_RAGGED_DOT = jax.lax.ragged_dot


def garbage_past_the_groups(lhs, rhs, sizes):
    """``ragged_dot`` whose rows past the groups' are not zeros."""
    out = REAL_RAGGED_DOT(lhs, rhs, sizes)
    past = jnp.arange(out.shape[0]) >= sizes.sum()
    return jnp.where(past[:, None], jnp.nan, out)


def picks(held_picks):
    """``[64, 4]`` distinct experts of 16 of which 0-3 are held: token ``t``
    picks ``held_picks(t)`` (a tuple of held experts) and fills up with
    experts held elsewhere."""
    rows = []
    for t in range(64):
        mine = tuple(held_picks(t))
        rows.append(mine + tuple(range(4 + t % 8, 4 + t % 8 + 4 - len(mine))))
    return jnp.asarray(rows, jnp.int32)


def group_limited_picks():
    """A.X-K1's choice in small: 16 experts in 4 groups, 2 kept, top-4; the
    share holds experts 0, 1 (half of group 0), and group 0 is closed to
    every odd token, which so sends nothing here."""
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    x = jax.random.normal(ks[0], (64, 32)) * 0.1
    x = x.at[:, 0].set(jnp.where(jnp.arange(64) % 2 == 0, 6.0, -6.0))
    router = (jax.random.normal(ks[1], (32, 16)) * 0.3).at[0].set(
        jnp.where(jnp.arange(16) < 4, 1.0, 0.0))
    _, experts = moe.route_top_k(x, router, 4, 1.0, None, 4, 2)
    sends = np.asarray((experts < 2).any(axis=1))
    assert sends[::2].all() and not sends[1::2].any()
    return experts


# name: (the picks, rows a pass, experts held, passes, ragged_dot)
WAYS_BACK = {
    "tokens_with_no_one_and_three_held_picks": (
        lambda: picks(lambda t: ((), (t % 4,), (0, 2, 3))[t // 22]),
        None, 4, 1, None),
    "every_pick_held_four_passes": (
        lambda: picks(lambda t: (0, 1, 2, 3)), 64, 4, 4, None),
    "a_tokens_pairs_straddle_two_passes": (
        lambda: picks(lambda t: (0, 3)), 32, 4, 4, None),
    "garbage_past_the_held_rows_of_the_last_pass": (
        lambda: picks(lambda t: (t % 4,) if t % 3 else ()), 32, 4, 2,
        garbage_past_the_groups),
    "group_limited_half_the_tokens_send_nothing": (
        group_limited_picks, None, 2, 1, None),
}


@pytest.mark.parametrize("way", ["plain", "kernel"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(WAYS_BACK))
def test_a_passes_results_go_back_by_its_own_rows(case, dtype, way,
                                                  monkeypatch):
    """The scatter-add and the kernel (interpret mode) against the gather
    over every routed pair: float32 to 1e-5, bf16 to one bf16 step."""
    from nnstreamer_tpu.ops.combine_rows import combine_rows

    make, rows, held, passes, ragged = WAYS_BACK[case]
    experts = make()
    if rows is not None:
        monkeypatch.setattr(moe, "share_rows", lambda *_: rows)
    rows = moe.share_rows(experts.size, held, 16)
    n_held = int((experts < held).sum())
    assert -(-n_held // rows) == passes
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(ks[0], (64, 32)).astype(dtype)
    weights = jax.random.uniform(ks[1], experts.shape, minval=0.1)
    w_in = (jax.random.normal(ks[2], (held, 32, 32)) * 0.2).astype(dtype)
    w_out = (jax.random.normal(ks[3], (held, 16, 32)) * 0.2).astype(dtype)
    share = {} if way == "plain" else {"combine": functools.partial(
        combine_rows, interpret=True, token_block=16, row_tile=16)}
    with jax.default_matmul_precision("highest"):
        want = gather_share(x, weights, experts, w_in, w_out, 0, 16)
        if ragged is not None:
            monkeypatch.setattr(jax.lax, "ragged_dot", ragged)
        got = jax.jit(functools.partial(
            moe._routed_share, first=0, total=16, **share))(
                x, weights, experts, w_in, w_out)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.abs(want).max() > 0.1 and np.isfinite(got).all()
    if dtype == jnp.float32:
        assert np.abs(got - want).max() < 1e-5
    else:
        assert (np.abs(got - want)
                <= 2.0 ** -7 * np.maximum(np.abs(got), np.abs(want))).all()
    # a token none of whose picks is held gets nothing from here
    assert not got[~np.asarray((experts < held).any(axis=1))].any()


def test_a_shares_derivatives_are_the_plain_way_backs(monkeypatch):
    """``routed_experts_p``'s jvp runs the share through XLA, the
    scatter-add its way back: a loss's gradients in ``x`` and the weights
    are the gather form's, over three passes."""
    monkeypatch.setattr(moe, "share_rows", lambda *_: 32)
    experts = picks(lambda t: (t % 4,) if t % 3 else (0, 2))
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    x = jax.random.normal(ks[0], (64, 32))
    weights = jax.random.uniform(ks[1], experts.shape, minval=0.1)
    w_in = jax.random.normal(ks[2], (4, 32, 32)) * 0.2
    w_out = jax.random.normal(ks[3], (4, 16, 32)) * 0.2

    def loss(share):
        return lambda x, w: (share(x, w, experts, w_in, w_out) ** 2).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(functools.partial(
            moe.routed_experts, first=0, total=16)), argnums=(0, 1))(
                x, weights)
        want = jax.grad(loss(functools.partial(
            gather_share, first=0, total=16)), argnums=(0, 1))(x, weights)
    for a, b in zip(got, want):
        assert np.abs(np.asarray(b)).max() > 0.1
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("n,rows,d,dtype,why", [
    (8192, 8192, 7168, jnp.bfloat16, None),
    (8192, 8192, 6144, jnp.bfloat16, None),
    (512, 1024, 128, jnp.float32, None),
    (8192, 8192, 7168, jnp.float32, None),
    (8192, 8192, 7168, jnp.int8, "neither bf16 nor float32"),
    (8192, 8192, 7200, jnp.bfloat16, "d in no whole lane tiles"),
    (50, 256, 128, jnp.float32, "the tokens in no whole blocks"),
    (512, 200, 128, jnp.float32, "a pass in no whole row tiles"),
    (8192, 8192, 32768, jnp.float32, "a visit's blocks over the budget"),
], ids=["axk1", "glm", "small", "float32", "int8", "odd_d", "odd_tokens",
        "odd_rows", "wide"])
def test_what_the_way_backs_kernel_tiles(n, rows, d, dtype, why):
    from nnstreamer_tpu.ops import combine_rows

    assert combine_rows.tiles((n, d), rows, dtype) == (why is None), why


def arrays_of(jaxpr):
    """The shape of every array a jaxpr makes, those of its inner ones too."""
    for eqn in jaxpr.eqns:
        yield from (v.aval.shape for v in eqn.outvars
                    if hasattr(v.aval, "shape"))
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from arrays_of(inner)


@pytest.mark.parametrize("way", ["plain", "kernel"])
def test_a_share_makes_no_array_of_every_routed_pairs_rows(way):
    """512 tokens, top-8, 2 of 16 experts held: 4096 routed pairs, a pass of
    1024 rows.  Neither the trace nor either lowering's text holds a
    ``[pairs, d]`` or ``[tokens, k, d]`` array, and the TPU's program sums a
    pass's rows in the kernel."""
    n, k, d = 512, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (n, d))
    weights, experts = moe.route_top_k(x, jax.random.normal(ks[1], (d, 16)),
                                       k)
    w_in = jax.random.normal(ks[2], (2, d, 64))
    w_out = jax.random.normal(ks[3], (2, 32, d))
    if way == "plain":
        fn = functools.partial(moe._routed_share, first=4, total=16)
    else:
        def fn(*a):
            return moe.routed_experts(*a, first=4, total=16)
    traced = jax.jit(fn).trace(x, weights, experts, w_in, w_out)
    shapes = set(arrays_of(traced.jaxpr.jaxpr))
    if way == "plain":  # the walk sees into the loop: a pass's rows
        assert (moe.share_rows(n * k, 2, 16), d) in shapes
    assert not [s for s in shapes if int(np.prod(s)) >= n * k * d]
    name = "nnstpu_moe_share_combine_total"
    before = counted(name, "kernel"), counted(name, "plain")
    cpu = traced.lower().as_text()
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    for text in (cpu, tpu):
        assert f"{n * k}x{d}x" not in text and f"{n}x{k}x{d}x" not in text
    assert "nns_combine_rows" not in cpu
    assert ("nns_combine_rows" in tpu) == (way == "kernel")
    if way == "kernel":  # the primitive's rule chose, and counted, each
        assert (counted(name, "kernel"), counted(name, "plain")) == (
            before[0] + 1, before[1] + 1)


def test_the_bias_steers_the_choice_and_stays_out_of_the_weights():
    p = moe_params(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (200, 32))
    w, experts = moe.route_top_k(x, p["router"], 4, 2.5, p["bias"])
    plain_w, plain = moe.route_top_k(x, p["router"], 4, 2.5)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, p["router"], precision=jax.lax.Precision.HIGHEST)))
    biased = scores + np.asarray(p["bias"])
    want = np.argsort(-biased, axis=-1, kind="stable")[:, :4]
    assert np.array_equal(np.asarray(experts), want)
    assert not np.array_equal(np.sort(np.asarray(experts), -1),
                              np.sort(np.asarray(plain), -1))
    top = np.take_along_axis(scores, want, axis=-1)
    np.testing.assert_allclose(np.asarray(w),
                               top / top.sum(-1, keepdims=True) * 2.5,
                               rtol=1e-6)
    # no bias: the program Laguna has
    same_w, same = moe.route_top_k(x, p["router"], 4, 2.5, None)
    assert np.array_equal(np.asarray(same), np.asarray(plain))
    assert np.array_equal(np.asarray(same_w), np.asarray(plain_w))


def test_a_share_is_counted_and_says_what_it_holds():
    name = "nnstpu_moe_lowerings_total"
    p = moe_params(jax.random.PRNGKey(0))
    before = counted(name, "grouped"), counted(name, "fused")
    traced = jax.jit(lambda x: moe.moe_top_k(
        share_of(p, 8, 4), x, 4, first=8)).trace(jnp.ones((8, 32)))
    traced.lower()
    traced.lower(lowering_platforms=("tpu",))
    assert counted(name, "grouped") == before[0] + 2
    assert counted(name, "fused") == before[1]
    assert counted("nnstpu_moe_held_experts", "16") == 4
    with pytest.raises(ValueError, match="experts 14...18 of 16"):
        moe.moe_top_k(share_of(p, 12, 4), jnp.ones((8, 32)), 4, first=14)


# -- the streaming path -------------------------------------------------------

def test_the_cut_reads_the_per_layer_lists_by_published_index():
    cfg = config([2, 6, 7, 8, 9], [4, 4])
    params = glm_dsa.init_params(cfg, 0, jnp.float32)
    kinds = [("indexer" in p, "moe" in p) for p in params["layers"]]
    assert kinds == [(True, False), (True, True)] + [(False, True)] * 3
    assert params["layers"][1]["moe"]["w_in"].shape == (4, 32, 32)
    assert params["layers"][1]["moe"]["router"].shape == (32, 16)
    assert params["layers"][0]["w_uq"].shape == (24, 4 * 16)
    assert params["layers"][0]["indexer"]["wq"].shape == (24, 2 * 8)


def test_token_frames_through_a_launch_string_at_batch_n_equal_n_single(tmp_path):
    """``tensor_filter framework=jax`` opens the model from a checkpoint and
    the published config by the builder's name, like the other zoo models;
    a batch of N windows gives the N rows that N single windows give."""
    cfg = config([2, 6, 7, 8, 9], [4, 4])
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    params = glm_dsa.init_params(cfg, 3, jnp.float32)
    save_state(params, str(tmp_path / "glm.npz"))
    ids = np.random.default_rng(4).integers(0, 96, (4, 24), dtype=np.int32)

    def run(frames, custom):
        got = []
        p = parse_launch(
            "datasrc name=s ! tensor_filter framework=jax name=f "
            f"model={tmp_path / 'glm.npz'} custom={custom} "
            "! tensor_sink name=out")
        p["s"].data = [f.copy() for f in frames]
        p["out"].connect("new-data",
                         lambda f: got.append(np.asarray(f.tensor(0))))
        p.run(timeout=120)
        return got

    custom = (f"builder=glm_dsa:build,config={tmp_path / 'config.json'},"
              "seq=24,dtype=float32")
    with jax.default_matmul_precision("highest"):
        singles = run(list(ids), custom)
        batched, = run([ids], custom + ",batch=4")
    assert batched.shape == (4, 96) and batched.dtype == np.float32
    assert [s.shape for s in singles] == [(96,)] * 4
    np.testing.assert_allclose(np.stack(singles), batched, atol=1e-5)
    want = glm_dsa_plain.forward(dict(cfg, seq=24), {},
                                 jax.tree_util.tree_map(np.asarray, params),
                                 ids)
    assert np.abs(batched - want).max() < 1e-4
