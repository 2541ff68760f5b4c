"""The ``falcon_h1`` hybrid token model and its chunked state-space scan
(``ops/ssm_scan.py``) at tiny widths on the CPU: the scan's XLA lowering and
its Pallas kernel in interpret mode against the sequential recurrence of its
definition, the model against its plain reference
(``benchmark/references/falcon_h1_plain.py``, float32 at ``highest``,
nothing of the program imported), every μP multiplier on its branch, which
lowering a trace gets, and token frames through a launch-string pipeline.
The kernel compiled by Mosaic for a described v5e is in
``tests/test_fused_attention.py``."""

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

from benchmark.references import falcon_h1_plain  # noqa: E402
from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.models import falcon_h1  # noqa: E402
from nnstreamer_tpu.obs.metrics import REGISTRY  # noqa: E402
from nnstreamer_tpu.ops import ssm_scan  # noqa: E402
from nnstreamer_tpu.utils.checkpoint import save_state  # noqa: E402

COUNTER = "nnstpu_ssm_scan_lowerings_total"
MULTIPLIERS = ("attention_in_multiplier", "attention_out_multiplier",
               "embedding_multiplier", "key_multiplier", "lm_head_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier")


def config(**over):
    """The published keys at tiny widths (the published multipliers)."""
    cfg = {
        "vocab_size": 96, "hidden_size": 32, "intermediate_size": 48,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "rope_theta": 1e11,
        "rms_norm_eps": 1e-5, "mamba_d_ssm": 32, "mamba_n_heads": 4,
        "mamba_d_head": 8, "mamba_n_groups": 2, "mamba_d_state": 8,
        "mamba_d_conv": 4, "mamba_conv_bias": True, "mamba_chunk_size": 8,
        "mamba_expand": 2, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375,
        "embedding_multiplier": 5.656854249492381,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
    }
    return dict(cfg, **over)


def lowerings():
    metric = REGISTRY.get(COUNTER)
    if metric is None:
        return {}
    return {key[0]: int(child.value) for key, child in metric.children()}


# -- the scan -----------------------------------------------------------------

def recurrence(x, dt, a, b, c, d, groups):
    """``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ``, ``y_t = S_t C_t + D
    x_t`` token by token, at float32 (the reference's own)."""
    bt, t, hp = x.shape
    heads = dt.shape[-1]
    return np.asarray(falcon_h1_plain.recurrence(
        x.reshape(bt, t, heads, hp // heads), dt, a,
        b.reshape(bt, t, groups, -1), c.reshape(bt, t, groups, -1), d,
        groups)).reshape(bt, t, hp)


def scan_operands(t, heads=4, p=128, groups=2, n=128, batch=2, seed=0):
    """Heads that decay strongly (A -16, Δ up to 0.5: gone within a few
    tokens) and weakly (A -0.05, Δ near 1e-3: whole windows back), B and C
    of two groups, a nonzero D."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((batch, t, heads * p)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (batch, t, heads)))
    a = -np.array([16.0, 0.05, 1.0, 4.0][:heads], np.float32)
    b, c = (rng.standard_normal((batch, t, groups * n)).astype(np.float32)
            * 0.3 for _ in range(2))
    d = np.array([1.0, 0.5, 0.0, 2.0][:heads], np.float32)
    return [jnp.asarray(v, jnp.float32) for v in (x, dt, a, b, c, d)]


@pytest.mark.parametrize("chunks", [1, 3, 5])
@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_the_chunked_scan_is_the_recurrence(path, chunks):
    q, groups = 128, 2
    ops = scan_operands(chunks * q)
    want = recurrence(*ops, groups)
    with jax.default_matmul_precision("highest"):
        if path == "plain":
            got = ssm_scan.plain_scan(*ops, q, groups)
        else:
            got = ssm_scan.ssd_scan_kernel(*ops, q, groups, interpret=True)
    err = np.abs(np.asarray(got) - want).max() / np.abs(want).max()
    assert err < 2e-5, err


@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_a_window_that_is_no_whole_number_of_chunks_is_padded(path):
    """Δ = 0 past the end: nothing decays and nothing enters the state, and
    the padded rows are cut off."""
    ops = scan_operands(2 * 128 - 37, heads=2, groups=1, batch=1, seed=3)
    want = recurrence(*ops, 1)
    with jax.default_matmul_precision("highest"):
        got = (ssm_scan.plain_scan(*ops, 128, 1) if path == "plain" else
               ssm_scan.ssd_scan_kernel(*ops, 128, 1, interpret=True))
    assert got.shape == want.shape
    assert np.abs(np.asarray(got) - want).max() < 2e-5 * np.abs(want).max()


def test_the_control_rounds_the_state_and_the_decays_and_errs():
    """``low``: the carried state and the decays in bfloat16, the same in
    both lowerings, and far from the recurrence where a head remembers long."""
    ops = scan_operands(4 * 128, seed=5)
    want = recurrence(*ops, 2)
    plain = np.asarray(ssm_scan.plain_scan(*ops, 128, 2, low=True))
    kernel = np.asarray(ssm_scan.ssd_scan_kernel(*ops, 128, 2, low=True,
                                                 interpret=True))
    scale = np.abs(want).max()
    assert np.abs(plain - kernel).max() < 1e-5 * scale
    assert np.abs(plain - want).max() > 1e-2 * scale


def test_bfloat16_operands_keep_the_products_in_float32():
    ops = scan_operands(2 * 128, seed=7)
    want = recurrence(*ops, 2)
    half = [v.astype(jnp.bfloat16) if i in (0, 3, 4) else v
            for i, v in enumerate(ops)]
    got = ssm_scan.ssd_scan_kernel(*half, 128, 2, interpret=True)
    plain = ssm_scan.plain_scan(*half, 128, 2)
    assert got.dtype == jnp.bfloat16
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got, np.float32) - want).max() < 0.02 * scale
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(plain, np.float32)).max() < 0.01 * scale


@pytest.mark.parametrize("x,dt,b,groups,chunk,why", [
    ((8, 4096, 4096), (8, 4096, 32), (8, 4096, 512), 2, 128, None),
    ((1, 256, 256), (1, 256, 2), (1, 256, 128), 1, 128, None),
    # two heads of 64 share a lane tile (the shared_tile body)
    ((1, 256, 128), (1, 256, 2), (1, 256, 128), 1, 128, None),
    ((1, 256, 256), (1, 256, 2), (1, 256, 64), 1, 128, "a state of 64"),
    ((1, 256, 256), (1, 256, 2), (1, 256, 256), 1, 64, "chunks of 64"),
    ((1, 256, 256), (1, 256, 2), (1, 256, 384), 3, 128, "heads over groups"),
    ((1, 256, 96), (1, 256, 2), (1, 256, 128), 1, 128, "heads of 48"),
    ((1, 256, 128), (1, 256, 2), (1, 256, 256), 2, 128,
     "one head of 64 a step: half a lane tile"),
], ids=["the_published", "one_group", "p64", "n64", "q64", "uneven", "p48",
        "p64_alone"])
def test_what_the_kernel_tiles(x, dt, b, groups, chunk, why):
    assert ssm_scan.scan_tiles(x, dt, b, jnp.bfloat16, groups, chunk) == (
        why is None), why
    assert not ssm_scan.scan_tiles(x, dt, b, jnp.int8, groups, chunk)


def test_one_trace_lowers_the_kernel_for_a_tpu_and_xla_here():
    ops = [jax.ShapeDtypeStruct(v.shape, v.dtype)
           for v in scan_operands(256, batch=1)]
    fn = jax.jit(lambda *a: ssm_scan.ssd_scan(*a, chunk=128, n_groups=2))
    traced = fn.trace(*ops)
    before = lowerings()
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
    after = lowerings()
    assert ssm_scan.KERNEL_NAME in tpu and "tpu_custom_call" in tpu
    assert ssm_scan.KERNEL_NAME not in cpu
    assert after.get("kernel", 0) - before.get("kernel", 0) == 1
    assert after.get("plain", 0) - before.get("plain", 0) == 1


# -- the model ----------------------------------------------------------------

def both(cfg, t, dtype, batch=3, seed=0, low=False):
    params = falcon_h1.init_params(cfg, seed, dtype)
    model = falcon_h1.build(cfg, seq=t, batch=batch, dtype=dtype,
                            params=params, low=low)
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, t), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.fn())(ids))
    host = jax.tree_util.tree_map(np.asarray, params)
    return got, falcon_h1_plain.forward(dict(cfg, seq=t), {}, host, ids)


@pytest.mark.parametrize("t", [24, 5], ids=["three_chunks", "inside_one"])
@pytest.mark.parametrize("layers", [1, 2])
def test_float32_matches_the_plain_reference(layers, t):
    got, want = both(config(num_hidden_layers=layers), t, jnp.float32)
    assert got.shape == want.shape == (3, 96)
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()


def test_bfloat16_stays_near_the_plain_reference_and_the_control_does_not():
    cfg = config(mamba_chunk_size=16)
    got, want = both(cfg, 64, jnp.bfloat16, batch=4)
    low, _ = both(cfg, 64, jnp.bfloat16, batch=4, low=True)
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 0.05, err
    assert np.abs(low - want).max() > np.abs(got - want).max()


@pytest.mark.parametrize("branch,key", [("attention_branch",
                                         "attention_out_multiplier"),
                                        ("mixer", "ssm_out_multiplier")])
def test_an_output_multiplier_of_zero_is_the_model_without_its_branch(
        monkeypatch, branch, key):
    cfg = config()
    params = falcon_h1.init_params(cfg, 1, jnp.float32)
    ids = np.random.default_rng(1).integers(0, 96, (2, 16), dtype=np.int32)
    zero = falcon_h1.apply(dict(cfg, **{key: 0.0}), params, ids, jnp.float32)
    whole = falcon_h1.apply(cfg, params, ids, jnp.float32)
    monkeypatch.setattr(falcon_h1, branch, lambda cfg, p, h, *rest: 0.0)
    without = falcon_h1.apply(cfg, params, ids, jnp.float32)
    np.testing.assert_allclose(np.asarray(zero), np.asarray(without),
                               rtol=1e-6, atol=1e-7)
    assert np.abs(np.asarray(whole) - np.asarray(without)).max() > 1e-4


@pytest.mark.parametrize("key", MULTIPLIERS + ("mlp_multipliers",
                                               "ssm_multipliers"))
def test_every_multiplier_reaches_the_program_and_the_reference(key):
    """Halving one multiplier moves the program's logits, and the reference
    moves with it: each is read from the config on both sides."""
    cfg = config()
    half = dict(cfg, **{key: (np.asarray(cfg[key]) * 0.5).tolist()})
    params = falcon_h1.init_params(cfg, 2, jnp.float32)
    host = jax.tree_util.tree_map(np.asarray, params)
    ids = np.random.default_rng(2).integers(0, 96, (2, 16), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        a = np.asarray(falcon_h1.apply(cfg, params, ids, jnp.float32))
        b = np.asarray(falcon_h1.apply(half, params, ids, jnp.float32))
    ref = falcon_h1_plain.forward(dict(half, seq=16), {}, host, ids)
    assert np.abs(a - b).max() > 1e-4 * np.abs(a).max()
    assert np.abs(b - ref).max() < 2e-4 * np.abs(ref).max()


def test_the_in_projection_splits_z_xbc_and_dt_at_the_published_widths():
    published = dict(config(), hidden_size=5120, mamba_d_ssm=4096,
                     mamba_n_heads=32, mamba_n_groups=2, mamba_d_state=256)
    w = falcon_h1.widths(published)
    assert (w["d_ssm"], w["bc"], w["conv"], w["in"]) == (4096, 512, 5120, 9248)
    mup = falcon_h1.mup_vector(published)
    assert mup.shape == (9248,)
    assert [float(mup[i]) for i in (0, 4096, 8192, 8704, 9216)] == [
        np.float32(m) for m in published["ssm_multipliers"]]


def test_the_model_lowers_one_scan_a_layer_kernel_for_a_tpu():
    cfg = config(num_hidden_layers=3, mamba_d_ssm=256, mamba_n_heads=2,
                 mamba_d_head=128, mamba_d_state=128, mamba_chunk_size=128)
    shapes = jax.eval_shape(lambda: falcon_h1.init_params(cfg, 0))
    ids = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    traced = jax.jit(lambda p, x: falcon_h1.apply(cfg, p, x)).trace(shapes, ids)
    before = lowerings()
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    after = lowerings()
    assert text.count(ssm_scan.KERNEL_NAME) >= 3
    assert after.get("kernel", 0) - before.get("kernel", 0) == 3
    assert after.get("plain", 0) == before.get("plain", 0)


# -- the streaming path -------------------------------------------------------

def test_token_frames_through_a_launch_string_at_batch_n_equal_n_single(
        tmp_path):
    """``tensor_filter framework=jax`` opens the model from a checkpoint and
    the published config by the builder's name, like the other zoo models;
    a batch of N windows gives the N rows that N single windows give."""
    cfg = config()
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    params = falcon_h1.init_params(cfg, 3, jnp.float32)
    save_state(params, str(tmp_path / "falcon_h1.npz"))
    ids = np.random.default_rng(4).integers(0, 96, (4, 20), dtype=np.int32)

    def run(frames, custom):
        got = []
        p = parse_launch(
            "datasrc name=s ! tensor_filter framework=jax name=f "
            f"model={tmp_path / 'falcon_h1.npz'} custom={custom} "
            "! tensor_sink name=out")
        p["s"].data = [f.copy() for f in frames]
        p["out"].connect("new-data",
                         lambda f: got.append(np.asarray(f.tensor(0))))
        p.run(timeout=120)
        return got

    custom = (f"builder=falcon_h1:build,config={tmp_path / 'config.json'},"
              "seq=20,dtype=float32")
    with jax.default_matmul_precision("highest"):
        singles = run(list(ids), custom)
        batched, = run([ids], custom + ",batch=4")
    assert batched.shape == (4, 96) and batched.dtype == np.float32
    assert [s.shape for s in singles] == [(96,)] * 4
    np.testing.assert_allclose(np.stack(singles), batched, atol=1e-5)
    want = falcon_h1_plain.forward(dict(cfg, seq=20), {},
                                   jax.tree_util.tree_map(np.asarray, params),
                                   ids)
    assert np.abs(batched - want).max() < 2e-4 * np.abs(want).max()
