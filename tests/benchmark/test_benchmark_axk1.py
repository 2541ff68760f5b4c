"""The ``axk1`` kind, its configuration and the readers that PR 40 brought,
beyond what the manifest and rehearsal tests hold every entry to: the
published keys kept, the cut and the counts at the published widths, the
seeded routers decisive at both levels with the group limit binding for a
stated share of the tokens, the control refused, and readers that find
nothing to read saying nothing."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, run as bench_run  # noqa: E402
from benchmark.layer_metrics import device_trace, glm_shares  # noqa: E402
from benchmark.model_kinds import axk1 as kind  # noqa: E402

MAN = manifest.load_manifest(ROOT)
CELL = "axk1_l5_ep16.ctx2x16k"
# the cell's two rooflines: the accepted readers read them off the marks
# (``attention``, ``held_experts``) and the work functions of this kind
READERS = {"latent_attention_roofline": device_trace.attention_roofline,
           "group_limited_experts_roofline": glm_shares.held_experts_roofline}
CFG = manifest.load_config(MAN, "axk1_l5_ep16", ROOT)
REDUCED = {"num_hidden_layers": (61, 5), "n_routed_experts": (192, 12),
           "vocab_size": (163840, 20480)}


def rehearse(seed, *extra):
    args = bench_run.parse_args(["--workload", CELL, "--seed", str(seed),
                                 "--seconds", "0.5", "--rehearsal", *extra])
    return bench_run.run_cell(args)[1]


def test_the_configuration_is_the_published_one_with_three_keys_cut():
    published = CFG["published"]
    assert published["model_type"] == "axk1"
    for key, value in published.items():
        if key in REDUCED:
            assert (value, CFG[key]) == REDUCED[key], key
        else:
            assert CFG[key] == value, key
    assert sorted(CFG["reduced"]) == sorted(REDUCED)
    assert "16 chips share each layer" in CFG["deployment"]
    assert len(CFG["assumed"]) >= 8
    said = " ".join(CFG["assumed"])
    for what in ("two highest", "no selection bias", "interleaved",
                 "ep_size", "moe_layer_freq", "num_key_value_heads",
                 "seeded random weights", "binds"):
        assert what in said, what
    s = kind.sizes(CFG)
    # the program's keys: the router keeps the published width and groups
    assert s["n_routed_experts"] == 192 and s["experts_held"] == [0, 12]
    assert (s["n_group"], s["topk_group"], s["num_experts_per_tok"]) == (
        8, 4, 8)
    assert s["seq"] == 16384 > s["rope_scaling"][
        "original_max_position_embeddings"]
    assert s["layers"] == [0, 1, 2, 3, 4] and s["token_chunk"] == 8192
    assert [kind._sparse(s, i) for i in s["layers"]] == [False] + [True] * 4
    # every width is the published one
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_attention_heads"):
        assert s[key] == published[key], key
    r = kind.sizes(CFG, rehearsal=True)
    assert r["experts_held"] == [0, 4] and r["n_routed_experts"] == 64
    assert r["seq"] > r["rope_scaling"]["original_max_position_embeddings"]


def test_the_counts_at_the_published_widths():
    s = kind.sizes(CFG)
    assert kind.param_count(s) == 3_491_257_344
    # the whole model is the same call with no cut: "A.X K1 519B"
    whole = dict(s, layers=list(range(61)), experts_held=[0, 192],
                 vocab_size=163840)
    assert round(kind.param_count(whole) / 1e9, 2) == 518.98
    flops = kind.frame_flops(s)
    parts = {k: round(v / 1e12, 3) for k, v in flops.items()}
    assert parts == {"projections": 16.568, "latent_attention": 27.489,
                     "dense_mlp": 12.988, "experts": 8.839, "head": 0.0,
                     "total": 65.885}
    # every causal key's score and value products, a layer and a window
    assert flops["latent_attention"] == 5 * 2 * 64 * (16384 * 16385 // 2) * (
        192 + 128)
    assert kind.attention_work(s)["flops"] == flops["latent_attention"]
    assert kind.held_experts_work(s)["flops"] == flops["experts"]
    marks = kind.marks(s)
    # each part under the accepted reader's label and under its metric's
    assert marks["latent_attention"] is marks["attention"]
    assert marks["group_limited_experts"] is marks["held_experts"]
    assert marks["attention"] == {"names": ["nns_latent_attention"],
                                  "dims": [[64, 512, 16384]]}
    # the mark is no prefix of the kernel under a selection, nor it of this
    assert not "nns_latent_sparse_attention".startswith(
        marks["attention"]["names"][0])
    # no mark names what a loop carries whole (a chunk's tokens, the pairs,
    # a chunk's choice), nor the output projection's [8192, 7168] weight
    for carried in ([8192, 7168], [65536], [8192, 8]):
        assert not any(carried == dims for mark in marks.values()
                       for dims in mark["dims"]), carried
    experts = marks["held_experts"]
    assert experts["names"] == ["ragged-dot"]
    assert [65536, 7168] in experts["dims"] and [8192, 8, 24] in experts["dims"]


def test_the_cell_runs_the_traffic_the_glm_cell_runs():
    cell = manifest.find(MAN["workloads"], CELL, "cell")
    assert cell["chips"] == 1 and cell["traffic"] == "ctx2x16k_saturated"
    other = manifest.find(MAN["workloads"], "glm52_l5_ep16.ctx2x16k", "cell")
    assert other["traffic"] == cell["traffic"]
    mix = manifest.load_traffic(cell["traffic"])
    assert (mix["streams"], mix["inflight"], mix["frame_pool"],
            mix["warm_rounds"], mix["check_frames"]) == (2, 2, 4, 3, 4)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_lower_precision_control_comes_out_not_correct(seed):
    sound, control = rehearse(seed), rehearse(seed, "--control")
    assert sound.line["correct"] is True
    assert control.line["correct"] is False
    assert (control.line["compared"]["logit_err"]["value"]
            > 1.5 * sound.line["compared"]["logit_err"]["value"])


@pytest.mark.parametrize("rehearsal", [True, False],
                         ids=["rehearsal", "published_router"])
def test_the_seeded_routers_are_decisive_at_both_levels(rehearsal):
    """Over the embedding rows, at float32 with the program's router and
    the reference's: the four drawn groups' scores stand clear of the
    others', the eight drawn experts are the group-limited choice, and a
    choice among all experts takes every token's lure, which the limited one
    does not; for about half of the tokens the lure is an expert held here,
    one that the token sends nothing to.  At the published router width (192 experts in 8
    groups, hidden cut to 512 so that it fits a test) and at the
    rehearsal's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.references import axk1_plain
    from nnstreamer_tpu.parallel import moe

    s = kind.sizes(CFG, rehearsal=True)
    if not rehearsal:
        s = dict(s, n_routed_experts=192, experts_held=[0, 12],
                 hidden_size=512, vocab_size=2048)
    w = kind.init_weights(s, 40)
    assert [("mlp" in p, "moe" in p) for p in w["layers"]] == [
        (True, False)] + [(False, True)] * 4
    router = np.asarray(w["layers"][1]["moe"]["router"], np.float32)
    assert "bias" not in w["layers"][1]["moe"]
    assert router.shape == (s["hidden_size"], s["n_routed_experts"])
    assert w["layers"][1]["moe"]["w_in"].shape[0] == s["experts_held"][1]
    chosen, lure = kind.drawn_routing(s, 40)
    k, groups, kept = (s["num_experts_per_tok"], s["n_group"],
                       s["topk_group"])
    size = s["n_routed_experts"] // groups
    # two experts in each of four groups, the lure in a fifth
    assert all(sorted(np.bincount(row // size, minlength=groups))[-kept:]
               == [k // kept] * kept for row in chosen[:64])
    assert not (lure[:, None] // size == chosen // size).any()
    first, count = s["experts_held"]
    here = (lure >= first) & (lure < first + count)
    assert 0.4 < here.mean() < 0.6
    assert not ((chosen >= first) & (chosen < first + count))[here].any()
    x = np.asarray(w["embed"], np.float32)
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + s["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        _, experts = moe.route_top_k(jnp.asarray(h), jnp.asarray(router), k,
                                     2.5, None, groups, kept)
        _, free = moe.route_top_k(jnp.asarray(h), jnp.asarray(router), k, 2.5)
        gates = np.asarray(axk1_plain.route(
            jnp.asarray(h), jnp.asarray(router), k, groups, kept, 2.5))
    experts, free = np.asarray(experts), np.asarray(free)
    assert np.array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    drawn = np.zeros_like(gates, bool)
    np.put_along_axis(drawn, chosen, True, 1)
    assert np.array_equal(gates != 0, drawn)
    # the limit binds for every token
    assert (free == lure[:, None]).any(-1).all()
    assert not (experts == lure[:, None]).any()
    # clear of bfloat16's rounding: the kept groups' scores against the best
    # group left out, the eighth chosen score against the ninth in its groups
    scores = 1 / (1 + np.exp(-(h @ router)))
    by_group = np.sort(scores.reshape(len(h), groups, size), -1)[..., -2:].sum(-1)
    ranked = np.sort(by_group, -1)
    assert (ranked[:, -kept] - ranked[:, -kept - 1]).min() > 0.05
    inside = np.where(np.repeat(by_group >= ranked[:, -kept, None], size, 1),
                      scores, -1.0)
    inside = np.sort(inside, -1)
    assert (inside[:, -k] - inside[:, -k - 1]).min() > 0.05
    # evenly loaded: every expert is drawn for about k / E of the tokens
    load = np.bincount(chosen.ravel(), minlength=s["n_routed_experts"])
    assert load.min() > 0.5 * load.mean() and load.max() < 1.6 * load.mean()


def test_readers_that_find_nothing_to_read_say_nothing():
    empty = SimpleNamespace(slices=[], kind=kind, sizes=kind.sizes(CFG),
                            notes={}, chips=1, frames_per_step=2, peak=None)
    other_kind = SimpleNamespace(slices=[object()], kind=SimpleNamespace(),
                                 sizes={}, notes={}, chips=1,
                                 frames_per_step=2, peak=None)
    for read in READERS.values():
        assert read(empty) is None and read(other_kind) is None


def test_the_readers_read_a_trace_that_carries_the_marks():
    """A hand-built slice: two steps in which the kernel ran 0.5 s and the
    expert layer's ops 0.2 s, against the work of two frames a step."""
    from benchmark import peaks, trace_reduce

    s = kind.sizes(CFG)
    slice_ = trace_reduce.Slice(
        steps=2, window_ns=2.4e9, busy_ns=2.4e9, model_ns=2.4e9,
        marked_ns={"attention": 1.0e9, "held_experts": 0.4e9},
        device_ops=[], idle_gaps=[])
    ctx = SimpleNamespace(slices=[slice_], kind=kind, sizes=s, notes={},
                          chips=1, frames_per_step=2,
                          peak=peaks.peak_for("TPU v5 lite"))
    attention = READERS["latent_attention_roofline"](ctx)
    experts = READERS["group_limited_experts_roofline"](ctx)
    flops = kind.frame_flops(s)
    assert attention == pytest.approx(
        100 * 2 * flops["latent_attention"] / ctx.peak.flops_per_s / 0.5)
    assert experts == pytest.approx(
        100 * 2 * flops["experts"] / ctx.peak.flops_per_s / 0.2)
    assert 0 < attention < 100 and 0 < experts < 100
    assert ctx.notes == {"attention_bound": "compute",
                         "held_experts_bound": "compute"}


def test_the_new_metrics_list_the_new_cell_alone():
    """Of the manifest only what this cell owns: its configuration and cell
    are there, its two metrics list it alone, and none of the rooflines
    the benchmark had before lists it.  Where an entry stands in its list, and what later PRs append, is
    not this file's to hold."""
    assert manifest.find(MAN["configs"], "axk1_l5_ep16",
                         "configuration")["reduced"] == CFG["reduced"]
    assert manifest.find(MAN["workloads"], CELL, "cell")["config"] == (
        "axk1_l5_ep16")
    for name, read in READERS.items():
        entry = manifest.find(MAN["per_layer"], name, "metric")
        assert entry["workloads"] == [CELL]
        assert entry["layer"] == "kernels"
        assert entry["moves"] == "frames_per_s"
        spec = manifest.load_layer_metric(name)
        assert getattr(manifest.module("layer_metrics", spec["reader"]),
                       spec["function"]) is read
    # the rooflines the benchmark had before read other kinds' marks
    for name in ("attention_roofline", "mixed_attention_roofline",
                 "moe_roofline", "sparse_attention_roofline",
                 "indexer_roofline", "held_experts_roofline"):
        assert CELL not in manifest.find(MAN["per_layer"], name,
                                         "metric")["workloads"], name
