"""The ``nemotron_h`` kind, its configuration, its cell and its two
readers, beyond what the manifest and rehearsal tests hold every
entry to: the published keys kept and the cut's keys listed, the counts at
the published widths, the routers decisive at every expert layer, the
control refused, and a reader that finds nothing to read saying nothing.
Nothing here pins where an entry stands in its list or how long a list
is."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, run as bench_run  # noqa: E402
from benchmark.layer_metrics import nemotron_shares  # noqa: E402
from benchmark.model_kinds import nemotron_h as kind  # noqa: E402

MAN = manifest.load_manifest(ROOT)
CONFIG = "nemotron3_super_l11_ep4"
CELL = "nemotron3_super_l11_ep4.ctx8x4k"
METRICS = ("latent_experts_roofline", "narrow_scan_roofline")
CFG = manifest.load_config(MAN, CONFIG, ROOT)
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]


def rehearse(seed, *extra):
    args = bench_run.parse_args(["--workload", CELL, "--seed", str(seed),
                                 "--seconds", "0.5", "--rehearsal", *extra])
    return bench_run.run_cell(args)[1]


def test_the_configuration_is_the_published_one_with_the_cut_listed():
    published = CFG["published"]
    assert published["model_type"] == "nemotron_h"
    cut = {"num_hidden_layers": (88, 11),
           "hybrid_override_pattern": (published["hybrid_override_pattern"],
                                       "MEMEMEMEM*E"),
           "n_routed_experts": (512, 128), "vocab_size": (131072, 32768),
           "num_nextn_predict_layers": (1, 0)}
    for key, value in published.items():
        if key in cut:
            assert (value, CFG[key]) == cut[key], key
        else:
            assert CFG[key] == value, key
    assert CFG["reduced"] == REDUCED
    assert published["hybrid_override_pattern"][27:38] == "MEMEMEMEM*E"
    assert CFG["deployment"].startswith("4 chips share each layer")
    assert "77 layers left out" in CFG["deployment"]
    said = " ".join(CFG["assumed"])
    for what in ("no rotary", "router and the shared expert read", "latent",
                 "relu2", "gated", "time_step_limit", "decisive",
                 "rehearsal", "limits"):
        assert what in said, what
    s = kind.sizes(CFG)
    assert s["layers"] == list(range(27, 38)) and s["seq"] == 4096
    assert s["experts_held"] == [0, 128] and s["n_routed_experts"] == 512
    for key in ("hidden_size", "mamba_num_heads", "mamba_head_dim",
                "ssm_state_size", "n_groups", "chunk_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "moe_intermediate_size", "moe_latent_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok"):
        assert s[key] == published[key], key


def test_the_entries_are_found_by_name():
    entry = manifest.find(MAN["configs"], CONFIG, "configuration")
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/nemotron3_super_l11_ep4.json"
    cell = manifest.find(MAN["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ctx8x4k_saturated", 1)
    mix = manifest.load_traffic(cell["traffic"])
    assert (mix["kind"], mix["streams"], mix["loop"], mix["inflight"]) == (
        "token_windows", 8, "closed", 2)


@pytest.mark.parametrize("metric", METRICS)
def test_the_new_metrics_list_the_new_cell_alone(metric):
    entry = manifest.find(MAN["per_layer"], metric, "metric")
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["moves"], entry["source"], entry["unit"]) == (
        "kernels", "frames_per_s", "device_trace", "%")
    spec = manifest.load_layer_metric(metric)
    assert spec["reader"] == "nemotron_shares"
    assert getattr(nemotron_shares, spec["function"]) is getattr(
        manifest.module("layer_metrics", spec["reader"]), metric)
    for other in MAN["per_layer"]:
        if other["name"].endswith("_roofline") and other["name"] not in METRICS:
            assert CELL not in other.get("workloads", ()), other["name"]


def test_the_counts_at_the_published_widths():
    s = kind.sizes(CFG)
    assert kind.param_count(s) == 4_648_163_712
    # 55.0 % of the chip's 16 909 336 064 bytes in bfloat16
    assert round(100 * 2 * kind.param_count(s) / 16_909_336_064, 1) == 55.0
    whole = dict(s, layers=list(range(88)), experts_held=[0, 512],
                 vocab_size=131072,
                 hybrid_override_pattern=CFG["published"][
                     "hybrid_override_pattern"])
    assert round(kind.param_count(whole) / 1e9, 1) == 120.7
    flops = kind.frame_flops(s)
    assert flops["total"] == pytest.approx(8.53e12, rel=0.01)
    shares = {k: round(100 * v / flops["total"], 1) for k, v in flops.items()}
    assert (shares["mixer_projections"], shares["ssm_scan"],
            shares["shared_experts"], shares["routed_experts"],
            shares["latent_projections"], shares["router"]) == (
        52.6, 1.6, 21.2, 14.5, 4.0, 1.0)
    assert round(shares["attention_projections"] + shares["attention"]) == 5
    # 2QN·G + 2QP·H + 4NP·H a token and Mamba layer
    per_token = 2 * 128 * 128 * 8 + 2 * 128 * 64 * 128 + 4 * 128 * 64 * 128
    assert kind.narrow_scan_work(s)["flops"] == 5 * 4096 * per_token
    # 22 of 512 picks a token, 128 held: 5.5 pairs of 2·2·1024·2688
    assert kind.latent_experts_work(s)["flops"] == 5 * 4096 * 5.5 * 4 * 1024 \
        * 2688
    marks = kind.marks(s)
    assert marks["narrow_scan"]["names"] == ["nns_ssd_scan"]
    assert marks["latent_experts"]["names"] == ["ragged-dot",
                                                "nns_combine_rows"]
    # a pass of 8192 tokens' 180 224 pairs: twice the 45 056 an even routing
    # sends to the 128 held, in whole tiles
    assert [90112, 1024] in marks["latent_experts"]["dims"]


def test_the_routers_are_decisive_at_every_expert_layer():
    """At the rehearsal's widths, through the reference's own walk: at each
    expert layer every token's 22nd and 23rd biased scores lie far apart:
    no bfloat16 rounding turns a choice over."""
    import jax
    import jax.numpy as jnp

    from benchmark.references import nemotron_h_plain as ref

    s = kind.sizes(CFG, rehearsal=True)
    w = kind.init_weights(s, 7)
    frames = np.random.default_rng(7).integers(0, s["vocab_size"], (2, 256),
                                               dtype=np.int32)
    k = s["num_experts_per_tok"]
    gaps = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(w["embed"])[frames], jnp.float32)
        for char, p in zip(ref.kinds(s), w["layers"]):
            key, mix = ref.MIXERS[char]
            h = ref.rms_norm(x, ref._f32(p["norm"]), s["layer_norm_epsilon"])
            if char == "E":
                scores = np.asarray(jax.nn.sigmoid(
                    h.reshape(-1, h.shape[-1]) @ ref._f32(p["moe"]["router"]))
                    + ref._f32(p["moe"]["bias"]))
                ranked = -np.sort(-scores, axis=1)
                gaps.append(float((ranked[:, k - 1] - ranked[:, k]).min()))
            x = x + mix(s, p[key], h)
    assert len(gaps) == 5 and min(gaps) > 0.05, gaps


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_comes_out_not_correct(seed):
    """W8A8 projections and the scans' state and decays in bfloat16:
    refused where the program passes."""
    sound, control = rehearse(seed), rehearse(seed, "--control")
    assert sound.line["correct"] is True
    assert control.line["correct"] is False
    assert (control.line["compared"]["logit_err"]["value"]
            > 1.2 * sound.line["compared"]["logit_err"]["value"])


@pytest.mark.parametrize("metric", METRICS)
def test_the_reader_that_finds_nothing_to_read_says_nothing(metric):
    read = getattr(nemotron_shares, metric)
    empty = SimpleNamespace(slices=[], kind=kind, sizes=kind.sizes(CFG),
                            notes={}, chips=1, frames_per_step=8, peak=None)
    other_kind = SimpleNamespace(slices=[object()], kind=SimpleNamespace(),
                                 sizes={}, notes={}, chips=1,
                                 frames_per_step=8, peak=None)
    assert read(empty) is None
    assert read(other_kind) is None


@pytest.mark.parametrize("metric,label", [
    ("latent_experts_roofline", "latent_experts"),
    ("narrow_scan_roofline", "narrow_scan")])
def test_the_reader_reads_a_trace_that_carries_the_mark(metric, label):
    """A hand-built slice: two steps in which the part ran 100 ms a step,
    against its work of eight frames a step."""
    from benchmark import peaks, trace_reduce

    s = kind.sizes(CFG)
    slice_ = trace_reduce.Slice(
        steps=2, window_ns=1.2e9, busy_ns=1.2e9, model_ns=1.2e9,
        marked_ns={label: 200e6}, device_ops=[], idle_gaps=[])
    ctx = SimpleNamespace(slices=[slice_], kind=kind, sizes=s, notes={},
                          chips=1, frames_per_step=8,
                          peak=peaks.peak_for("TPU v5 lite"))
    work = getattr(kind, f"{label}_work")(s)
    least = max(8 * work["flops"] / ctx.peak.flops_per_s,
                8 * work["bytes"] / ctx.peak.bytes_per_s)
    got = getattr(nemotron_shares, metric)(ctx)
    assert got == pytest.approx(100 * 2 * least / 0.2)
    assert 0 < got < 100
    bound = ("compute" if 8 * work["flops"] / ctx.peak.flops_per_s
             >= 8 * work["bytes"] / ctx.peak.bytes_per_s else "memory")
    assert ctx.notes == {f"{label}_bound": bound}
