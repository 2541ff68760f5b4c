"""The ``falcon_h1`` kind, its configuration, its cell and the reader that
PR 44 brought, beyond what the manifest and rehearsal tests hold every entry
to: the published keys kept and the depth alone cut, the counts at the
published widths, the control refused, and a reader that finds nothing to
read saying nothing.  Nothing here pins where an entry stands in its list
or how long a list is."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, run as bench_run  # noqa: E402
from benchmark.layer_metrics import ssm_shares  # noqa: E402
from benchmark.model_kinds import falcon_h1 as kind  # noqa: E402

MAN = manifest.load_manifest(ROOT)
CONFIG = "falconh1_34b_l4"
CELL = "falconh1_34b_l4.ctx8x4k"
METRIC = "ssm_scan_roofline"
CFG = manifest.load_config(MAN, CONFIG, ROOT)


def rehearse(seed, *extra):
    args = bench_run.parse_args(["--workload", CELL, "--seed", str(seed),
                                 "--seconds", "0.5", "--rehearsal", *extra])
    return bench_run.run_cell(args)[1]


def test_the_configuration_is_the_published_one_with_the_depth_cut():
    published = CFG["published"]
    assert published["model_type"] == "falcon_h1"
    for key, value in published.items():
        if key == "num_hidden_layers":
            assert (value, CFG[key]) == (72, 4)
        else:
            assert CFG[key] == value, key
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert "layers 0-3" in CFG["deployment"]
    said = " ".join(CFG["assumed"])
    for what in ("rotate_half", "time_step_limit", "A_log", "dt_bias",
                 "seeded random weights", "multiplier", "rehearsal"):
        assert what in said, what
    s = kind.sizes(CFG)
    assert s["layers"] == [0, 1, 2, 3] and s["seq"] == 4096
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads", "mamba_d_ssm",
                "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_n_groups", "mamba_chunk_size", "vocab_size"):
        assert s[key] == published[key], key
    assert (CFG["limits"]["logit_err"]
            > CFG["rehearsal_limits"]["logit_err"] * 0.5)


def test_the_entries_are_found_by_name():
    entry = manifest.find(MAN["configs"], CONFIG, "configuration")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "benchmark/configs/falconh1_34b_l4.json"
    cell = manifest.find(MAN["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ctx8x4k_saturated", 1)
    mix = manifest.load_traffic(cell["traffic"])
    assert (mix["kind"], mix["streams"], mix["loop"], mix["inflight"]) == (
        "token_windows", 8, "closed", 2)
    # ids uniform over every row of the vocabulary: the whole table is held
    assert kind.sizes(CFG)["vocab_size"] == 261120


def test_the_scans_roofline_lists_the_new_cell_alone():
    entry = manifest.find(MAN["per_layer"], METRIC, "metric")
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["moves"], entry["source"], entry["unit"]) == (
        "kernels", "frames_per_s", "device_trace", "%")
    spec = manifest.load_layer_metric(METRIC)
    assert getattr(manifest.module("layer_metrics", spec["reader"]),
                   spec["function"]) is ssm_shares.ssm_scan_roofline
    for other in MAN["per_layer"]:
        if other["name"].endswith("_roofline") and other["name"] != METRIC:
            assert CELL not in other.get("workloads", ()), other["name"]


def test_the_counts_at_the_published_widths():
    s = kind.sizes(CFG)
    assert kind.param_count(s) == 4_394_354_048
    # the whole model is the same count over 72 layers: "34B"
    whole = dict(s, layers=list(range(72)))
    assert round(kind.param_count(whole) / 1e9, 2) == 33.64
    flops = kind.frame_flops(s)
    parts = {k: round(v / 1e12, 3) for k, v in flops.items()}
    assert parts == {"dense_mlp": 10.823, "mixer_projections": 2.239,
                     "attention_projections": 1.031, "attention": 0.344,
                     "ssm_scan": 0.088, "conv": 0.001, "head": 0.003,
                     "total": 14.528}
    # 2QN·G + 2QP·H + 4NP·H a token and layer
    per_token = 2 * 128 * 256 * 2 + 2 * 128 * 128 * 32 + 4 * 256 * 128 * 32
    work = kind.ssm_scan_work(s)
    assert work["flops"] == 4 * 4096 * per_token == flops["ssm_scan"]
    assert work["bytes"] == 4 * 4096 * (2 * (2 * 4096 + 2 * 512) + 4 * 32)
    assert kind.marks(s)["ssm_scan"]["names"] == ["nns_ssd_scan"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_comes_out_not_correct(seed):
    """The scan's carried state and decays in bfloat16: refused where the
    program passes."""
    sound, control = rehearse(seed), rehearse(seed, "--control")
    assert sound.line["correct"] is True
    assert control.line["correct"] is False
    assert (control.line["compared"]["logit_err"]["value"]
            > 1.2 * sound.line["compared"]["logit_err"]["value"])


def test_the_reader_that_finds_nothing_to_read_says_nothing():
    empty = SimpleNamespace(slices=[], kind=kind, sizes=kind.sizes(CFG),
                            notes={}, chips=1, frames_per_step=8, peak=None)
    other_kind = SimpleNamespace(slices=[object()], kind=SimpleNamespace(),
                                 sizes={}, notes={}, chips=1,
                                 frames_per_step=8, peak=None)
    assert ssm_shares.ssm_scan_roofline(empty) is None
    assert ssm_shares.ssm_scan_roofline(other_kind) is None


def test_the_reader_reads_a_trace_that_carries_the_mark():
    """A hand-built slice: two steps in which the kernel ran 40 ms, against
    the scan's work of eight frames a step."""
    from benchmark import peaks, trace_reduce

    s = kind.sizes(CFG)
    slice_ = trace_reduce.Slice(
        steps=2, window_ns=2.4e9, busy_ns=2.4e9, model_ns=2.4e9,
        marked_ns={"ssm_scan": 40e6}, device_ops=[], idle_gaps=[])
    ctx = SimpleNamespace(slices=[slice_], kind=kind, sizes=s, notes={},
                          chips=1, frames_per_step=8,
                          peak=peaks.peak_for("TPU v5 lite"))
    work = kind.ssm_scan_work(s)
    least = max(8 * work["flops"] / ctx.peak.flops_per_s,
                8 * work["bytes"] / ctx.peak.bytes_per_s)
    got = ssm_shares.ssm_scan_roofline(ctx)
    assert got == pytest.approx(100 * 2 * least / 0.04)
    assert 0 < got < 100
    assert ctx.notes == {"ssm_scan_bound": "compute"}
