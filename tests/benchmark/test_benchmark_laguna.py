"""The ``laguna`` kind, the ``token_windows`` traffic and the readers that
PR 34 brought, beyond what the manifest and rehearsal tests hold every entry
to: the counts at the published widths, the control refused, and readers
that find nothing to read saying nothing."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, run as bench_run  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    device_trace, kernel_shares, program_counters)
from benchmark.model_kinds import laguna as kind  # noqa: E402
from benchmark.traffic_kinds import token_windows  # noqa: E402

MAN = manifest.load_manifest(ROOT)
CELL = "laguna_xs2_l5.ctx16x4k"
CFG = manifest.load_config(MAN, "laguna_xs2_l5", ROOT)


def rehearse(seed, *extra):
    args = bench_run.parse_args(["--workload", CELL, "--seed", str(seed),
                                 "--seconds", "0.5", "--rehearsal", *extra])
    return bench_run.run_cell(args)[1]


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    published = CFG["published"]
    assert published["num_hidden_layers"] == 40 and CFG["num_hidden_layers"] == 5
    for key, value in published.items():
        if key != "num_hidden_layers":
            assert CFG[key] == value, key
    assert CFG["reduced"] == ["num_hidden_layers"]
    s = kind.sizes(CFG)
    assert s["seq"] == 4096 and s["num_experts"] == 256
    assert s["num_experts_per_tok"] == 8 and s["sliding_window"] == 512
    assert s["layer_types"][:5] == ["full_attention"] + ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert s["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert s["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4


def test_the_counts_at_the_published_widths():
    s = kind.sizes(CFG)
    assert kind.param_count(s) == 3_869_267_968
    # the whole model is the same call with no cut: 33.4 B
    assert round(kind.param_count(dict(s, num_hidden_layers=40)) / 1e9, 2) == 33.44
    flops = kind.frame_flops(s)
    parts = {k: round(v / 1e12, 4) for k, v in flops.items()}
    assert parts == {"projections": 1.4087, "dense_mlp": 0.4123,
                     "experts": 0.9449, "full_attention": 0.4124,
                     "window_attention": 0.1933, "head": 0.0004,
                     "total": 3.3721}
    assert kind.moe_work(s)["flops"] == flops["experts"]
    assert kind.attention_work(s)["flops"] == (
        flops["full_attention"] + flops["window_attention"])
    marks = kind.marks(s)
    assert marks["attention"]["names"] == ["nns_blocked_attention"]
    assert [262144, 2048] in marks["moe"]["dims"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_lower_precision_control_comes_out_not_correct(seed):
    sound, control = rehearse(seed), rehearse(seed, "--control")
    assert sound.line["correct"] is True
    assert control.line["correct"] is False
    assert (control.line["compared"]["logit_err"]["value"]
            > 1.5 * sound.line["compared"]["logit_err"]["value"])


def test_windows_follow_the_seed_and_cover_the_vocabulary():
    a = token_windows.make_frames(2**31 + 5, 16, 4, 4096, 100352)
    b = token_windows.make_frames(2**31 + 5, 16, 4, 4096, 100352)
    c = token_windows.make_frames(2**31 + 6, 16, 4, 4096, 100352)
    assert a.shape == (16, 4, 4096) and a.dtype == np.int32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 100352 and a.max() > 100000
    assert len({row.tobytes() for row in a.reshape(64, -1)}) == 64


def test_readers_that_find_nothing_to_read_say_nothing():
    """On the parent of the PR that brought them the program has no such
    histogram and the kind no such marks: no value, no error."""
    from nnstreamer_tpu.obs.metrics import MetricsRegistry

    empty = SimpleNamespace(slices=[], kind=kind, sizes=kind.sizes(CFG),
                            notes={}, chips=1, frames_per_step=16, peak=None)
    assert kernel_shares.moe_roofline(empty) is None
    assert device_trace.attention_roofline(empty) is None
    other_kind = SimpleNamespace(slices=[object()], kind=SimpleNamespace(),
                                 sizes={}, notes={}, chips=1,
                                 frames_per_step=16, peak=None)
    assert kernel_shares.moe_roofline(other_kind) is None
    import nnstreamer_tpu.obs.metrics as metrics

    real, metrics.REGISTRY = metrics.REGISTRY, MetricsRegistry()
    try:
        assert program_counters.weights_upload_s(None) is None
    finally:
        metrics.REGISTRY = real


def test_weights_upload_s_reads_what_opening_a_model_records():
    report = rehearse(5)
    assert report.line["correct"] is True
    assert program_counters.weights_upload_s(None) > 0
