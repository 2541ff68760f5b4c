"""The ``glm_dsa`` kind, its configuration and the readers that PR 38
brought, beyond what the manifest and rehearsal tests hold every entry to:
the published keys kept, the cut and the counts at the published widths,
the control refused, and readers that find nothing to read saying nothing."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, run as bench_run  # noqa: E402
from benchmark.layer_metrics import glm_shares  # noqa: E402
from benchmark.model_kinds import glm_dsa as kind  # noqa: E402

MAN = manifest.load_manifest(ROOT)
CELL = "glm52_l5_ep16.ctx2x16k"
CFG = manifest.load_config(MAN, "glm52_l5_ep16", ROOT)
REDUCED = {"num_hidden_layers": (78, 5), "n_routed_experts": (256, 16),
           "vocab_size": (154880, 19360), "num_nextn_predict_layers": (1, 0)}


def rehearse(seed, *extra):
    args = bench_run.parse_args(["--workload", CELL, "--seed", str(seed),
                                 "--seconds", "0.5", "--rehearsal", *extra])
    return bench_run.run_cell(args)[1]


def test_the_configuration_is_the_published_one_with_four_keys_cut():
    published = CFG["published"]
    for key, value in published.items():
        if key in REDUCED:
            assert (value, CFG[key]) == REDUCED[key], key
        else:
            assert CFG[key] == value, key
    assert sorted(CFG["reduced"]) == sorted(REDUCED)
    assert "16 chips share each layer" in CFG["deployment"]
    assert len(CFG["assumed"]) >= 8
    s = kind.sizes(CFG)
    # the program's keys: the router keeps the published width
    assert s["n_routed_experts"] == 256 and s["experts_held"] == [0, 16]
    assert s["num_experts_per_tok"] == 8 and s["index_topk"] == 2048
    assert s["seq"] == 16384 > s["index_topk"]
    # the per-layer lists are whole and read by published index: a leading
    # dense layer that selects, then one whole period full, shared x 3
    assert len(s["indexer_types"]) == len(s["mlp_layer_types"]) == 78
    assert s["layers"] == [2, 6, 7, 8, 9]
    assert [s["indexer_types"][i] for i in s["layers"]] == [
        "full", "full", "shared", "shared", "shared"]
    assert [s["mlp_layer_types"][i] for i in s["layers"]] == [
        "dense"] + ["sparse"] * 4
    r = kind.sizes(CFG, rehearsal=True)
    assert r["seq"] > r["index_topk"] and r["experts_held"] == [0, 4]
    assert r["n_routed_experts"] == 16


def test_the_counts_at_the_published_widths():
    s = kind.sizes(CFG)
    assert kind.param_count(s) == 3_881_517_056
    # the whole model is the same call with no cut: 743.4 B without the
    # next-token-prediction layer, of the published ~750 B
    whole = dict(s, layers=list(range(78)), experts_held=[0, 256],
                 vocab_size=154880)
    assert round(kind.param_count(whole) / 1e9, 1) == 743.4
    flops = kind.frame_flops(s)
    parts = {k: round(v / 1e12, 3) for k, v in flops.items()}
    assert parts == {"projections": 27.037, "indexer": 2.813,
                     "sparse_attention": 10.308, "dense_mlp": 7.422,
                     "experts": 7.628, "head": 0.0, "total": 55.208}
    assert round(flops["total"] / s["seq"] / 1e9, 2) == 3.37  # a token
    # every causal key instead of the selected ones would be 4.3 times it
    causal = s["seq"] * (s["seq"] + 1) // 2
    assert round(causal / kind._selected_pairs(s), 1) == 4.3
    assert kind.sparse_attention_work(s)["flops"] == flops["sparse_attention"]
    # the mark finds the scoring and selecting ops, so the work is the causal
    # scores alone: the indexer's projections are in frame_flops only
    assert round(kind.indexer_work(s)["flops"] / 1e12, 3) == 2.199
    assert kind.indexer_work(s)["flops"] == 2 * 16384 * 16385 * 32 * 128
    assert kind.held_experts_work(s)["flops"] == flops["experts"]
    marks = kind.marks(s)
    assert marks["sparse_attention"]["names"] == ["nns_latent_sparse_attention"]
    assert marks["indexer"] == {"names": ["nns_index_select"],
                                "dims": [[32, 16384]]}
    # no mark names what a loop carries whole (a chunk's tokens, the pairs,
    # a chunk's choice), nor the value up-projection's [512, 16384] weight
    for carried in ([8192, 6144], [65536], [8192, 8], [512, 16384]):
        assert not any(carried == dims for mark in marks.values()
                       for dims in mark["dims"]), carried
    assert [65536, 6144] in marks["held_experts"]["dims"]


def test_the_traffic_is_two_clients_of_16k_windows():
    mix = manifest.load_traffic("ctx2x16k_saturated")
    assert (mix["kind"], mix["streams"], mix["inflight"], mix["frame_pool"],
            mix["warm_rounds"]) == ("token_windows", 2, 2, 4, 3)
    assert mix["check_frames"] >= 4
    cell = manifest.find(MAN["workloads"], CELL, "cell")
    assert cell["chips"] == 1 and cell["traffic"] == "ctx2x16k_saturated"


def test_the_first_client_carries_and_the_window_holds_both_clients_pushes():
    """Two clients are half the frames each, and the one whose thread
    carries the rounds reads one round where the other reads two: p50 lies
    between the two only while the window counts as many frames of each.
    The pipeline starts its sources last-added first, so the carrier is
    client 0, whose label the demux hands out first: client 1 is released
    after the round's last label, which is where the window opens."""
    import statistics

    import jax.numpy as jnp
    import numpy as np

    from benchmark.traffic_kinds import token_windows
    from nnstreamer_tpu.backends.jax_backend import JaxModel
    from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

    mix = manifest.load_traffic("ctx2x16k_saturated")
    seq, vocab, streams = 32, 19360, mix["streams"]
    rng = np.random.default_rng(38)
    params = {"embed": rng.standard_normal((vocab, 16), np.float32),
              "mix": rng.standard_normal((2048, 2048), np.float32) / 45}

    def apply(params, ids):
        x = jnp.tile(params["embed"][ids].mean(axis=1), (1, 128))
        # a round of tens of ms, as nothing beside the chip's 1.5 s: in a
        # round of a few ms the carrier's thread keeps the interpreter lock
        # from the other client for a round and more, and the roles swap
        for _ in range(48):
            x = jnp.tanh(x @ params["mix"])
        return x[:, :16] @ params["embed"].T

    model = JaxModel(
        apply=apply, params=params,
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.int32,
                                             shape=(streams, seq))),
        output_spec=TensorsSpec.of(TensorSpec(dtype=np.float32,
                                              shape=(streams, vocab))))
    res = token_windows.run(mix, model, {}, SimpleNamespace(
        frame_shape=lambda sizes: (seq,)), {"vocab_size": vocab}, 38, 0.5)
    assert res.drained and res.window["attempted"] == res.window["arrived"]
    lat = [[(got - put) / 1e6 for put, got in zip(puts, gots)
            if res.t0_ns <= put < res.t1_ns]
           for puts, gots in zip(res.push_ns, res.sink_ns)]
    assert statistics.median(lat[0]) < 0.75 * statistics.median(lat[1])
    # the round that closes the warm-up released both clients: neither's
    # next push is stamped before the window opens
    warm = mix["warm_rounds"]
    for puts, gots in zip(res.push_ns, res.sink_ns):
        after = [put for put in puts if put >= gots[warm - 1]]
        assert after and min(after) >= res.t0_ns


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_lower_precision_control_comes_out_not_correct(seed):
    sound, control = rehearse(seed), rehearse(seed, "--control")
    assert sound.line["correct"] is True
    assert control.line["correct"] is False
    assert (control.line["compared"]["logit_err"]["value"]
            > 1.5 * sound.line["compared"]["logit_err"]["value"])


def test_the_weights_are_a_checkpoints_and_the_routers_are_decisive():
    """The pytree ``glm_dsa.build`` takes; a token's 8 drawn experts stand
    out of the router's 256 (here 4 of 16) whatever the small bias says."""
    import numpy as np

    s = kind.sizes(CFG, rehearsal=True)
    w = kind.init_weights(s, 38)
    assert [("indexer" in p, "moe" in p) for p in w["layers"]] == [
        (True, False), (True, True)] + [(False, True)] * 3
    moe = w["layers"][1]["moe"]
    assert moe["w_in"].shape[0] == 4 and moe["router"].shape[-1] == 16
    bias = np.asarray(moe["bias"], np.float32)
    assert bias.shape == (16,) and np.abs(bias).max() > 0
    scores = (np.asarray(w["embed"], np.float32)
              @ np.asarray(moe["router"], np.float32))
    ranked = np.sort(scores, axis=-1)[:, ::-1]
    k = s["num_experts_per_tok"]
    assert (ranked[:, k - 1] - ranked[:, k]).min() > 10 * np.abs(bias).max()


def test_readers_that_find_nothing_to_read_say_nothing():
    empty = SimpleNamespace(slices=[], kind=kind, sizes=kind.sizes(CFG),
                            notes={}, chips=1, frames_per_step=2, peak=None)
    other_kind = SimpleNamespace(slices=[object()], kind=SimpleNamespace(),
                                 sizes={}, notes={}, chips=1,
                                 frames_per_step=2, peak=None)
    for read in (glm_shares.sparse_attention_roofline,
                 glm_shares.indexer_roofline,
                 glm_shares.held_experts_roofline):
        assert read(empty) is None and read(other_kind) is None


def test_the_new_metrics_list_the_new_cell_alone():
    per_layer = {m["name"]: m for m in MAN["per_layer"]}
    for name in ("sparse_attention_roofline", "indexer_roofline",
                 "held_experts_roofline"):
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["layer"] == "kernels"
        assert per_layer[name]["moves"] == "frames_per_s"
    assert len(MAN["workloads"]) <= 3
    assert len(json.dumps(MAN)) < 64 * 1024
