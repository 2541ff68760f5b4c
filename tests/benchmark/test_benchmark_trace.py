"""The trace reduction on hand-built events: busy union, idle share, the
model executable's time, the T x T classifier, the longest gaps."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import peaks, trace_reduce as tr  # noqa: E402
from benchmark.layer_metrics import device_trace  # noqa: E402
from benchmark.model_kinds import vit  # noqa: E402

MS = 1e6


def device(steps=3, period=100, lead=5):
    """``steps + 2`` runs of ``jit_model`` every ``period`` ms (the first is
    taken as cut by the trace's start and left out): each a 90 ms
    module holding a 50 ms matmul, a 30 ms fusion over T x T scores (the two
    overlap by 10 ms, as two cores' ops may) and a 10 ms tail; a 2 ms copy
    sits in each gap; a warm-up module of another name comes first."""
    modules = [tr.Op("jit_warmup", 0, 2 * MS)]
    ops = [tr.Op("warm.1", 0, 2 * MS, "f32[8,8]")]
    for i in range(steps + 2):
        t = (lead + i * period) * MS
        modules.append(tr.Op("jit_model", t, 90 * MS))
        ops += [
            tr.Op("dot.1", t, 50 * MS, "bf16[32,1369,1024]{2,1,0} dot(...)"),
            tr.Op("fusion.7", t + 40 * MS, 40 * MS,
                  "bf16[32,16,1369,1369]{3,2,1,0} fusion(bf16[32,16,1369,64])"),
            tr.Op("tail.2", t + 80 * MS, 10 * MS, "f32[32,1000]"),
            tr.Op("copy.3", t + 94 * MS, 2 * MS, "u8[32,518,518,3]"),
        ]
        modules.append(tr.Op("jit_copy", t + 94 * MS, 2 * MS))
    return tr.DeviceTrace("/device:TPU:0", modules, ops)


@pytest.mark.parametrize("intervals,want", [
    ([], 0), ([(0, 10)], 10), ([(0, 10), (5, 20)], 20),
    ([(0, 10), (20, 30)], 20), ([(0, 30), (5, 10), (10, 12)], 30),
    ([(20, 30), (0, 10), (9, 21)], 30), ([(5, 5), (7, 6)], 0),
])
def test_union_of_intervals(intervals, want):
    assert tr.union_ns(intervals) == want


def test_model_runs_are_the_module_with_most_time():
    runs = tr.model_runs(device().modules)
    assert {m.name for m in runs} == {"jit_model"} and len(runs) == 5
    assert tr.model_runs([]) == []


def test_slice_covers_whole_steps_only():
    s = tr.reduce_device(device(steps=3), (1369, 1369))
    assert s.steps == 3
    assert s.window_ns == pytest.approx(300 * MS)
    # each step: ops cover [0, 90) and [94, 96) of 100 ms
    assert s.busy_ns == pytest.approx(3 * 92 * MS)
    assert s.model_ns == pytest.approx(3 * 90 * MS)
    assert s.marked_ns == pytest.approx(3 * 40 * MS)


def test_idle_share_and_gaps():
    s = tr.reduce_device(device(steps=3), (1369, 1369))
    ctx = SimpleNamespace(slices=[s])
    assert device_trace.device_idle_pct(ctx) == pytest.approx(8.0)
    names = [g[0] for g in s.idle_gaps]
    secs = [g[1] for g in s.idle_gaps]
    assert secs == sorted(secs, reverse=True)
    assert secs[0] == pytest.approx(0.004)
    assert set(names) == {"after:tail.2", "after:copy.3"}
    assert len(s.idle_gaps) == 6


def test_device_ops_ranked_by_summed_time():
    s = tr.reduce_device(device(steps=3), None)
    assert s.device_ops[0] == ("dot.1", pytest.approx(0.150))
    assert s.device_ops[1] == ("fusion.7", pytest.approx(0.120))
    assert s.marked_ns == 0
    assert len(tr.reduce_device(device(), None, top=2).device_ops) == 2


def test_the_first_run_is_left_out_as_cut():
    dev = device(steps=3)
    cut = dev.modules[1]._replace(start_ns=0.0, dur_ns=60 * MS)  # a cut run
    dev = dev._replace(modules=[dev.modules[0], cut] + dev.modules[2:])
    s = tr.reduce_device(dev, None)
    assert s.steps == 3 and s.model_ns == pytest.approx(3 * 90 * MS)


def test_fewer_than_two_runs_give_nothing():
    assert tr.reduce_device(device(steps=0), None) is None
    assert tr.reduce_trace([device(steps=0)]) == []
    assert len(tr.reduce_trace([device(), device(steps=0)])) == 1


@pytest.mark.parametrize("text,dims,want", [
    ("bf16[32,16,1369,1369]{3,2,1,0}", (1369, 1369), True),
    ("f32[1369,1369]", (1369, 1369), True),
    ("bf16[32,1369,1024]", (1369, 1369), False),
    ("bf16[32,16,729,729]", (1369, 1369), False),
    ("bf16[32,16,729,729]", (729, 729), True),
    ("bf16[32,1369,13690]", (1369, 1369), False),
    ("bf16[32,11369,1369]", (1369, 1369), False),
    ("bf16[4,4]{1369,1369}", (1369, 1369), False),
    ("%fusion = (bf16[2,1369,64], f32[2,16,1369,1369]) fusion(...)", (1369, 1369), True),
    ("", (1369, 1369), False),
])
def test_the_score_shape_classifier(text, dims, want):
    assert tr.has_trailing_dims(text, dims) is want


@pytest.mark.parametrize("hlo,want", [
    ("%fusion.66 = bf16[32,16,1369]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[32,16,"
     "1369,1369]{2,3,1,0:T(8,128)(2,1)} %gte.183), kind=kLoop",
     "fusion bf16[32,16,1369]"),
    ("%fusion.1602 = (bf16[32,16,1369]{2,1,0:T(8,128)(2,1)S(1)}, bf16[32,16,1369,"
     "1369]{2,3,1,0:T(8,128)(2,1)}) fusion(bf16[32,1369,16,64]{1,3,2,0} %b)",
     "fusion (bf16[32,16,1369], bf16[32,16,1369,1369])"),
    ("%convert_reduce_fusion.9 = f32[32,1369]{1,0} fusion(f32[4]{0} %a)",
     "convert_reduce_fusion f32[32,1369]"),
    ("jit_flat_fn(17109938503190111236)", "jit_flat_fn"),
])
def test_short_names_sum_over_layers(hlo, want):
    assert tr.short_name(hlo) == want


XSPACE = '''
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 90000000 }
    events { metadata_id: 3 offset_ps: 192000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 200000000 duration_ps: 90000000 }
    events { metadata_id: 1 offset_ps: 300000000 duration_ps: 90000000 }
  }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 100000000 duration_ps: 40000000 }
    events { metadata_id: 4 offset_ps: 140000000 duration_ps: 50000000 }
    events { metadata_id: 2 offset_ps: 200000000 duration_ps: 40000000 }
    events { metadata_id: 4 offset_ps: 240000000 duration_ps: 50000000 }
  }
  lines { name: "Async XLA Ops" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 100000000 duration_ps: 99000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_flat_fn(123)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = bf16[2,16,9,9]{3,2,1,0} fusion(bf16[2,9,16,4]{3,2,1,0} %q)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_dynamic_slice(7)" } }
  event_metadata { key: 4 value { id: 4 name: "%dot.2 = bf16[2,9,64]{2,1,0} dot(bf16[2,9,64]{2,1,0} %x)" } }
  event_metadata { key: 5 value { id: 5 name: "%copy-start.7 = (bf16[64,64]{1,0}) copy-start(bf16[64,64]{1,0} %c)" } }
}
planes { name: "/host:CPU" lines { name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5 } }
  event_metadata { key: 1 value { id: 1 name: "main" } } }
'''


def test_reading_an_xspace_as_the_chip_writes_it():
    """Device planes only; ``XLA Modules`` and ``XLA Ops`` only (the async
    copies overlap the ops and would hide every gap); names shortened, the
    HLO text kept for the shape classifier."""
    from jax.profiler import ProfileData

    devs = tr.from_profile(ProfileData.from_text_proto(XSPACE))
    assert [d.device for d in devs] == ["/device:TPU:0"]
    assert len(devs[0].modules) == 5 and len(devs[0].ops) == 4
    assert devs[0].ops[0].name == "fusion bf16[2,16,9,9]"
    (s,) = tr.reduce_trace(devs, (9, 9))
    assert s.steps == 2 and s.window_ns == pytest.approx(200e3)
    assert s.busy_ns == pytest.approx(180e3)
    assert s.model_ns == pytest.approx(180e3)
    assert s.marked_ns == pytest.approx(80e3)
    assert s.idle_gaps[0] == ("after:dot bf16[2,9,64]", pytest.approx(10e-6))


def recorded():
    """The device lines of a recorded chip trace, trimmed to the first steps
    (``fixtures/``; its ``source`` says which run)."""
    import gzip
    import json

    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "vit_h14_378.mux48.trace.json.gz")
    with gzip.open(path, "rt", encoding="utf-8") as f:
        fx = json.load(f)
    names = fx["names"]

    def ops(rows):
        return [tr.Op(tr.short_name(names[i]), float(s), float(d), names[i])
                for i, s, d in rows]

    return tr.DeviceTrace("/device:TPU:0", ops(fx["modules"]), ops(fx["ops"]))


def test_the_reduction_on_a_recorded_chip_trace():
    """What the chip wrote for ``vit_h14_378.mux48`` (PR 30's first tower,
    ViT-H/14 at 378 x 378; its cell went out under the memory floor and the
    trace stays as the reduction's recorded case): the first run of the
    model is cut by the trace's start and left out; three whole steps of
    432.4 ms follow, a period of about 462 ms; attention's three fusions (the
    ops with a 729 x 729 operand or result) take 30.5 % of the device time."""
    dev = recorded()
    runs = tr.model_runs(dev.modules)
    assert {m.name for m in runs} == {"jit_flat_fn"} and len(runs) == 5
    assert runs[0].dur_ns < 0.9 * runs[1].dur_ns            # the cut one
    s = tr.reduce_device(dev, (729, 729))
    assert s.steps == 3
    assert s.model_ns / s.steps == pytest.approx(432.4e6, rel=0.002)
    assert s.window_ns / s.steps == pytest.approx(462e6, rel=0.03)
    assert s.marked_ns / s.model_ns == pytest.approx(0.305, abs=0.003)
    assert 0.03 < 1 - s.busy_ns / s.window_ns < 0.10
    assert s.busy_ns <= s.model_ns * 1.001
    assert s.device_ops[0][0] == "convert_reduce_fusion (f32[48,729], bf16[48,729,1280])"
    marked = [n for n, _ in s.device_ops if "729,729" in n]
    assert marked == ["fusion (bf16[48,16,729], bf16[48,16,729,729])"]
    assert all(name.startswith("after:") for name, _ in s.idle_gaps)
    assert s.idle_gaps[0][1] == pytest.approx(0.03, abs=0.02)
    ctx = SimpleNamespace(slices=[s], kind=vit, chips=1, frames_per_step=48,
                          peak=peaks.peak_for("TPU v5 lite"), notes={},
                          sizes={"image_size": 378, "patch": 14, "d_model": 1280,
                                 "n_heads": 16, "n_layers": 32, "num_classes": 1000})
    assert device_trace.step_mfu(ctx) == pytest.approx(56.76, abs=0.1)
    assert device_trace.attention_roofline(ctx) == pytest.approx(16.1, abs=0.1)


def test_shares_from_a_slice():
    """Work from shapes over trace time: with the device taking exactly the
    least time the share reads 100, with more time less, never more."""
    sizes = {"image_size": 518, "patch": 14, "d_model": 1024, "n_heads": 16,
             "n_layers": 24, "num_classes": 1000}
    peak = peaks.peak_for("TPU v5 lite")
    step_s = 32 * vit.frame_flops(sizes)["total"] / peak.flops_per_s
    att_s = 32 * vit.attention_work(sizes)["flops"] / peak.flops_per_s

    def ctx(model_s, marked_s):
        s = tr.Slice(steps=4, window_ns=0, busy_ns=0, model_ns=4 * model_s * 1e9,
                     marked_ns=4 * marked_s * 1e9, device_ops=[], idle_gaps=[])
        return SimpleNamespace(slices=[s], kind=vit, sizes=sizes, chips=1,
                               frames_per_step=32, peak=peak, notes={})

    assert device_trace.step_mfu(ctx(step_s, att_s)) == pytest.approx(100.0)
    assert device_trace.step_mfu(ctx(2.5 * step_s, att_s)) == pytest.approx(40.0)
    c = ctx(step_s, 8 * att_s)
    assert device_trace.attention_roofline(c) == pytest.approx(12.5)
    assert c.notes["attention_bound"] == "compute"
    # nothing to read gives nothing, never 0
    assert device_trace.attention_roofline(ctx(step_s, 0.0)) is None
    empty = SimpleNamespace(slices=[])
    for fn in (device_trace.step_mfu, device_trace.attention_roofline,
               device_trace.device_idle_pct):
        assert fn(empty) is None
