"""The trace reduction on hand-built events: busy union, idle share, the
model executable's time, marks by label (an op's name, an array's trailing
dims), the longest gaps; and on two recorded chip traces."""

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
SCORES = {"attention": {"dims": [[1369, 1369]]}}


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
    s = tr.reduce_device(device(steps=3), SCORES)
    assert s.steps == 3
    assert s.window_ns == pytest.approx(300 * MS)
    # each step: ops cover [0, 90) and [94, 96) of 100 ms
    assert s.busy_ns == pytest.approx(3 * 92 * MS)
    assert s.model_ns == pytest.approx(3 * 90 * MS)
    assert s.marked_ns == {"attention": pytest.approx(3 * 40 * MS)}


@pytest.mark.parametrize("mark,ms", [
    ({"names": ["fusion"]}, 40),                      # by the op's name
    ({"dims": [[1369, 1369]]}, 40),                   # by an array it holds
    ({"names": ["fusion"], "dims": [[1369, 1369]]}, 40),   # both: counted once
    ({"names": ["dot", "tail"]}, 60),                 # several prefixes
    ({"names": ["fusion"], "dims": [[1369, 1024]]}, 90),   # either is enough
    ({"names": ["usion"]}, None),                     # a prefix, not a part
    ({"dims": [[729, 729]]}, None),
    ({}, None),
])
def test_a_mark_is_an_ops_name_or_an_arrays_trailing_dims(mark, ms):
    s = tr.reduce_device(device(steps=3), {"part": mark})
    if ms is None:  # a label no op carries is left out, not 0
        assert s.marked_ns == {}
    else:
        assert s.marked_ns == {"part": pytest.approx(3 * ms * MS)}


def test_two_labels_on_one_trace_and_a_label_no_op_carries():
    marks = {"attention": {"names": ["nns_fused_attention"],
                           "dims": [[1369, 1369]]},
             "matmul": {"names": ["dot"]},
             "experts": {"names": ["grouped_matmul"], "dims": [[256, 512]]}}
    s = tr.reduce_device(device(steps=3), marks)
    assert s.marked_ns == {"attention": pytest.approx(3 * 40 * MS),
                           "matmul": pytest.approx(3 * 50 * MS)}
    kind = SimpleNamespace(
        marks=lambda sizes: marks,
        attention_work=lambda sizes: {"flops": 197e12 * 0.004, "bytes": 1.0},
        matmul_work=lambda sizes: {"flops": 1.0, "bytes": 819e9 * 0.04},
        experts_work=lambda sizes: {"flops": 1.0, "bytes": 1.0})
    ctx = SimpleNamespace(slices=[s], kind=kind, sizes={}, chips=1, notes={},
                          frames_per_step=1, peak=peaks.peak_for("TPU v5 lite"))
    # 4 ms of FLOPs over 40 ms, 40 ms of bytes over 50 ms
    assert device_trace.roofline(ctx, "attention") == pytest.approx(10.0)
    assert device_trace.roofline(ctx, "matmul") == pytest.approx(80.0)
    assert ctx.notes == {"attention_bound": "compute", "matmul_bound": "memory"}
    # marked by the kind, carried by no op of this trace: nothing, never 0
    assert device_trace.roofline(ctx, "experts") is None
    # no such mark, or a mark with no work function: nothing, and no raise
    assert device_trace.roofline(ctx, "router") is None
    del kind.matmul_work
    assert device_trace.roofline(ctx, "matmul") is None


def test_idle_share_and_gaps():
    s = tr.reduce_device(device(steps=3), SCORES)
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
    assert s.marked_ns == {}
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
    (s,) = tr.reduce_trace(devs, {"scores": {"dims": [[9, 9]]},
                                  "matmul": {"names": ["dot"]}})
    assert s.steps == 2 and s.window_ns == pytest.approx(200e3)
    assert s.busy_ns == pytest.approx(180e3)
    assert s.model_ns == pytest.approx(180e3)
    assert s.marked_ns == {"scores": pytest.approx(80e3),
                           "matmul": pytest.approx(100e3)}
    assert s.idle_gaps[0] == ("after:dot bf16[2,9,64]", pytest.approx(10e-6))


def recorded(fixture):
    """The device lines of a recorded chip trace, trimmed to the first steps
    (``fixtures/``; its ``source`` says which run)."""
    import gzip
    import json

    path = os.path.join(os.path.dirname(__file__), "fixtures", fixture)
    with gzip.open(path, "rt", encoding="utf-8") as f:
        fx = json.load(f)
    names = fx["names"]

    def ops(rows):
        return [tr.Op(tr.short_name(names[i]), float(s), float(d), names[i])
                for i, s, d in rows]

    return tr.DeviceTrace("/device:TPU:0", ops(fx["modules"]), ops(fx["ops"]))


def test_the_reduction_on_a_recorded_trace_of_the_plain_path():
    """What the chip wrote for a tower that has no cell (PR 30's first,
    ViT-H/14 at 378 x 378 under 48 cameras; its cell went out under the
    memory floor) while attention was XLA's: the recorded case of marks by
    dims.  The first run of the model is cut by the trace's start and left
    out; three whole steps of 432.4 ms follow, a period of about 462 ms;
    attention's three fusions (the ops with a 729 x 729 operand or result)
    take 30.5 % of the device time."""
    dev = recorded("no_cell.vit_h14_378_plain_attention.trace.json.gz")
    sizes = {"image_size": 378, "patch": 14, "d_model": 1280,
             "n_heads": 16, "n_layers": 32, "num_classes": 1000}
    runs = tr.model_runs(dev.modules)
    assert {m.name for m in runs} == {"jit_flat_fn"} and len(runs) == 5
    assert runs[0].dur_ns < 0.9 * runs[1].dur_ns            # the cut one
    s = tr.reduce_device(dev, vit.marks(sizes))
    assert s.steps == 3
    assert s.model_ns / s.steps == pytest.approx(432.4e6, rel=0.002)
    assert s.window_ns / s.steps == pytest.approx(462e6, rel=0.03)
    assert s.marked_ns["attention"] / s.model_ns == pytest.approx(0.305, abs=0.003)
    assert 0.03 < 1 - s.busy_ns / s.window_ns < 0.10
    assert s.busy_ns <= s.model_ns * 1.001
    assert s.device_ops[0][0] == "convert_reduce_fusion (f32[48,729], bf16[48,729,1280])"
    marked = [n for n, _ in s.device_ops if "729,729" in n]
    assert marked == ["fusion (bf16[48,16,729], bf16[48,16,729,729])"]
    assert all(name.startswith("after:") for name, _ in s.idle_gaps)
    assert s.idle_gaps[0][1] == pytest.approx(0.03, abs=0.02)
    ctx = SimpleNamespace(slices=[s], kind=vit, chips=1, frames_per_step=48,
                          peak=peaks.peak_for("TPU v5 lite"), notes={},
                          sizes=sizes)
    assert device_trace.step_mfu(ctx) == pytest.approx(56.76, abs=0.1)
    assert device_trace.attention_roofline(ctx) == pytest.approx(16.1, abs=0.1)


CELL_SIZES = {"image_size": 384, "patch": 16, "d_model": 1536, "n_heads": 16,
              "n_layers": 40, "num_classes": 1000}
# (ledger, PR 32, ``breakdown.device_ops`` of siglip2_gopt16_384.mux48: the
# ten ops that took most time, seconds over 11 traced steps), under the
# names the benchmark gives them (PERF.md section 5)
PR32_OPS = [
    ("convert_reduce_fusion", "(f32[48,576]{1,0}, bf16[48,576,1536]{2,1,0})", 1.64536463),
    ("convolution_add_fusion", "bf16[48,576,6144]{2,1,0}", 1.222316919),
    ("convolution_add_fusion", "bf16[48,576,4608]{2,1,0}", 0.948587863),
    ("nns_fused_attention", "bf16[48,576,1536]{2,1,0}", 0.422850018),
    ("convert_reduce_fusion", "f32[48,576]{1,0}", 0.069776077),
    ("reshape", "bf16[48,24,16,24,16,3]{5,4,3,2,1,0}", 0.058055718),
    ("copy", "bf16[48,24,24,16,16,3]{5,4,3,2,1,0}", 0.036391841),
    ("convert_reduce_fusion", "f32[48,1000]{1,0}", 0.005159322),
    ("copy", "bf16[48,576,768]{2,1,0}", 0.002727411),
    ("copy-done", "bf16[48,576,1536]{2,1,0}", 0.001222707),
]


def test_attention_roofline_reads_the_kernel_by_its_name():
    """PR 32's program as the ledger's breakdown has it, built by hand: 11
    whole steps of the ten ops back to back, a 30 ms gap after each.  No op
    holds a ``[..., 576, 576]`` array, so the mark by dims alone (the
    benchmark before PR 33) reads nothing; by the kernel's name the share is
    3.914 TFLOP over 197 TFLOP/s = 19.87 ms over 38.44 ms a step."""
    steps = 11
    modules, ops, t = [], [], 0.0
    for i in range(steps + 2):
        start = t
        for j, (root, shape, total_s) in enumerate(PR32_OPS):
            text = f"%{root}.{7 * i + j} = {shape} fusion(bf16[48,576,4608]{{2,1,0}} %p)"
            ops.append(tr.Op(tr.short_name(text), t, total_s / steps * 1e9, text))
            t += total_s / steps * 1e9
        modules.append(tr.Op("jit_flat_fn", start, t - start))
        t += 30 * MS
    dev = tr.DeviceTrace("/device:TPU:0", modules, ops)
    s = tr.reduce_device(dev, vit.marks(CELL_SIZES))
    assert s.steps == steps
    assert s.marked_ns == {"attention": pytest.approx(0.422850018e9)}
    assert s.device_ops[3] == ("nns_fused_attention bf16[48,576,1536]",
                               pytest.approx(0.422850018))
    ctx = SimpleNamespace(slices=[s], kind=vit, chips=1, frames_per_step=48,
                          peak=peaks.peak_for("TPU v5 lite"), notes={},
                          sizes=CELL_SIZES)
    assert device_trace.attention_roofline(ctx) == pytest.approx(51.69, abs=0.05)
    assert ctx.notes["attention_bound"] == "compute"
    assert device_trace.step_mfu(ctx) == pytest.approx(84.38, abs=0.05)
    assert device_trace.device_idle_pct(ctx) == pytest.approx(
        100 * 30 / (30 + 4412.452506 / steps), abs=0.01)
    by_dims = tr.reduce_device(dev, {"attention": {"dims": [[576, 576]]}})
    assert by_dims.marked_ns == {}
    ctx.slices = [by_dims]
    assert device_trace.attention_roofline(ctx) is None


def test_the_reduction_on_a_recorded_trace_of_the_cell():
    """What the chip wrote for ``siglip2_gopt16_384.mux48`` in PR 33's first
    traced run: three whole steps of 401.18 ms every 432.5 ms; attention is
    ``nns_fused_attention``, 40 calls a step, 38.44 ms of it, marked by name
    (no op holds a 576 x 576 array): 51.68 % of its roofline."""
    dev = recorded("siglip2_gopt16_384.mux48.trace.json.gz")
    runs = tr.model_runs(dev.modules)
    assert {m.name for m in runs} == {"jit_flat_fn"} and len(runs) == 5
    assert runs[0].dur_ns < 0.9 * runs[1].dur_ns            # the cut one
    s = tr.reduce_device(dev, vit.marks(CELL_SIZES))
    assert s.steps == 3
    assert s.model_ns / s.steps == pytest.approx(401.18e6, rel=0.0005)
    assert s.window_ns / s.steps == pytest.approx(432.5e6, rel=0.001)
    assert s.marked_ns["attention"] / s.steps == pytest.approx(38.44e6, rel=0.0005)
    kernel = [o for o in dev.ops if o.name.startswith("nns_fused_attention")]
    assert len(kernel) == 3 * 40 + 11                 # and 11 of the cut run
    assert "custom-call" in kernel[0].text and "576,576" not in kernel[0].text
    assert tr.reduce_device(dev, {"attention": {"dims": [[576, 576]]}}).marked_ns == {}
    assert [n for n, _ in s.device_ops[:4]] == [
        "convert_reduce_fusion (f32[48,576], bf16[48,576,1536])",
        "convolution_add_fusion bf16[48,576,6144]",
        "convolution_add_fusion bf16[48,576,4608]",
        "nns_fused_attention bf16[48,576,1536]"]
    assert s.idle_gaps[0][1] == pytest.approx(0.0417, abs=0.001)
    ctx = SimpleNamespace(slices=[s], kind=vit, chips=1, frames_per_step=48,
                          peak=peaks.peak_for("TPU v5 lite"), notes={},
                          sizes=CELL_SIZES)
    assert device_trace.step_mfu(ctx) == pytest.approx(84.376, abs=0.01)
    assert device_trace.attention_roofline(ctx) == pytest.approx(51.68, abs=0.01)
    assert device_trace.device_idle_pct(ctx) == pytest.approx(7.25, abs=0.01)


def test_shares_from_a_slice():
    """Work from shapes over trace time: with the device taking exactly the
    least time the share reads 100, with more time less, never more."""
    sizes = {"image_size": 518, "patch": 14, "d_model": 1024, "n_heads": 16,
             "n_layers": 24, "num_classes": 1000}
    peak = peaks.peak_for("TPU v5 lite")
    step_s = 32 * vit.frame_flops(sizes)["total"] / peak.flops_per_s
    att_s = 32 * vit.attention_work(sizes)["flops"] / peak.flops_per_s

    def ctx(model_s, marked_s):
        marked = {"attention": 4 * marked_s * 1e9} if marked_s else {}
        s = tr.Slice(steps=4, window_ns=0, busy_ns=0, model_ns=4 * model_s * 1e9,
                     marked_ns=marked, device_ops=[], idle_gaps=[])
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
