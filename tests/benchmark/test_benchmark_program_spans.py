"""The reader ``program_spans`` on hand-built ring records (as
``test_benchmark_trace.py`` does for device ops), and through ``run_cell``
in ``--rehearsal`` on the CPU: gap arithmetic, self time under nesting, a
span that straddles the device's completion, a round handed from one thread
to another, dropped records, and the mean of a bimodal gap."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, run as bench_run  # noqa: E402
from benchmark.layer_metrics import program_spans as ps  # noqa: E402

MAN = manifest.load_manifest(ROOT)
SEVEN = ["host_gap_ms_mean", "gap_return_ms_mean", "gap_collect_ms_mean",
         "gap_invoke_ms_mean", "gap_unnamed_pct", "ticket_wait_ms_p50",
         "compile_s"]
STEP = 500.0          # the device's step, ms
MS = 1_000_000


class Ring:
    """Records in ``obs/flight.py``'s layout, times given in ms."""

    def __init__(self):
        self.records, self.sid = [], 0

    def span(self, name, cat, tid, start, end, parent=0, **args):
        self.sid += 1
        self.records.append(("X", round(start * MS), round((end - start) * MS),
                             tid, name, cat, 0, self.sid, parent, args or None))
        return self.sid

    def element(self, name, cls, tid, start, end, parent):
        return self.span(name, "dispatch", tid, start, end, parent, element=cls)

    def round(self, k, tid, t_in, inv_end, wait_until=None, dev_early=0.0):
        """Round ``k`` on thread ``tid``: in at ``t_in``, the invoke's last
        5 ms end at ``inv_end``, the device is done ``STEP`` later; then 2 ms
        of unbatch, 3 of decoder, 1 of sink, and 0.5 of unwinding.  Returns
        when the device was done."""
        done = inv_end + STEP
        conv = self.element("conv", "TensorConverter", tid, t_in, done + 6.5, 99)
        mux = self.element("mux", "TensorMux", tid, t_in + 0.1, done + 6.4, conv)
        if wait_until is not None:
            self.span("mux.ticket_wait", "stage", tid, t_in + 0.2, wait_until,
                      mux, ticket=k)
        filt = self.element("f", "TensorFilter", tid, inv_end - 6, done + 6.2, mux)
        self.span("f.invoke", "stage", tid, inv_end - 5, inv_end, filt, round=k)
        split = self.element("split", "TensorSplit", tid, inv_end + 0.5,
                             done + 6, filt)
        self.element("unbatch", "TensorUnbatch", tid, inv_end + 0.6, done + 2, split)
        self.element("dec", "TensorDecoder", tid, done + 2, done + 5, split)
        self.element("out", "TensorSink", tid, done + 5, done + 6, split)
        self.span("device_exec", "device", "device:tpu", inv_end - 5,
                  done - dev_early, element="f", round=k)
        return done


def same_thread(gaps, dev_early=0.0):
    """Rounds 1..n on one thread, the gap before round ``k+1`` ``gaps[k-1]``
    ms long: 6 of return path, 0.5 of unwinding, 0.5 between the pushes (no
    span), the rest on the way in, the invoke's 5 at its end."""
    ring, done, inv_end = Ring(), None, 100.0
    for k, gap in enumerate([None] + list(gaps), start=1):
        if gap is not None:
            inv_end = done + gap
        t_in = 50.0 if gap is None else done + 7.0
        done = ring.round(k, "src:cam0", t_in, inv_end, dev_early=dev_early)
    return ring.records


def mean(rounds_, key):
    return sum(r[key] for r in rounds_) / len(rounds_) / MS


def part(rounds_, stage):
    return sum(r["parts"].get(stage, 0.0) for r in rounds_) / len(rounds_) / MS


def test_gap_arithmetic_and_self_time_under_nesting():
    (r,) = ps.rounds(same_thread([30.0]), 0, 10**12)
    assert r["round"] == 2
    assert r["gap_ns"] == 30 * MS and r["period_ns"] == 530 * MS
    # unbatch 2 + decoder 3 + sink 1 after the device is done; the split
    # that holds them has no time of its own
    assert r["parts"]["return"] == pytest.approx(6 * MS)
    assert r["parts"]["invoke"] == pytest.approx(5 * MS)
    # 0.5 of unwinding through filter, mux and converter, then the way in
    assert r["parts"]["collect"] == pytest.approx((0.5 + 30 - 7 - 5) * MS)
    assert r["unnamed"] == pytest.approx(0.5 * MS)
    assert r["ticket_wait_ns"] == 0
    assert sum(r["parts"].values()) + r["unnamed"] == pytest.approx(r["gap_ns"])


@pytest.mark.parametrize("early", [0.0, 1.5, 4.0])
def test_a_span_that_straddles_the_devices_completion_is_split_at_it(early):
    """``tensor_unbatch`` blocks on the device for 500 ms and works for 2:
    only what lies after ``device_exec``'s end is the gap's."""
    (r,) = ps.rounds(same_thread([30.0], dev_early=early), 0, 10**12)
    assert r["gap_ns"] == pytest.approx((30 + early) * MS)
    assert r["parts"]["return"] == pytest.approx((6 + early) * MS)
    by_class = ps.rounds(same_thread([30.0], dev_early=early), 0, 10**12,
                         key=lambda s: s.args.get("element"))[0]["parts"]
    assert by_class["TensorUnbatch"] == pytest.approx((2 + early) * MS)
    assert by_class["TensorDecoder"] == pytest.approx(3 * MS)
    assert "TensorSplit" not in by_class or by_class["TensorSplit"] == 0


def test_two_threads_the_ticket_handed_over():
    ring = Ring()
    done = ring.round(1, "src:cam0", 50.0, 100.0)
    # round 2 is collected on another thread during round 1's step and
    # waits for the ticket until round 1's mux lets go of it
    hand = done + 6.45
    ring.round(2, "src:cam7", 150.0, done + 30.0, wait_until=hand)
    (r,) = ps.rounds(ring.records, 0, 10**12)
    assert r["gap_ns"] == 30 * MS
    assert r["ticket_wait_ns"] == pytest.approx((hand - 150.2) * MS)
    assert r["parts"]["return"] == pytest.approx(6 * MS)
    assert r["parts"]["invoke"] == pytest.approx(5 * MS)
    # round 1's thread until the hand-over (0.45 of its unwinding), round
    # 2's from there: nothing between them is without a span
    assert r["unnamed"] == pytest.approx(0, abs=1)
    assert r["parts"]["collect"] == pytest.approx((30 - 6 - 5) * MS)
    # the other thread's wait is not the mux's work
    assert ps.rounds(ring.records, 0, 10**12, key=lambda s: s.args.get(
        "element"))[0]["parts"]["TensorMux"] < 20 * MS


def test_bimodal_gaps_read_as_their_mean_not_their_median():
    rounds_ = ps.rounds(same_thread([21.0, 21.0, 21.0, 39.0]), 0, 10**12)
    assert [r["gap_ns"] / MS for r in rounds_] == [21, 21, 21, 39]
    assert mean(rounds_, "gap_ns") == pytest.approx(25.5)
    assert part(rounds_, "return") == pytest.approx(6)
    assert part(rounds_, "collect") == pytest.approx(25.5 - 11.5)


def test_only_rounds_dispatched_in_the_window_count():
    records = same_thread([30.0, 30.0, 30.0])
    starts = sorted(r[1] for r in records if r[4] == "f.invoke")
    inside = ps.rounds(records, starts[1], starts[3])
    assert [r["round"] for r in inside] == [2, 3]
    assert ps.rounds(records, starts[3] + 1, 10**12) == []


def ctx_over(monkeypatch, records, dropped=0):
    from nnstreamer_tpu.obs import spans

    monkeypatch.setattr(spans, "snapshot", lambda: list(records))
    monkeypatch.setattr(spans, "recorder_stats", lambda: {
        "capacity": 16384, "threads": 2, "records": len(records),
        "dropped": dropped})
    return SimpleNamespace(result=SimpleNamespace(t0_ns=0, t1_ns=10**12),
                           notes={})


def test_metrics_sum_to_the_gap(monkeypatch):
    ctx = ctx_over(monkeypatch, same_thread([21.0, 39.0, 21.0, 39.0]))
    gap = ps.host_gap_ms_mean(ctx)
    assert gap == pytest.approx(30.0)
    named = (ps.gap_return_ms_mean(ctx) + ps.gap_collect_ms_mean(ctx)
             + ps.gap_invoke_ms_mean(ctx))
    assert named + gap * ps.gap_unnamed_pct(ctx) / 100 == pytest.approx(gap)
    assert ps.gap_unnamed_pct(ctx) == pytest.approx(100 * 0.5 / 30)
    assert ps.ticket_wait_ms_p50(ctx) == 0.0
    assert ctx.notes["span_dropped"] == 0 and ctx.notes["gap_rounds"] == 4
    assert ctx.notes["gap_ms"] == [21.0, 39.0, 21.0, 39.0]
    assert ctx.notes["round_period_ms_mean"] == pytest.approx(530.0)


@pytest.mark.parametrize("name", SEVEN[:6])
def test_dropped_records_or_no_device_exec_read_as_nothing(monkeypatch, name):
    records = same_thread([30.0, 30.0])
    ctx = ctx_over(monkeypatch, records, dropped=3)
    assert getattr(ps, name)(ctx) is None
    assert ctx.notes["span_dropped"] == 3
    no_exec = [r for r in records if r[4] != "device_exec"]
    assert getattr(ps, name)(ctx_over(monkeypatch, no_exec)) is None
    # a program that records nothing (this PR's parent): nothing, no raise
    assert getattr(ps, name)(ctx_over(monkeypatch, [])) is None


def test_compile_s_is_the_histograms_sum():
    from nnstreamer_tpu.obs.device import record_compile
    from nnstreamer_tpu.obs.metrics import REGISTRY

    before = ps.compile_s(None) or 0.0
    record_compile(object(), "k1", "miss", 2_500_000_000)
    record_compile(object(), "k2", "persist_hit", 500_000_000)
    record_compile(object(), "k1", "hit")
    assert ps.compile_s(None) == pytest.approx(before + 3.0)
    assert REGISTRY.get("nnstpu_compile_seconds") is not None


def rehearse(trace, monkeypatch, tmp_path):
    # a trace directory of this test's own: the rehearsal tests' CLI run
    # traces the same cell from the same checkout, perhaps at the same time
    monkeypatch.setattr(bench_run, "WORK_DIR", str(tmp_path))
    cell = MAN["workloads"][0]["name"]
    args = bench_run.parse_args([
        "--workload", cell, "--seed", str(2**31 + 29), "--seconds", "0.5",
        "--trace", str(trace), "--rehearsal"])
    return bench_run.run_cell(args)


def test_rehearsal_traced_run_reports_the_seven(monkeypatch, tmp_path):
    from nnstreamer_tpu.obs import hooks, spans

    code, report = rehearse(1, monkeypatch, tmp_path)
    assert code == 3 and report.line["correct"] is True
    metrics = report.line["metrics"]
    assert set(SEVEN) <= set(metrics)
    gap = metrics["host_gap_ms_mean"]["value"]
    assert gap > 0
    named = sum(metrics[n]["value"] for n in SEVEN[1:4])
    unnamed = gap * metrics["gap_unnamed_pct"]["value"] / 100
    assert named + unnamed == pytest.approx(gap, abs=1e-6)
    assert metrics["compile_s"]["value"] > 0
    for name in SEVEN:
        spec = manifest.find(MAN["per_layer"], name, "metric")
        assert metrics[name]["unit"] == spec["unit"]
    # the lanes went with the pipeline; the records stay for the reader
    assert not hooks.enabled and not spans.enabled
    assert spans.recorder_stats()["dropped"] == 0


def test_rehearsal_untraced_run_records_nothing(monkeypatch, tmp_path):
    from nnstreamer_tpu.obs import hooks, spans

    code, report = rehearse(0, monkeypatch, tmp_path)
    assert code == 3 and report.line["correct"] is True
    assert spans.snapshot() == [] and not hooks.enabled
    assert set(report.line["metrics"]) == {m["name"] for m in MAN["end_to_end"]}
