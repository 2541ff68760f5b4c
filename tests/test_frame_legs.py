"""A frame's latency from inside the program: the ``pad_wait`` record of
every frame a collect pad, a queue or a dynbatch holds, the chain of ids
that takes a source frame to its round's ``device_exec`` and its stream's
sink (``collector.frame_legs``), and the host-stall lane's beat."""

import ctypes
import threading
import time

import numpy as np
import pytest

import nnstreamer_tpu as nns
from nnstreamer_tpu.backends.custom import (
    register_custom_easy, unregister_custom_easy)
from nnstreamer_tpu.buffer import Frame
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.graph.node import SourceNode
from nnstreamer_tpu.obs import collector, hooks, hoststall, spans
from nnstreamer_tpu.obs.metrics import REGISTRY
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

MS = 1_000_000
LATE = 3                 # the source that pushes 50 ms after the others
SINKS = {f"s{i}": f"out{i}" for i in range(4)}


class TimedSrc(SourceNode):
    """Pushes a frame at each of ``at_ms`` (from the shared start)."""

    def __init__(self, name, at_ms, start, shape=(4,)):
        super().__init__(name)
        self.at_ms, self.start_evt, self.shape = at_ms, start, shape

    def output_spec(self):
        return TensorsSpec.of(TensorSpec(dtype=np.float32, shape=self.shape))

    def frames(self):
        self.start_evt.wait(10)
        t0 = time.perf_counter()
        for k, at in enumerate(self.at_ms):
            time.sleep(max(0.0, at / 1e3 - (time.perf_counter() - t0)))
            yield Frame.of(np.full(self.shape, k, np.float32), pts=k)


@pytest.fixture
def listener():
    def on_dispatch(node, frame, outs, t_ns):
        pass

    hooks.connect("device_dispatch", on_dispatch)
    yield
    hooks.disconnect("device_dispatch", on_dispatch)


@pytest.fixture
def slow_filter():
    """custom-easy filters that take 10 ms a frame."""
    def slow(x):
        time.sleep(0.010)
        return x * 2

    for name, shape in (("slow44", (4, 4)), ("slow4", (4,)), ("slow24", (2, 4))):
        spec = TensorsSpec.of(TensorSpec(dtype=np.float32, shape=shape))
        register_custom_easy(name, slow, spec, spec)
    yield
    for name in ("slow44", "slow4", "slow24"):
        unregister_custom_easy(name)


def run_merged(late_ms=50):
    """4 sources -> 2 x (tensor_mux -> tensor_batch) -> tensor_merge ->
    filter -> unbatch -> demux -> 4 sinks.  Every source pushes two frames
    10 ms apart; source ``LATE`` starts ``late_ms`` after the others, so
    the others' second frames arrive before the first round is booked."""
    start = threading.Event()
    p = nns.Pipeline(name="frame_legs")
    merge = p.add(nns.make("tensor_merge", name="merge", mode="linear",
                           option="1", sync_mode="nosync"))
    for g in range(2):
        mux = p.add(nns.make("tensor_mux", name=f"mux{g}", sync_mode="nosync"))
        for i in range(2):
            s = 2 * g + i
            first = late_ms if s == LATE else 0
            p.link(p.add(TimedSrc(f"s{s}", (first, first + 10), start)),
                   f"mux{g}.sink_{i}")
        batch = p.add(nns.make("tensor_batch", name=f"batch{g}"))
        p.link(mux, batch)
        p.link(batch, f"merge.sink_{g}")
    filt = p.add(TensorFilter(framework="custom-easy", model="slow44", name="f"))
    unbatch = p.add(nns.make("tensor_unbatch", name="unbatch"))
    demux = p.add(nns.make("tensor_demux", name="demux"))
    p.link_chain(merge, filt, unbatch, demux)
    for i in range(4):
        p.link(f"demux.src_{i}", p.add(TensorSink(name=f"out{i}")))
    p.start()
    start.set()
    assert p.wait(timeout=30)
    p.stop()
    return spans.snapshot()


def by_cat(records, cat):
    return [r for r in records if r[5] == cat]


def end(r):
    return r[1] + r[2]


class TestMergedRounds:
    @pytest.fixture
    def records(self, listener, slow_filter):
        return run_merged()

    def test_every_frame_has_its_legs_and_they_sum_to_its_life(self, records):
        legs = collector.frame_legs(records, SINKS)
        assert len(legs) == 8 and spans.recorder_stats()["dropped"] == 0
        pushes = {(r[6], r[7]): r[1] for r in by_cat(records, "source")}
        sinks = {(r[6], r[4]): end(r) for r in by_cat(records, "dispatch")}
        for f in legs:
            assert f["push_ns"] == pushes[(f["trace_id"], f["span_id"])]
            total = (f["forward_ns"] + f["wait_ns"] + f["device_ns"]
                     + f["return_ns"])
            assert total == f["end_ns"] - f["push_ns"]
            # the sink's own span, under the round's trace id
            assert f["end_ns"] in [t for (_, name), t in sinks.items()
                                   if name == SINKS[f["source"]]]
            # the filter's 10 ms; forward is the threads' own work (a busy
            # machine stretches it, never the 45 ms the others waited)
            assert f["device_ns"] >= 10 * MS and 0 <= f["forward_ns"] < 30 * MS

    def test_the_late_pad_waits_nothing_the_others_until_it_came(self, records):
        legs = collector.frame_legs(records, SINKS)
        late = {f["round"]: f for f in legs if f["source"] == f"s{LATE}"}
        assert sorted(late) == [1, 2]
        for f in legs:
            if f["source"] == f"s{LATE}":
                assert f["wait_ns"] < 20 * MS
                continue
            # pushed before the late source's frame of its round, taken
            # when that one arrived: no constant, the records' own times
            gap = late[f["round"]]["push_ns"] - f["push_ns"]
            assert gap >= 45 * MS
            assert abs(f["wait_ns"] - gap) < 20 * MS

    def test_interleaved_arrivals_go_to_their_own_round_by_ids(self, records):
        """Frame 1 of three sources is on its pad before round 1 is booked:
        order of arrival would put it in round 1."""
        legs = collector.frame_legs(records, SINKS)
        pushes = sorted(r[1] for r in by_cat(records, "source")
                        if r[4] != f"s{LATE}.push")
        first_booking = min(end(r) for r in by_cat(records, "wait")
                            if r[4] == "merge.pad_wait")
        assert pushes[-1] < first_booking
        per_source = {}
        for f in legs:  # oldest push first
            per_source.setdefault(f["source"], []).append(f["round"])
        assert per_source == {f"s{i}": [1, 2] for i in range(4)}
        # a collector's waits of one ticket end at one booking, before the
        # round's enqueue; the merge's ticket is the coalesce record's too
        execs = {r[9]["round"]: r for r in records if r[4] == "device_exec"}
        tickets = {}
        for r in by_cat(records, "wait"):
            tickets.setdefault((r[4], r[9]["ticket"]), set()).add(end(r))
        assert len(tickets) == 6 and all(len(v) == 1 for v in tickets.values())
        for f in legs:
            (merged,) = [w for w in f["waits"] if w[0] == "merge.pad_wait"]
            assert merged[1] in ("sink_0", "sink_1")
        coalesced = {r[9]["ticket"] for r in by_cat(records, "coalesce")
                     if r[4] == "merge"}
        assert coalesced == {t for (name, t) in tickets if name.startswith("merge")}
        for k, dev in execs.items():
            assert max(tickets[("merge.pad_wait", k - 1)]) <= dev[1]

    def test_the_chain_survives_the_merge(self, records):
        merges = [r for r in by_cat(records, "coalesce") if r[4] == "merge"]
        muxes = {f"{r[6]:x}/{r[7]:x}" for r in by_cat(records, "coalesce")
                 if r[4].startswith("mux")}
        assert len(merges) == 2 and len(muxes) == 4
        for r in merges:
            assert len(r[9]["parents"]) == 2
            assert set(r[9]["parents"]) <= muxes
        # the round's device_exec hangs under the merge's span
        spans_of = {r[7] for r in merges}
        assert {r[8] for r in records if r[4] == "device_exec"} == spans_of

    def test_the_way_back_carries_the_rounds_trace(self, records):
        rounds = {r[6] for r in by_cat(records, "coalesce") if r[4] == "merge"}
        for i in range(4):
            outs = [r for r in by_cat(records, "dispatch") if r[4] == f"out{i}"]
            assert {r[6] for r in outs} - {0} == rounds  # 0: the EOS event

    def test_waterfall_prints_the_wait_with_its_frame(self, records):
        f = collector.frame_legs(records, SINKS)[0]
        text = spans.waterfall(spans.records_for_trace(f["trace_id"], records))
        assert ".pad_wait" in text and "pad sink_" in text


class TestQueue:
    def run(self, n=3):
        start = threading.Event()
        p = nns.Pipeline(name="queued")
        src = p.add(TimedSrc("s0", tuple(range(n)), start))
        q = p.add(nns.make("queue", name="q"))
        filt = p.add(TensorFilter(framework="custom-easy", model="slow4",
                                  name="f"))
        p.link_chain(src, q, filt, p.add(TensorSink(name="out0")))
        p.start()
        start.set()
        assert p.wait(timeout=30)
        p.stop()
        return spans.snapshot()

    def test_a_queue_writes_push_to_pop(self, listener, slow_filter):
        records = self.run()
        waits = by_cat(records, "wait")
        assert [r[4] for r in waits] == ["q.pad_wait"] * 3
        assert all(r[9] == {"pad": "sink"} and r[3] == "queue:q" for r in waits)
        pushes = {r[7]: r[1] for r in by_cat(records, "source")}
        pops = sorted(end(r) for r in waits)
        for r in waits:  # under the frame's own span, from its push on
            assert 0 <= r[1] - pushes[r[8]] < 20 * MS
        # three frames a millisecond apart behind a 10 ms filter: the
        # third is popped two filter calls after the first
        assert pops[2] - pops[0] >= 18 * MS
        legs = collector.frame_legs(records, {"s0": "out0"})
        assert [f["round"] for f in legs] == [1, 2, 3]
        assert legs[2]["wait_ns"] >= 15 * MS
        assert legs[2]["wait_ns"] > legs[0]["wait_ns"]

    def test_attribute_trace_reads_the_wait_as_the_queue_leg(
            self, listener, slow_filter):
        records = self.run()
        for push in by_cat(records, "source"):
            mine = spans.records_for_trace(push[6], records)
            legs = collector.attribute_trace(mine)
            waited = sum(r[2] for r in mine if r[5] == "wait")
            assert waited > 0 and legs["queue"] == waited
            assert legs["device"] >= 10 * MS


class TestDynBatch:
    def test_a_flush_writes_what_each_frame_waited(self, listener, slow_filter):
        """No timeout stands in ``tensor_dynbatch``: frames pile up behind
        a slow consumer and the next flush takes them all, the first of
        them having waited longest."""
        def slow(x):
            time.sleep(0.060)
            return x

        start = threading.Event()
        p = nns.Pipeline(name="dyn")
        src = p.add(TimedSrc("s0", (0, 15, 25, 35), start))
        dyn = p.add(nns.make("tensor_dynbatch", name="dyn", max_batch=4))
        filt = p.add(TensorFilter(framework="custom", model=slow, name="f"))
        undo = p.add(nns.make("tensor_dynunbatch", name="undo"))
        got = []
        sink = p.add(TensorSink(name="out0"))
        sink.connect("new-data", got.append)
        p.link_chain(src, dyn, filt, undo, sink)
        p.start()
        start.set()
        assert p.wait(timeout=30)
        p.stop()
        records = spans.snapshot()
        assert len(got) == 4
        waits = by_cat(records, "wait")
        assert [r[4] for r in waits] == ["dyn.pad_wait"] * 4
        flushes = sorted({end(r) for r in waits})
        assert len(flushes) == 2  # frame 0 alone, then the pile-up
        piled = sorted((r for r in waits if end(r) == flushes[1]),
                       key=lambda r: r[1])
        assert len(piled) == 3
        assert piled[0][2] >= 30 * MS and piled[0][2] - piled[2][2] >= 10 * MS
        # the element's own dispatch is a span now, as a collector's is
        own = [r for r in by_cat(records, "dispatch") if r[4] == "dyn"]
        assert len(own) >= 4 and own[0][9] == {"element": "DynBatch"}
        legs = collector.frame_legs(records, {"s0": "out0"})
        assert len(legs) == 4 and [f["round"] for f in legs] == [1, 2, 2, 2]


class TestNoListener:
    def test_nothing_is_written_and_no_beat_runs(self, slow_filter):
        assert hooks.enabled is False
        records = run_merged(late_ms=5)
        assert not [r for r in records if r[5] in ("wait", "host")]
        assert "host:beat" not in [t.name for t in threading.enumerate()]

    def test_the_beat_lives_and_dies_with_the_lane(self, listener, slow_filter):
        start = threading.Event()
        p = nns.Pipeline(name="beat")
        src = p.add(TimedSrc("s0", (0, 60), start))
        p.link_chain(src, p.add(TensorSink(name="out0")))
        p.start()
        try:
            assert "host:beat" in [t.name for t in threading.enumerate()]
            start.set()
            assert p.wait(timeout=30)
        finally:
            p.stop()
        assert "host:beat" not in [t.name for t in threading.enumerate()]
        assert REGISTRY.get("nnstpu_host_stalls_total") is not None
        assert REGISTRY.get("nnstpu_host_stall_seconds") is not None


class TestHostBeat:
    def test_a_held_interpreter_lock_is_one_stall_with_what_the_kernel_knows(
            self):
        """``PyDLL`` calls keep the interpreter lock: 80 ms of ``usleep``
        under it stop every Python thread, the beat's too."""
        spans.enable()
        before = REGISTRY.counter(
            "nnstpu_host_stalls_total", labelnames=("cause",))
        counted = sum(c.value for _, c in before.children())
        beat = hoststall.HostBeat()
        beat.start()
        try:
            time.sleep(0.1)   # a few beats on time, a baseline read
            held = time.perf_counter_ns()
            ctypes.PyDLL(None).usleep(80_000)
            let_go = time.perf_counter_ns()
            time.sleep(0.1)
        finally:
            beat.stop()
        # the one stall that overlaps the held lock (a busy machine may
        # make a beat late elsewhere)
        (r,) = [r for r in spans.snapshot() if r[4] == "host_stall"
                and r[1] < let_go and held < end(r)]
        assert r[5] == "host" and r[3] == "host:beat"
        assert 50 * MS <= r[2] <= let_go - held + 70 * MS
        args = r[9]
        assert args["cpu_ms"] is not None and 0 <= args["cpu_ms"] < 150
        assert isinstance(args["nivcsw"], int) and isinstance(args["majflt"], int)
        for key in ("run_delay_ms", "throttled_ms", "nr_throttled",
                    "steal_ms", "psi_cpu_ms", "psi_memory_ms", "psi_io_ms"):
            assert args[key] is None or args[key] >= 0
        assert args["cause"] in ("throttled", "runqueue", "gc", "fault",
                                 "unknown")
        after = sum(c.value for _, c in before.children())
        assert after >= counted + 1

    def test_no_cpu_stat_reads_none_and_never_raises(self, monkeypatch):
        monkeypatch.setattr(hoststall, "_read", lambda path: None)
        assert hoststall.cpu_stat_path() is None
        kernel = hoststall._Kernel()
        read = kernel.read()
        assert set(read) == {"run_delay_ms", "throttled_ms", "nr_throttled",
                             "steal_ms", "psi_cpu_ms", "psi_memory_ms",
                             "psi_io_ms"}
        assert set(read.values()) == {None}

    def test_steal_and_pressure_are_read_in_ms(self, monkeypatch):
        files = {"/proc/stat": "cpu  10 0 20 300 1 0 2 7 0 0\ncpu0 1 2\n",
                 "/proc/pressure/cpu":
                 "some avg10=0.00 avg60=0.00 avg300=0.00 total=123456\n"
                 "full avg10=0.00 avg60=0.00 avg300=0.00 total=7\n"}
        monkeypatch.setattr(hoststall, "_read", files.get)
        monkeypatch.setattr(hoststall, "_TICKS_PER_S", 100)
        assert hoststall._steal_ms() == 70.0
        assert hoststall._pressure_ms("cpu") == 123.456
        assert hoststall._pressure_ms("memory") is None

    @pytest.mark.parametrize("text,expected", [
        ("usage_usec 5\nnr_periods 3\nnr_throttled 2\nthrottled_usec 1500\n",
         (2, 1_500_000)),                                         # v2
        ("nr_periods 3\nnr_throttled 4\nthrottled_time 2500000\n",
         (4, 2_500_000)),                                         # v1
        ("usage_usec 5\nuser_usec 3\n", None),    # v2, cpu controller off
    ])
    def test_cpu_stat_of_either_cgroup_version(self, monkeypatch, text,
                                               expected):
        monkeypatch.setattr(hoststall, "_read", lambda path: text)
        assert hoststall._throttle("/sys/fs/cgroup/cpu.stat") == expected

    @pytest.mark.parametrize("args,gc_ns,cause", [
        ({"throttled_ms": 60.0, "run_delay_ms": 70.0}, 0, "throttled"),
        ({"throttled_ms": 0.0, "run_delay_ms": 55.0}, 0, "runqueue"),
        ({"throttled_ms": None, "run_delay_ms": None}, 60 * MS, "gc"),
        ({"throttled_ms": None, "run_delay_ms": 1.0, "majflt": 3}, 0, "fault"),
        ({"throttled_ms": 0.0, "run_delay_ms": 0.2, "majflt": 0}, 0, "unknown"),
    ])
    def test_what_a_late_beat_is_counted_under(self, args, gc_ns, cause):
        assert hoststall.cause_of(100 * MS, args, gc_ns) == cause

    def test_a_long_collection_is_a_gc_pause(self, monkeypatch):
        spans.enable()
        clock = iter([1_000 * MS, 1_007 * MS, 2_000 * MS, 2_001 * MS])
        monkeypatch.setattr(hoststall, "now_ns", lambda: next(clock))
        hoststall._on_gc("start", {})
        hoststall._on_gc("stop", {"generation": 2, "collected": 11})
        hoststall._on_gc("start", {})
        hoststall._on_gc("stop", {"generation": 0, "collected": 0})  # 1 ms
        (r,) = [r for r in spans.snapshot() if r[5] == "host"]
        assert r[4] == "gc_pause" and (r[1], r[2]) == (1_000 * MS, 7 * MS)
        assert r[9] == {"generation": 2, "collected": 11}
