"""Stage spans and completion probes of a pipeline that starts while the
hook bus has a listener: what lands in ``obs.spans``' ring, that nothing
does without a listener, that the lanes go with the pipeline, and that the
same spans sit on the profiler's clock as ``nns/`` annotations."""

import glob
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

import nnstreamer_tpu as nns
from nnstreamer_tpu.backends.jax_backend import JaxModel
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.obs import hooks, spans
from nnstreamer_tpu.utils import profiling

STREAMS, ROUNDS = 4, 6


def mux_pipeline(rounds=ROUNDS):
    """4 sources -> mux -> batch -> filter -> split -> 2 sinks."""
    def apply(p, x):
        del p
        return jnp.tanh(x.reshape(STREAMS, -1)[:, :6])

    p = nns.Pipeline(name="stage_spans")
    mux = p.add(nns.make("tensor_mux", sync_mode="nosync", name="mux"))
    for i in range(STREAMS):
        src = p.add(DataSrc(
            data=[np.full((8,), i + k, np.float32) for k in range(rounds)],
            name=f"cam{i}"))
        p.link(src, f"mux.sink_{i}")
    batch = p.add(nns.make("tensor_batch", name="batch"))
    filt = p.add(TensorFilter(framework="jax", name="f",
                              model=JaxModel(apply=apply)))
    split = p.add(nns.make("tensor_split", name="split", tensorseg="6:2,6:2"))
    p.link_chain(mux, batch, filt, split)
    got = []
    for g in range(2):
        sink = p.add(TensorSink(name=f"out{g}"))
        sink.connect("new-data", got.append)
        p.link(f"split.src_{g}", sink)
    return p, got


def complete(records, cat=None, name=None):
    return [r for r in records if r[0] == spans.PH_COMPLETE
            and (cat is None or r[5] == cat) and (name is None or r[4] == name)]


@pytest.fixture
def listener():
    seen = []

    def on_dispatch(node, frame, outs, t_ns):
        seen.append((node.name, t_ns))

    hooks.connect("device_dispatch", on_dispatch)
    yield seen
    hooks.disconnect("device_dispatch", on_dispatch)


class TestListenerAtStart:
    def test_invoke_and_device_exec_per_round_with_equal_round_ids(
            self, listener):
        p, got = mux_pipeline()
        p.run(timeout=60)
        assert len(got) == 2 * ROUNDS and len(listener) == ROUNDS
        recs = spans.snapshot()
        invokes = complete(recs, "stage", "f.invoke")
        execs = complete(recs, "device", "device_exec")
        assert len(invokes) == len(execs) == ROUNDS
        assert sorted(r[9]["round"] for r in invokes) == \
            sorted(r[9]["round"] for r in execs) == list(range(1, ROUNDS + 1))
        by_round = {r[9]["round"]: r for r in execs}
        for inv in invokes:
            ex = by_round[inv[9]["round"]]
            # enqueue -> done starts with the invoke and outlasts it
            assert abs(ex[1] - inv[1]) < 1_000_000
            assert ex[1] + ex[2] >= inv[1]
        assert spans.recorder_stats()["dropped"] == 0

    def test_dispatch_spans_chain_to_the_source_and_carry_their_class(
            self, listener):
        p, _ = mux_pipeline()
        p.run(timeout=60)
        recs = spans.snapshot()
        by_sid = {r[7]: r for r in complete(recs)}
        pushes = {r[7] for r in recs
                  if r[0] == spans.PH_INSTANT and r[5] == "source"}
        assert pushes
        for inv in complete(recs, "stage", "f.invoke"):
            chain, cur = [], inv
            while cur[8] in by_sid:
                cur = by_sid[cur[8]]
                chain.append(cur[4])
            # filter <- batch <- mux, whose parent is the camera's push
            assert chain == ["f", "batch", "mux"]
            assert cur[8] in pushes
            assert cur[9] == {"element": "TensorMux"}
        classes = {r[9]["element"] for r in complete(recs, "dispatch")}
        assert classes == {"TensorMux", "TensorBatch", "TensorFilter",
                           "TensorSplit", "TensorSink"}
        # the lane a pipeline starts by itself records no flow per pad push
        assert not [r for r in recs if r[5] == "dataflow"]

    def test_ticket_wait_is_a_child_of_the_muxs_span(self, listener):
        # a one-frame stream ends while the round that took its frame is
        # still downstream: its EOS books the next ticket and queues
        # behind that round
        pushed, downstream = threading.Event(), threading.Event()

        class OneFrame(DataSrc):
            def frames(self):
                yield from super().frames()
                pushed.set()
                assert downstream.wait(30)

        class After(DataSrc):
            def frames(self):
                assert pushed.wait(30)
                yield from super().frames()

        def busy(frame):
            downstream.set()
            time.sleep(0.03)

        p = nns.Pipeline(name="ticket")
        mux = p.add(nns.make("tensor_mux", sync_mode="nosync", name="mux"))
        p.link(p.add(OneFrame(data=[np.zeros(4, np.float32)], name="cam0")),
               "mux.sink_0")
        p.link(p.add(After(data=[np.ones(4, np.float32)] * 3, name="cam1")),
               "mux.sink_1")
        p.link(mux, p.add(TensorSink(name="out", callback=busy)))
        p.run(timeout=60)
        recs = spans.snapshot()
        by_sid = {r[7]: r for r in complete(recs)}
        waits = [r for r in complete(recs, "stage")
                 if r[4] == "mux.ticket_wait"]
        assert len(waits) == 1
        (w,) = waits
        parent = by_sid[w[8]]
        assert parent[4] == "mux" and parent[5] == "dispatch"
        assert parent[3] == w[3] == "src:cam0"                # same thread
        assert parent[1] <= w[1] and w[1] + w[2] <= parent[1] + parent[2]
        assert w[9] == {"ticket": 1} and w[2] > 10_000_000

    def test_lanes_stop_with_the_pipeline(self, listener):
        p, _ = mux_pipeline()
        p.start()
        assert spans.enabled
        assert any(t.name.startswith("device:") for t in threading.enumerate())
        p.wait(timeout=60)
        p.stop()
        assert not spans.enabled
        assert not any(t.name.startswith("device:")
                       for t in threading.enumerate())
        assert p.tracers == [] and "tracers" not in p.stats()
        # the listener is all that is left on the bus, and the records stay
        assert hooks.enabled
        assert complete(spans.snapshot(), "device", "device_exec")

    def test_explicit_tracers_give_the_same_records(self, monkeypatch):
        monkeypatch.setenv("NNSTPU_TRACERS", "spans;device")
        p, _ = mux_pipeline()
        p.run(timeout=60)
        recs = spans.snapshot()
        assert len(complete(recs, "stage", "f.invoke")) == ROUNDS
        assert len(complete(recs, "device", "device_exec")) == ROUNDS
        assert {r[9]["element"] for r in complete(recs, "dispatch")} >= {
            "TensorMux", "TensorFilter"}
        # ... plus the flows of every pad push, for Perfetto's arrows
        assert [r for r in recs if r[5] == "dataflow"]


class TestNoListener:
    def test_nothing_is_recorded_and_the_gate_stays_shut(self):
        flags = []
        p, got = mux_pipeline()
        for sink in ("out0", "out1"):
            p[sink].connect("new-data", lambda f: flags.append(
                (hooks.enabled, spans.enabled)))
        p.run(timeout=60)
        assert len(got) == 2 * ROUNDS
        assert flags and not any(h or s for h, s in flags)
        assert spans.snapshot() == []
        assert p["f"].dispatches == 0

    def test_a_listener_connected_after_start_turns_no_lane_on(self):
        p, _ = mux_pipeline()
        p.start()
        seen = []
        hooks.connect("error", seen.append)
        try:
            assert not spans.enabled
            p.wait(timeout=60)
        finally:
            p.stop()
            hooks.disconnect("error", seen.append)
        assert complete(spans.snapshot()) == []


class TestProfilingDoesNotBlock:
    def test_stats_come_from_device_completions(self):
        p, got = mux_pipeline()
        with profiling.profiled():
            p.run(timeout=60)
        assert len(got) == 2 * ROUNDS
        s = p.stats()["f"]
        assert s["count"] == ROUNDS and s["min_ms"] > 0
        # the number is the completion's: the device_exec spans', exactly
        execs = complete(spans.snapshot(), "device", "device_exec")
        assert sorted(r[2] / 1e6 for r in execs)[0] == s["min_ms"]
        # profiling alone brings the device lane, not the stage spans
        assert complete(spans.snapshot(), "dispatch") == []

    def test_the_dispatching_thread_never_waits_for_the_device(
            self, monkeypatch):
        import jax

        waited = []
        real = jax.block_until_ready

        def spy(x):
            waited.append(threading.current_thread().name)
            return real(x)

        monkeypatch.setattr(jax, "block_until_ready", spy)
        p, _ = mux_pipeline()
        with profiling.profiled():
            p.run(timeout=60)
        assert len(waited) == ROUNDS
        assert all(name.startswith("device:") for name in waited)


class TestProfilersClock:
    def test_nns_events_on_the_xplane_and_t0_anchor(self, tmp_path, listener):
        import jax
        from jax.profiler import ProfileData

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        p, _ = mux_pipeline()
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            p.run(timeout=60)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
        events = [ev for plane in ProfileData.from_file(path).planes
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("nns/")]
        names = {ev.name for ev in events}
        assert {"nns/mux", "nns/batch", "nns/f", "nns/split", "nns/out0",
                "nns/f.invoke"} <= names
        ring = {r[9]["round"]: r
                for r in complete(spans.snapshot(), "stage", "f.invoke")}
        offsets = []
        for ev in events:
            if ev.name != "nns/f.invoke":
                continue
            stats = dict(ev.stats)
            span = ring[stats["round"]]
            assert stats["t0_ns"] == span[1]       # the ring's own start
            offsets.append(ev.start_ns - stats["t0_ns"])
            assert abs(ev.duration_ns - span[2]) < 1_000_000
        assert len(offsets) == ROUNDS
        # one offset maps the ring's clock onto the xplane's, every round
        assert max(offsets) - min(offsets) < 1_000_000
