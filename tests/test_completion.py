"""Dispatch-ahead at a device filter (``graph/completion.py``).

The worker is driven here on the CPU with every round forced ahead through
the filter's private seam (``_force_ahead``): what leaves it, in what order
and from which thread, against the same graph run inline.  The rule that
decides the way on an accelerator is driven with synthetic timings.
"""

import threading
import time
import warnings

import numpy as np
import pytest

import nnstreamer_tpu as nns
from nnstreamer_tpu import Pipeline, PipelineError
from nnstreamer_tpu.backends.jax_backend import JaxBackend, JaxModel
from nnstreamer_tpu.buffer import Frame
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.graph import completion
from nnstreamer_tpu.graph.completion import AheadRule, Completion
from nnstreamer_tpu.graph.node import Node, SinkTerminal, SourceNode
from nnstreamer_tpu.obs import hooks
from nnstreamer_tpu.obs.metrics import REGISTRY
from nnstreamer_tpu.spec import TensorsSpec

MS = 1_000_000


def doubling():
    return JaxModel(apply=lambda params, x: x * 2.0)


def rounds_of(name):
    """``nnstpu_filter_rounds_total`` of filter ``name``, by way."""
    metric = REGISTRY.get("nnstpu_filter_rounds_total")
    return {key[1]: child.value
            for key, child in (metric.children() if metric else ())
            if key[0] == name}


def counted(name, fn):
    """What ``fn()`` added to filter ``name``'s rounds, by way."""
    before = rounds_of(name)
    fn()
    after = rounds_of(name)
    return {way: n - before.get(way, 0.0) for way, n in after.items()
            if n != before.get(way, 0.0)}


class Recorder(SinkTerminal):
    """Notes every frame (its first value), caps and EOS in arrival order,
    with the thread that brought it."""

    def __init__(self, name=None, delay_s=0.0, probe=None):
        super().__init__(name)
        self.items = []
        self.threads = set()
        self.delay_s = delay_s
        self.probe = probe

    def process(self, pad, frame):
        del pad
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.probe is not None:
            self.probe()
        t = np.asarray(frame.tensor(0))
        self.items.append(("frame", t.shape, float(t.reshape(-1)[0])))
        self.threads.add(threading.current_thread().name)

    def _handle_event(self, pad, event):
        if event.kind == "caps":
            self.items.append(("caps", event.payload.tensors[0].shape))
        elif event.kind == "eos":
            self.items.append(("eos",))
        super()._handle_event(pad, event)


class Boom(Node):
    """Passes frames through; raises on the frames whose value is in
    ``bad`` (once each: a restarted element sees the next frame)."""

    def __init__(self, name=None, bad=()):
        super().__init__(name)
        self.add_sink_pad("sink")
        self.add_src_pad("src")
        self.bad = set(bad)

    def process(self, pad, frame):
        del pad
        v = float(np.asarray(frame.tensor(0)).reshape(-1)[0])
        if v in self.bad:
            self.bad.discard(v)
            raise RuntimeError(f"boom at {v}")
        return frame


def shapes_stream(n=40, every=10):
    """``n`` frames whose length changes every ``every`` (caps events)."""
    return [np.full((4 if (i // every) % 2 == 0 else 6,), float(i),
                    np.float32) for i in range(n)]


def run_chain(data, ahead, *extra, sink=None, timeout=60):
    p = Pipeline()
    src = p.add(DataSrc(data=data))
    filt = p.add(TensorFilter(name="f", framework="jax", model=doubling()))
    filt._force_ahead = ahead
    sink = p.add(sink or Recorder(name="rec"))
    nodes = [p.add(e) for e in extra]
    p.link_chain(src, filt, *nodes, sink)
    p.run(timeout=timeout)
    return p, filt, sink


class TestWorker:
    def test_frames_caps_and_eos_leave_in_the_inline_order(self):
        runs = {}
        ways = {ahead: counted("f", lambda a=ahead: runs.__setitem__(
            a, run_chain(shapes_stream(), a)[2])) for ahead in (False, True)}
        inline, ahead = runs[False], runs[True]
        assert ahead.items == inline.items
        assert [i[0] for i in ahead.items].count("caps") == 3
        assert ahead.items[-1] == ("eos",)
        assert ahead.threads == {"ahead:f"}  # the worker delivered them all
        assert ways == {False: {"inline": 40.0}, True: {"ahead": 40.0}}

    def test_a_full_fifo_holds_the_dispatching_thread(self):
        p = Pipeline()
        seen = []
        src = p.add(DataSrc(data=[np.full((4,), float(i), np.float32)
                                  for i in range(12)]))
        filt = p.add(TensorFilter(name="f", framework="jax", model=doubling()))
        filt._force_ahead = True
        rec = p.add(Recorder(name="rec", delay_s=0.02,
                             probe=lambda: seen.append(len(filt._done._held))))
        p.link_chain(src, filt, rec)
        p.run(timeout=60)
        assert len(rec.items) == 13 and max(seen) == completion.DEPTH

    @pytest.mark.parametrize("ahead", [False, True])
    def test_an_unhandled_fault_downstream_reaches_the_bus(self, ahead):
        errors = []

        def on_error(pipeline, node, exc):
            errors.append(repr(exc))

        hooks.connect("error", on_error)
        try:
            with pytest.raises(PipelineError, match="boom at 10.0"):
                run_chain([np.full((4,), float(i), np.float32)
                           for i in range(10)], ahead, Boom(bad={10.0}))
        finally:
            hooks.disconnect("error", on_error)
        assert errors == ["RuntimeError('boom at 10.0')"]

    @pytest.mark.parametrize("ahead", [False, True])
    def test_a_restart_policy_downstream_absorbs_the_fault(self, ahead):
        p = Pipeline()
        src = p.add(DataSrc(data=[np.full((4,), float(i), np.float32)
                                  for i in range(10)]))
        filt = p.add(TensorFilter(name="f", framework="jax", model=doubling()))
        filt._force_ahead = ahead
        boom = p.add(Boom(name="boom", bad={6.0}))
        rec = p.add(Recorder(name="rec"))
        p.link_chain(src, filt, boom, rec)
        p.set_restart_policy("boom", "restart", backoff_ms=0)
        p.run(timeout=60)
        got = [i[2] for i in rec.items if i[0] == "frame"]
        assert got == [2.0 * i for i in range(10) if i != 3]
        assert p.recovery_stats()["actions"] == {"restart_node": 1}
        assert p.recovery_stats()["shed_frames"] == {"boom": 1}

    def test_wait_drains_the_fifo(self):
        data = [np.full((4,), float(i), np.float32) for i in range(8)]
        p = Pipeline()
        src = p.add(DataSrc(data=data))
        filt = p.add(TensorFilter(name="f", framework="jax", model=doubling()))
        filt._force_ahead = True
        rec = p.add(Recorder(name="rec", delay_s=0.03))
        p.link_chain(src, filt, rec)
        p.start()
        try:
            assert p.wait(60)
            assert len(rec.items) == 9 and not filt._done._held
        finally:
            p.stop()

    def test_stop_with_a_round_in_flight_joins_the_worker(self):
        class Endless(SourceNode):
            def output_spec(self):
                return TensorsSpec.from_arrays([np.zeros((4,), np.float32)])

            def frames(self):
                i = 0
                while not self.stopped:
                    yield Frame.of(np.full((4,), float(i), np.float32))
                    i += 1

        p = Pipeline()
        src = p.add(Endless(name="src"))
        filt = p.add(TensorFilter(name="f", framework="jax", model=doubling()))
        filt._force_ahead = True
        rec = p.add(Recorder(name="rec", delay_s=0.05))
        p.link_chain(src, filt, rec)
        p.start()
        deadline = time.monotonic() + 30
        while len(rec.items) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert filt._done._held  # rounds handed over and not delivered
        t0 = time.monotonic()
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)  # a leaked worker
            p.stop()
        assert time.monotonic() - t0 < 5
        assert not [t for t in threading.enumerate()
                    if t.name == "ahead:f" and t.is_alive()]
        got = [i[2] for i in rec.items if i[0] == "frame"]
        assert got == [2.0 * i for i in range(len(got))]

    def test_merge_with_two_rounds_in_flight_is_bit_equal_to_inline(self):
        """``tensor_merge`` leases its output from the pool and the filter
        fences it with the round's result: with a round on the device and
        the next one merged behind it, no leased buffer is rewritten under
        its upload."""
        import jax.numpy as jnp

        rng = np.random.default_rng(46)
        a = rng.integers(0, 256, (24, 8, 16, 16, 3), dtype=np.uint8)
        b = rng.integers(0, 256, (24, 8, 16, 16, 3), dtype=np.uint8)
        w_in = (rng.standard_normal((16 * 16 * 3, 512)) / 40).astype(np.float32)
        w = (rng.standard_normal((512, 512)) / 23).astype(np.float32)

        def apply(params, x):  # long enough a step for the next to queue
            h = x.reshape(x.shape[0], -1).astype(jnp.float32) / 255.0
            h = h @ params[0]
            for _ in range(48):
                h = jnp.tanh(jnp.tile(h, (8, 1)) @ params[1])[:16]
            return h

        def run(ahead):
            got, held = [], []
            p = Pipeline()
            merge = p.add(nns.make("tensor_merge", mode="linear", option="3",
                                   sync_mode="nosync"))
            for i, (name, data) in enumerate((("a", a), ("b", b))):
                p.add(DataSrc(name=name, data=list(data)))
                p.link(name, f"{merge.name}.sink_{i}")
            filt = p.add(TensorFilter(name="f", framework="jax", model=JaxModel(
                apply=apply, params=(w_in, w))))
            filt._force_ahead = ahead
            sink = p.add(TensorSink(name="out"))

            def on_data(f):
                got.append(np.array(f.tensor(0)))
                if ahead:
                    held.append(len(filt._done._held))

            sink.connect("new-data", on_data)
            p.link_chain(merge, filt, sink)
            p.run(timeout=120)
            return np.stack(got), held

        (inline, _), (ahead, held) = run(False), run(True)
        assert inline.shape == (24, 16, 512)
        assert max(held) == completion.DEPTH  # two rounds in flight
        np.testing.assert_array_equal(ahead, inline)


# the rule on synthetic timings: (device step, host return) in ms a round
CELLS = {
    "vit": (401.2, 15.2),         # 3.8 %
    "laguna": (565.1, 7.5),       # 1.3 %
    "falcon": (724.9, 6.8),       # 0.94 %
    "glm": (1310.0, 0.9),         # 0.07 %
    "axk1": (1013.0, 0.9),        # 0.09 %
}


def settle(rule, step_ms, return_ms, rounds=3):
    for _ in range(rounds):
        rule.observe(int(step_ms * MS), int(return_ms * MS))
    return rule.ahead


class TestRule:
    @pytest.mark.parametrize("cell", ["vit", "laguna", "falcon"])
    def test_engages_where_the_way_back_is_worth_a_round(self, cell):
        rule = AheadRule()
        assert settle(rule, *CELLS[cell], rounds=2) is False
        assert settle(rule, *CELLS[cell], rounds=1) is True  # the 3rd round

    @pytest.mark.parametrize("cell", ["glm", "axk1"])
    def test_stays_inline_where_it_is_not(self, cell):
        rule = AheadRule()
        assert settle(rule, *CELLS[cell], rounds=50) is False

    def test_the_first_round_is_not_read(self):
        rule = AheadRule()
        rule.observe(5000 * MS, 0)  # a first run's step: no share
        assert settle(rule, *CELLS["falcon"], rounds=2) is True

    def test_one_stalled_round_does_not_engage_an_inline_filter(self):
        rule = AheadRule()
        settle(rule, *CELLS["glm"], rounds=5)
        rule.observe(int(1310 * MS), 100 * MS)  # a 100 ms stall on the way back
        assert rule.ahead is False
        assert settle(rule, *CELLS["glm"], rounds=5) is False

    @pytest.mark.parametrize("stall", ["step", "return"])
    def test_one_stalled_round_does_not_release_an_engaged_filter(self, stall):
        rule = AheadRule()
        step, back = CELLS["falcon"]
        assert settle(rule, step, back, rounds=5) is True
        if stall == "step":
            rule.observe(int((step + 100) * MS), int(back * MS))
        else:
            rule.observe(int(step * MS), 100 * MS)
        assert rule.ahead is True
        assert settle(rule, step, back, rounds=5) is True

    def test_hysteresis_band(self):
        rule = AheadRule()
        assert settle(rule, 1000.0, 4.0, rounds=5) is False  # 0.4 %: not in
        assert settle(rule, 1000.0, 6.0, rounds=5) is True   # 0.6 %: in
        assert settle(rule, 1000.0, 3.0, rounds=5) is True   # 0.3 %: stays
        assert settle(rule, 1000.0, 2.0, rounds=2) is False  # 0.2 %: out


class FakeDevice(JaxBackend):
    """The jax backend, claiming an accelerator: the rule's way on a CPU."""

    def on_accelerator(self):
        return True


class TestEngagement:
    def _run(self, backend, n=12):
        """The way each frame took (the filter's worker or none), what
        reached the sink, and the rounds counted by way."""
        p = Pipeline()
        src = p.add(DataSrc(data=[np.full((4,), float(i), np.float32)
                                  for i in range(n)]))
        filt = p.add(TensorFilter(name="g", backend=backend, model=doubling()))
        rec = p.add(Recorder(name="rec"))
        p.link_chain(src, filt, rec)
        ways = []
        rec.probe = lambda: ways.append(type(filt._done).__name__)
        rounds = counted("g", lambda: p.run(timeout=60))
        return ways, rec, rounds

    def test_the_cpu_platform_never_engages(self):
        ways, rec, rounds = self._run(JaxBackend())
        assert set(ways) == {"NoneType"}
        assert not [t for t in rec.threads if t.startswith("ahead:")]
        assert rounds == {"inline": 12.0}

    def test_lanes_never_engage(self, monkeypatch):
        monkeypatch.setenv("NNSTPU_DISPATCH_LANES", "2")
        ways, _, rounds = self._run(FakeDevice())
        assert set(ways) == {"NoneType"}
        assert rounds == {"inline": 12.0}

    def test_an_accelerator_gets_a_worker_and_counts_each_round_once(self):
        ways, rec, rounds = self._run(FakeDevice(), n=20)
        assert set(ways) == {"Completion"}
        got = [i[2] for i in rec.items if i[0] == "frame"]
        assert got == [2.0 * i for i in range(20)]
        assert sum(rounds.values()) == 20.0
        assert rounds["inline"] >= completion.SKIP + completion.WINDOW


def test_a_completion_without_rounds_holds_no_thread():
    class Stub:
        name = "stub"
        pipeline = None

    done = Completion(Stub(), AheadRule())
    done.flush()
    done.close()
    assert done._thread is None
