"""Device-lane observability: DeviceTracer completion probes, compile
accounting, per-device memory gauges, and the pipeline health watchdog."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from nnstreamer_tpu import Frame, Pipeline
from nnstreamer_tpu.backends.jax_backend import JaxBackend, JaxModel
from nnstreamer_tpu.buffer import Frame as _Frame
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.queue import Queue
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.graph.node import Node, SourceNode
from nnstreamer_tpu.obs import hooks, spans
from nnstreamer_tpu.obs.device import (
    DeviceTracer,
    device_memory_snapshot,
    oldest_inflight,
    register_memory_gauges,
)
from nnstreamer_tpu.obs.export import (
    MetricsServer,
    health_snapshot,
    render_text,
)
from nnstreamer_tpu.obs.metrics import REGISTRY, MetricsRegistry
from nnstreamer_tpu.obs.watchdog import PipelineWatchdog
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec


def _wait_for(cond, timeout=10.0, step=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


def _jax_model(shape=(4,)):
    return JaxModel(
        apply=lambda params, x: x * 2,
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.float32, shape=shape)),
    )


def _spec(shape):
    return TensorsSpec.of(TensorSpec(dtype=np.float32, shape=shape))


class _BlockingOutput:
    """Duck-typed array whose readiness is test-controlled."""

    def __init__(self, event):
        self._event = event

    def block_until_ready(self):
        self._event.wait()
        return self


class TestDeviceTracer:
    def test_device_exec_spans_on_cpu_backend(self):
        """The flagship path: a jax pipeline with ONLY the device tracer
        attached yields per-dispatch device_exec spans on a dedicated
        device track, flow-linked from the host side, plus histograms
        and counters on the registry."""
        reg = MetricsRegistry()
        got = []
        p = Pipeline(name="devlane")
        src = p.add(DataSrc(
            data=[np.full(4, i, np.float32) for i in range(6)], name="s"))
        filt = p.add(TensorFilter(framework="jax", model=_jax_model(),
                                  name="f"))
        p.link_chain(src, filt, p.add(TensorSink(callback=got.append,
                                                 name="out")))
        tracer = p.attach_tracer(DeviceTracer(registry=reg))
        p.run(timeout=60)
        assert len(got) == 6
        assert _wait_for(lambda: tracer.summary()["completed"] == 6)
        summ = tracer.summary()
        assert summ["dispatches"] == 6 and summ["dropped"] == 0
        assert summ["by_element"]["f"]["count"] == 6
        assert summ["compiles"]["miss"] >= 1

        doc = json.loads(json.dumps(spans.chrome_trace(spans.snapshot())))
        events = doc["traceEvents"]
        execs = [e for e in events
                 if e.get("ph") == "X" and e["name"] == "device_exec"]
        assert len(execs) == 6
        # all device_exec spans share one tid row, named device:<platform>
        tids = {e["tid"] for e in execs}
        assert len(tids) == 1
        rows = {e["tid"]: e["args"]["name"] for e in events
                if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert rows[tids.pop()].startswith("device:")
        # flow arrows host dispatch -> device span (cross-thread pairs)
        starts = {e["id"]: e for e in events
                  if e.get("ph") == "s" and e.get("cat") == "device"}
        ends = [e for e in events
                if e.get("ph") == "f" and e.get("cat") == "device"
                and e["id"] in starts and starts[e["id"]]["tid"] != e["tid"]]
        assert len(ends) == 6

        text = render_text(reg)
        assert "nnstpu_device_exec_seconds_bucket" in text
        assert ('nnstpu_device_dispatches_total{pipeline="devlane",'
                'element="f"} 6') in text

    def test_reaper_queue_overflow_accounting(self):
        """The probe queue is bounded: with the reaper wedged on an
        unready output, probes past the bound drop and are counted —
        a sick device never backs host memory up into the pipeline."""
        reg = MetricsRegistry()
        p = Pipeline(name="ovf")
        node = p.add(Node(name="f"))
        tracer = DeviceTracer(registry=reg, capacity=2)
        p._tracers.append(tracer)
        tracer.start(p)
        release = threading.Event()
        frame = Frame.of(np.zeros(4, np.float32))
        t0 = time.perf_counter_ns()
        try:
            # first probe: reaper pops it and blocks on readiness
            hooks.emit("device_dispatch", node, frame,
                       (_BlockingOutput(release),), t0)
            assert _wait_for(lambda: tracer.summary()["inflight"] == 0)
            # fill the bound, then overflow
            for _ in range(2):
                hooks.emit("device_dispatch", node, frame,
                           (_BlockingOutput(release),), t0)
            for _ in range(2):
                hooks.emit("device_dispatch", node, frame,
                           (_BlockingOutput(release),), t0)
            summ = tracer.summary()
            assert summ["dropped"] == 2 and summ["dispatches"] == 3
            assert oldest_inflight() is not None  # watchdog's view
            release.set()
            assert _wait_for(lambda: tracer.summary()["completed"] == 3)
            assert oldest_inflight() is None
            assert ('nnstpu_device_probe_dropped_total{pipeline="ovf"} 2'
                    in render_text(reg))
        finally:
            release.set()
            tracer.stop()

    def test_conf_activation(self, monkeypatch):
        monkeypatch.setenv("NNSTPU_TRACERS", "device")
        got = []
        p = Pipeline(name="devconf")
        src = p.add(DataSrc(data=[np.zeros(4, np.float32)], name="s"))
        filt = p.add(TensorFilter(framework="jax", model=_jax_model(),
                                  name="f"))
        p.link_chain(src, filt, p.add(TensorSink(callback=got.append)))
        p.run(timeout=60)
        tr = p.stats()["tracers"]
        assert "device" in tr
        assert _wait_for(
            lambda: p.stats()["tracers"]["device"]["completed"] == 1)


class TestCompileAccounting:
    def test_hit_miss_evict_hook_and_counters(self):
        events = []
        hooks.connect("compile", lambda *a: events.append(a))
        miss0 = _counter_value("nnstpu_compile_total", result="miss")
        hit0 = _counter_value("nnstpu_compile_total", result="hit")
        evict0 = _counter_value("nnstpu_compile_total", result="evict")
        be = JaxBackend()
        be.open(_jax_model(shape=(None,)), custom="compile_cache=2")
        be.reconfigure(_spec((4,)))    # miss
        be.reconfigure(_spec((4,)))    # hit
        be.reconfigure(_spec((8,)))    # miss
        be.reconfigure(_spec((16,)))   # miss + evicts (4,)
        results = [e[2] for e in events]
        assert results == ["miss", "hit", "miss", "evict", "miss"]
        # miss events carry wall time and (on backends that expose
        # cost_analysis) flops/bytes
        miss_events = [e for e in events if e[2] == "miss"]
        assert all(e[3] > 0 for e in miss_events)
        assert _counter_value("nnstpu_compile_total",
                              result="miss") == miss0 + 3
        assert _counter_value("nnstpu_compile_total",
                              result="hit") == hit0 + 1
        assert _counter_value("nnstpu_compile_total",
                              result="evict") == evict0 + 1

    def test_compile_span_when_tracing(self):
        spans.enable()
        be = JaxBackend()
        be.open(_jax_model(shape=(None,)))
        be.reconfigure(_spec((32,)))
        recs = [r for r in spans.snapshot() if r[4] == "compile"]
        assert recs, "no compile span recorded while tracing was enabled"
        ph, ts, dur, _tid, _name, cat, *_ = recs[-1]
        assert ph == spans.PH_COMPLETE and cat == "compile" and dur > 0


def _counter_value(name, **labels) -> float:
    metric = REGISTRY.get(name)
    if metric is None:
        return 0.0
    try:
        return metric.labels(**labels).value
    except ValueError:
        return 0.0


class _StallingSrc(SourceNode):
    """Pushes one frame, then goes silent until stop is requested."""

    def output_spec(self):
        return TensorsSpec.of(TensorSpec(dtype=np.float32, shape=(4,)))

    def frames(self):
        yield _Frame.of(np.zeros(4, np.float32))
        self._stop_evt.wait()


class TestWatchdog:
    def test_stalled_source_flips_healthz_and_dumps(self, tmp_path,
                                                    monkeypatch):
        """Acceptance: a silent source flips /healthz to 503 with a
        reason and writes a stall flight dump to [obs] flight_dump_dir,
        within the configured interval."""
        monkeypatch.setenv("NNSTPU_OBS_FLIGHT_DUMP_DIR", str(tmp_path))
        reg = MetricsRegistry()
        health_events = []
        hooks.connect("health", lambda *a: health_events.append(a))
        p = Pipeline(name="wd_src")
        src = p.add(_StallingSrc(name="cam"))
        p.link(src, p.add(TensorSink(name="out")))
        wd = p.attach_tracer(PipelineWatchdog(
            registry=reg, interval_s=0.03, stall_s=0.1))
        with MetricsServer(port=0, registry=reg) as ms:
            p.start()
            try:
                assert _wait_for(lambda: not wd.summary()["healthy"])
                summ = wd.summary()
                assert any("stalled_source:cam" in r
                           for r in summ["reasons"]), summ
                with pytest.raises(urllib.error.HTTPError) as exc_info:
                    urllib.request.urlopen(
                        f"http://{ms.host}:{ms.port}/healthz", timeout=10)
                assert exc_info.value.code == 503
                body = exc_info.value.read().decode()
                assert "stalled_source:cam" in body
                assert 'nnstpu_health{pipeline="wd_src"} 0' \
                    in render_text(reg)
                assert (tmp_path / "wd_src.stall.trace.json").exists()
                # the health hook event fired for other tracers
                assert any(ev[0] is p and ev[1] is False
                           for ev in health_events)
            finally:
                p.stop()
        # stopping unregisters the provider: /healthz recovers
        healthy, failures = health_snapshot()
        assert healthy and "wd_src" not in failures

    def test_wedged_queue_detected_and_recovers(self):
        reg = MetricsRegistry()
        p = Pipeline(name="wd_q")
        q = p.add(Queue(max_size_buffers=8, name="q0"))
        wd = PipelineWatchdog(registry=reg, interval_s=0.03, stall_s=0.08,
                              queue_depth=2)
        p._tracers.append(wd)
        wd.start(p)
        p.state = "PLAYING"  # the monitor only judges a PLAYING graph
        try:
            hooks.emit("queue_push", q, 3)  # depth high, pops never come
            assert _wait_for(lambda: not wd.summary()["healthy"])
            assert any("wedged_queue:q0" in r
                       for r in wd.summary()["reasons"])
            assert wd.health()[0] is False
            # a pop clears the wedge: health recovers
            hooks.emit("queue_pop", q, 0)
            assert _wait_for(lambda: wd.summary()["healthy"])
            assert wd.summary()["transitions"] == 2
            assert 'nnstpu_health{pipeline="wd_q"} 1' in render_text(reg)
        finally:
            p.state = "STOPPED"
            wd.stop()

    def test_overdue_device_dispatch_detected(self):
        """The device-lane deadline: a dispatch whose completion the
        DeviceTracer has not observed within the deadline flags the
        pipeline unhealthy."""
        reg = MetricsRegistry()
        p = Pipeline(name="wd_dev")
        node = p.add(Node(name="f"))
        dev = DeviceTracer(registry=reg, capacity=4)
        p._tracers.append(dev)
        dev.start(p)
        wd = PipelineWatchdog(registry=reg, interval_s=0.03, stall_s=60.0,
                              device_deadline_s=0.05)
        p._tracers.append(wd)
        wd.start(p)
        p.state = "PLAYING"
        release = threading.Event()
        try:
            hooks.emit("device_dispatch", node,
                       Frame.of(np.zeros(4, np.float32)),
                       (_BlockingOutput(release),), time.perf_counter_ns())
            assert _wait_for(lambda: not wd.summary()["healthy"])
            assert any("overdue_device:f" in r
                       for r in wd.summary()["reasons"])
            release.set()
            assert _wait_for(lambda: wd.summary()["healthy"])
        finally:
            release.set()
            p.state = "STOPPED"
            wd.stop()
            dev.stop()

    def test_pipeline_error_marks_unhealthy(self):
        reg = MetricsRegistry()

        def boom(x):
            if float(np.max(x)) > 0:  # negotiation probes with zeros
                raise RuntimeError("wd crash")
            return x

        p = Pipeline(name="wd_err")
        src = p.add(DataSrc(data=[np.ones(4, np.float32)], name="s"))
        filt = p.add(TensorFilter(framework="custom", model=boom, name="f"))
        p.link_chain(src, filt, p.add(TensorSink(name="out")))
        wd = p.attach_tracer(PipelineWatchdog(registry=reg, interval_s=0.05))
        from nnstreamer_tpu.graph.pipeline import PipelineError

        with pytest.raises(PipelineError):
            p.run(timeout=60)
        assert not wd.summary()["healthy"]
        # posted by the source loop (the chain runs synchronously in the
        # source thread), so the blamed node is the source
        assert any(r.startswith("error:") and "wd crash" in r
                   for r in wd.summary()["reasons"])


class _FakeDevice:
    platform = "tpu"
    id = 0

    def memory_stats(self):
        return {
            "bytes_in_use": 1024,
            "peak_bytes_in_use": 2048,
            "bytes_limit": 4096,
            "num_allocs": 17,  # not a tracked key: never exposed
        }


class TestBusyDecay:
    def test_busy_gauge_decays_to_zero_after_stop(self, monkeypatch):
        """Scrape-time staleness fix: once the tracer stops (and its
        intervals age out of the window), the busy gauge must read 0 —
        not hold the last computed fraction forever."""
        monkeypatch.setenv("NNSTPU_OBS_BUSY_WINDOW_S", "0.3")
        reg = MetricsRegistry()
        got = []
        p = Pipeline(name="busydecay")
        src = p.add(DataSrc(data=[np.zeros(4, np.float32)] * 4, name="s"))
        filt = p.add(TensorFilter(framework="jax", model=_jax_model(),
                                  name="f"))
        p.link_chain(src, filt, p.add(TensorSink(callback=got.append)))
        tracer = p.attach_tracer(DeviceTracer(registry=reg))
        p.run(timeout=60)
        assert _wait_for(lambda: tracer.summary()["completed"] == 4)
        p.stop()
        gauge = reg.get("nnstpu_device_busy_fraction")
        assert gauge is not None and gauge.children()

        def decayed():
            reg.collect()
            return all(c.value == 0.0 for _, c in gauge.children())

        assert _wait_for(decayed, timeout=5.0)
        # the decay collector removes itself once the window has aged out:
        # the gauge can read 0 a moment before that (the last interval ended
        # before stop() set the deadline)
        def removed():
            reg.collect()
            return tracer._busy_decay_handle is None

        assert _wait_for(removed, timeout=5.0)

    def test_restart_replaces_leftover_decay_collector(self, monkeypatch):
        monkeypatch.setenv("NNSTPU_OBS_BUSY_WINDOW_S", "30")
        reg = MetricsRegistry()
        got = []
        p = Pipeline(name="busyrestart")
        src = p.add(DataSrc(data=[np.zeros(4, np.float32)] * 2, name="s"))
        filt = p.add(TensorFilter(framework="jax", model=_jax_model(),
                                  name="f"))
        p.link_chain(src, filt, p.add(TensorSink(callback=got.append)))
        tracer = p.attach_tracer(DeviceTracer(registry=reg))
        p.run(timeout=60)
        assert _wait_for(lambda: tracer.summary()["completed"] == 2)
        p.stop()
        assert tracer._busy_decay_handle is not None  # long window: armed
        tracer.start(p)  # re-attach: live collector replaces the decay
        try:
            assert tracer._busy_decay_handle is None
        finally:
            tracer.stop()


class TestMemoryGauges:
    def test_exposition_golden(self):
        """Pin the per-device memory exposition exactly."""
        reg = MetricsRegistry()
        register_memory_gauges(reg, devices=[_FakeDevice()])
        expected = "\n".join([
            "# HELP nnstpu_device_memory_bytes Per-device allocator stats "
            "(bytes), sampled at scrape time",
            "# TYPE nnstpu_device_memory_bytes gauge",
            'nnstpu_device_memory_bytes{device="tpu:0",kind="bytes_in_use"}'
            " 1024",
            'nnstpu_device_memory_bytes{device="tpu:0",kind="bytes_limit"}'
            " 4096",
            'nnstpu_device_memory_bytes{device="tpu:0",'
            'kind="peak_bytes_in_use"} 2048',
            "# HELP nnstpu_device_memory_peak_bytes Per-device peak bytes "
            "in use observed since the last scrape (watermark drained at "
            "read; allocator peak reset where supported)",
            "# TYPE nnstpu_device_memory_peak_bytes gauge",
            'nnstpu_device_memory_peak_bytes{device="tpu:0"} 2048',
        ]) + "\n"
        assert render_text(reg) == expected

    def test_snapshot_shape_and_real_devices_never_raise(self):
        snap = device_memory_snapshot(devices=[_FakeDevice()])
        assert snap == {"tpu:0": {"bytes_in_use": 1024,
                                  "peak_bytes_in_use": 2048,
                                  "bytes_limit": 4096}}
        # the real-device path (CPU here: no allocator stats) is safe
        assert isinstance(device_memory_snapshot(), dict)
        reg = MetricsRegistry()
        register_memory_gauges(reg)
        render_text(reg)  # collector runs; must not raise
