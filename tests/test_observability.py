"""Observability: tracer hooks, metrics registry, Prometheus exposition,
plus the older conf-driven dot dumps + per-pipeline latency stats."""

import os
import time
import urllib.request

import numpy as np
import pytest

from nnstreamer_tpu import Frame, Pipeline
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.queue import Queue
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.obs import hooks
from nnstreamer_tpu.obs.export import MetricsServer, render_text
from nnstreamer_tpu.obs.metrics import MetricsRegistry
from nnstreamer_tpu.obs.tracers import (
    DropsTracer,
    LatencyTracer,
    StatsTracer,
    make_tracer,
    parse_tracer_names,
)


def simple_pipeline(got):
    p = Pipeline(name="obs_test")
    src = p.add(DataSrc(data=[np.full(4, i, np.float32) for i in range(5)]))
    filt = p.add(
        TensorFilter(framework="custom", model=lambda x: x * 2, name="double")
    )
    sink = p.add(TensorSink(callback=got.append))
    p.link_chain(src, filt, sink)
    return p


def test_dump_dot_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("NNSTPU_COMMON_DUMP_DOT_DIR", str(tmp_path / "dots"))
    got = []
    simple_pipeline(got).run(timeout=30)
    path = tmp_path / "dots" / "obs_test.PLAYING.dot"
    assert path.exists()
    dot = path.read_text()
    assert "digraph" in dot and "double" in dot


class TestDotTransitions:
    """Satellite: {name}.{transition}.dot on EVERY state transition and on
    post_error — the full GST_DEBUG_DUMP_DOT_DIR analog."""

    def test_playing_and_stopped_dumps(self, tmp_path, monkeypatch):
        monkeypatch.setenv("NNSTPU_COMMON_DUMP_DOT_DIR", str(tmp_path))
        got = []
        simple_pipeline(got).run(timeout=30)
        assert (tmp_path / "obs_test.PLAYING.dot").exists()
        assert (tmp_path / "obs_test.STOPPED.dot").exists()

    def test_error_dump(self, tmp_path, monkeypatch):
        monkeypatch.setenv("NNSTPU_COMMON_DUMP_DOT_DIR", str(tmp_path))

        def boom(x):
            if float(np.max(x)) > 0:  # negotiation probes with zeros
                raise RuntimeError("dot crash")
            return x

        p = Pipeline(name="dot_err")
        src = p.add(DataSrc(data=[np.ones(4, np.float32)], name="s"))
        filt = p.add(TensorFilter(framework="custom", model=boom, name="f"))
        p.link_chain(src, filt, p.add(TensorSink(name="out")))
        from nnstreamer_tpu.graph.pipeline import PipelineError

        with pytest.raises(PipelineError):
            p.run(timeout=30)
        assert (tmp_path / "dot_err.ERROR.dot").exists()

    def test_stopped_dump_annotated_with_live_stats(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv("NNSTPU_COMMON_DUMP_DOT_DIR", str(tmp_path))
        got = []
        p = Pipeline(name="dot_ann")
        src = p.add(DataSrc(
            data=[np.zeros((4,), np.float32) for _ in range(5)], name="s"))
        q = p.add(Queue(max_size_buffers=8, name="q"))
        sink = p.add(TensorSink(callback=got.append, name="out"))
        p.link_chain(src, q, sink)
        p.attach_tracer(StatsTracer(registry=MetricsRegistry()))
        p.run(timeout=30)
        dot = (tmp_path / "dot_ann.STOPPED.dot").read_text()
        assert "5 frames" in dot, dot
        assert "depth" in dot


def test_conf_enables_profiling_and_stats(monkeypatch):
    monkeypatch.setenv("NNSTPU_COMMON_ENABLE_PROFILING", "true")
    got = []
    p = simple_pipeline(got)
    p.run(timeout=30)
    assert len(got) == 5
    stats = p.stats()
    assert "double" in stats
    assert stats["double"]["count"] == 5
    assert stats["double"]["p50_ms"] >= 0


def test_stats_scoped_to_pipeline(monkeypatch):
    monkeypatch.setenv("NNSTPU_COMMON_ENABLE_PROFILING", "true")
    from nnstreamer_tpu.utils import profiling

    profiling.record("not_in_this_pipeline", 123)
    got = []
    p = simple_pipeline(got)
    p.run(timeout=30)
    assert "not_in_this_pipeline" not in p.stats()


def test_xplane_trace_dir(tmp_path, monkeypatch):
    """conf-driven jax.profiler trace around the PLAYING interval (SURVEY
    §5's device-level tracing analog); trace files land in the dir."""
    trace_dir = tmp_path / "xplane"
    monkeypatch.setenv("NNSTPU_COMMON_XPLANE_TRACE_DIR", str(trace_dir))
    got = []
    simple_pipeline(got).run(timeout=60)
    assert len(got) == 5
    files = [
        os.path.join(r, f)
        for r, _, fs in os.walk(trace_dir) for f in fs
    ]
    assert files, "no xplane trace files were written"


class TestHookBus:
    def test_enabled_tracks_connections(self):
        assert hooks.enabled is False
        seen = []
        hooks.connect("pad_push", seen.append)
        assert hooks.enabled is True
        # a dummy 1-arg emit (real signature: (pad, item)) — fine for a
        # bus unit test, not for real sites
        hooks.emit("pad_push", "x")  # nnslint: disable=hooks
        assert seen == ["x"]
        hooks.disconnect("pad_push", seen.append)
        assert hooks.enabled is False

    def test_unknown_hook_rejected(self):
        with pytest.raises(ValueError, match="unknown hook"):
            hooks.connect("nope", lambda: None)

    def test_raising_callback_is_detached_not_fatal(self):
        def bad(*a):
            raise RuntimeError("boom")

        hooks.connect("error", bad)
        hooks.emit("error", None, None, None)  # must not raise
        assert hooks.enabled is False  # bad callback auto-detached

    @pytest.mark.parametrize("chain", ["nodes", "collect_pad"])
    def test_disabled_hot_loop_overhead(self, chain):
        """The acceptance guard: with no tracer installed the hook gate
        must add no measurable per-frame cost.  2000 frames through a
        3-node chain, or onto a ``CollectNode`` pad (an arrival that
        completes a round: stamps nothing, writes no wait); the bound is
        generous (100 us/frame) — it catches a regression to
        unconditional emission (dict/kwargs building, clock reads), not
        scheduler noise."""
        assert hooks.enabled is False
        from nnstreamer_tpu.elements.mux import TensorMux
        from nnstreamer_tpu.graph.node import Node
        from nnstreamer_tpu.obs import spans

        a, sink = Node(), TensorSink()
        ap = a.add_src_pad()
        if chain == "collect_pad":
            b = TensorMux(sync_mode="nosync")
            ap.link(b.add_sink_pad("sink_0"))
            bp = b.src_pads["src"]
        else:
            b = Node()
            ap.link(b.add_sink_pad())
            bp = b.add_src_pad()
        bp.link(sink.sink_pads["sink"])
        frame = Frame.of(np.zeros((4,), np.float32))
        n = 2000
        ap.push(frame)  # warm signature binding
        t0 = time.perf_counter_ns()
        for _ in range(n):
            ap.push(frame)
        per_frame_ns = (time.perf_counter_ns() - t0) / n
        assert per_frame_ns < 100_000, (
            f"disabled hook bus costs {per_frame_ns:.0f} ns/frame"
        )
        assert sink.num_frames == n + 1
        assert not getattr(b, "_waits", None) and not spans.snapshot()


class TestMetricsRegistry:
    def test_counter_gauge_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total", "help", labelnames=("el",))
        c.inc(2, el="a")
        c.labels(el="a").inc()
        assert c.labels(el="a").value == 3
        g = reg.gauge("g")
        g.set(7)
        assert g.labels().__class__  # no-label child path
        with pytest.raises(ValueError, match="labels"):
            c.inc(1)  # labelnames declared, labels required
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("c_total")

    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="only go up"):
            reg.counter("c").inc(-1)

    def test_histogram_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("h_ms", buckets=(1.0, 5.0))
        for v in (0.5, 3.0, 100.0):
            h.observe(v)
        cumulative, total, count = h.labels().snapshot()
        assert cumulative == [(1.0, 1), (5.0, 2), (float("inf"), 3)]
        assert count == 3 and total == 103.5

    def test_exposition_golden(self):
        """Pin the Prometheus text format exactly: HELP/TYPE headers,
        label quoting, histogram _bucket/_sum/_count, +Inf, int-vs-float
        value rendering."""
        reg = MetricsRegistry()
        reg.counter("nns_frames_total", "Frames seen",
                    labelnames=("element",)).inc(5, element="q0")
        reg.gauge("nns_depth", "Queue depth").set(2)
        h = reg.histogram("nns_lat_ms", "Latency", buckets=(1.0, 2.5))
        h.observe(0.5)
        h.observe(2.0)
        h.observe(9.75)
        expected = "\n".join([
            '# HELP nns_depth Queue depth',
            '# TYPE nns_depth gauge',
            'nns_depth 2',
            '# HELP nns_frames_total Frames seen',
            '# TYPE nns_frames_total counter',
            'nns_frames_total{element="q0"} 5',
            '# HELP nns_lat_ms Latency',
            '# TYPE nns_lat_ms histogram',
            'nns_lat_ms_bucket{le="1"} 1',
            'nns_lat_ms_bucket{le="2.5"} 2',
            'nns_lat_ms_bucket{le="+Inf"} 3',
            'nns_lat_ms_sum 12.25',
            'nns_lat_ms_count 3',
        ]) + "\n"
        assert render_text(reg) == expected

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter("c", labelnames=("p",)).inc(1, p='a"b\\c\nd')
        assert r'c{p="a\"b\\c\nd"} 1' in render_text(reg)

    def test_collector_runs_at_collect_time(self):
        reg = MetricsRegistry()
        state = {"v": 1}
        reg.add_collector(lambda: reg.gauge("live").set(state["v"]))
        assert "live 1" in render_text(reg)
        state["v"] = 42
        assert "live 42" in render_text(reg)


class TestLatencyTracer:
    def test_end_to_end_latency_per_frame(self):
        """The flagship acceptance path: per-frame src->sink latency is
        recorded for EVERY frame, correlated across a queue (thread hop)
        and a filter (payload replaced via with_tensors)."""
        reg = MetricsRegistry()
        got = []
        p = Pipeline(name="lat")
        src = p.add(DataSrc(
            data=[np.full(4, i, np.float32) for i in range(5)], name="s"))
        q = p.add(Queue(max_size_buffers=8))
        filt = p.add(TensorFilter(framework="custom", model=lambda x: x + 1,
                                  name="f"))
        sink = p.add(TensorSink(callback=got.append, name="out"))
        p.link_chain(src, q, filt, sink)
        tracer = p.attach_tracer(LatencyTracer(registry=reg))
        p.run(timeout=30)
        assert len(got) == 5
        summ = tracer.summary()
        assert list(summ) == ["s->out"]
        s = summ["s->out"]
        assert s["count"] == 5
        assert 0 < s["min_ms"] <= s["p50_ms"] <= s["p90_ms"] \
            <= s["p99_ms"] <= s["max_ms"]
        # same data as a histogram on the registry
        text = render_text(reg)
        assert ('nnstpu_e2e_latency_ms_count{pipeline="lat",src="s",'
                'sink="out"} 5') in text
        # and via pipeline.stats()
        assert p.stats()["tracers"]["latency"]["s->out"]["count"] == 5

    def test_hooks_detached_after_stop(self):
        p = Pipeline()
        src = p.add(DataSrc(data=[np.zeros((2,), np.float32)]))
        sink = p.add(TensorSink())
        p.link(src, sink)
        p.attach_tracer(LatencyTracer(registry=MetricsRegistry()))
        p.run(timeout=30)
        assert hooks.enabled is False


class TestStatsTracer:
    def test_per_element_throughput(self):
        reg = MetricsRegistry()
        got = []
        p = Pipeline(name="thr")
        src = p.add(DataSrc(
            data=[np.zeros((8,), np.float32) for _ in range(4)], name="s"))
        q = p.add(Queue(max_size_buffers=4, name="q"))
        sink = p.add(TensorSink(callback=got.append, name="out"))
        p.link_chain(src, q, sink)
        tracer = p.attach_tracer(StatsTracer(registry=reg))
        p.run(timeout=30)
        summ = tracer.summary()
        assert summ["s"] == {"frames": 4, "bytes": 128}
        assert summ["q"]["frames"] == 4 and summ["q"]["bytes"] == 128
        assert summ["q"]["queue_depth"] == 0  # drained at EOS
        text = render_text(reg)
        assert ('nnstpu_element_frames_total{pipeline="thr",element="s",'
                'pad="src"} 4') in text
        assert ('nnstpu_element_bytes_total{pipeline="thr",element="s",'
                'pad="src"} 128') in text


class TestDropCounters:
    """Satellite: leaky-mode drops are counted, not silent."""

    def _frames(self, n):
        return [Frame.of(np.full((2,), i, np.float32)) for i in range(n)]

    @pytest.mark.parametrize("backend", ["python", "native"])
    def test_frame_queue_backends_count_drops(self, backend):
        if backend == "native":
            from nnstreamer_tpu.native import available
            from nnstreamer_tpu.native.queue import NativeFrameQueue

            if not available():
                pytest.skip("native runtime unavailable")
            q = NativeFrameQueue(2)
        else:
            from nnstreamer_tpu.native.queue import PyFrameQueue

            q = PyFrameQueue(2)
        for f in self._frames(5):
            q.push(f, leaky="downstream")
        assert q.dropped == 3
        assert q.stats() == {"depth": 2, "capacity": 2, "dropped": 3}
        q.push(self._frames(1)[0], leaky="upstream")
        assert q.dropped == 4
        q.close()

    def test_queue_element_counts_and_reports(self):
        q = Queue(max_size_buffers=2, leaky="downstream", name="lq")
        for f in self._frames(5):
            q._dispatch(None, f)
        assert q.dropped == 3
        st = q.stats()
        assert st["dropped"] == 3 and st["depth"] == 2 \
            and st["capacity"] == 2 and st["leaky"] == "downstream"
        assert st["backend"] in ("native", "python")
        q.stop()
        # element-level counter survives the backend queue teardown
        assert q.stats()["dropped"] == 3

    def test_drops_tracer_sees_leaky_downstream(self):
        reg = MetricsRegistry()
        p = Pipeline(name="dr")
        q = p.add(Queue(max_size_buffers=2, leaky="downstream", name="lq"))
        tracer = p.attach_tracer(DropsTracer(registry=reg))
        tracer.start(p)  # install hooks without running the pipeline
        for f in self._frames(6):
            q._dispatch(None, f)
        assert q.dropped == 4
        assert tracer.summary()["lq"]["queue_downstream"] == 4
        assert ('nnstpu_drops_total{pipeline="dr",element="lq",'
                'reason="queue_downstream"} 4') in render_text(reg)
        q.stop()

    def test_drops_tracer_sees_rate_and_dynbatch(self):
        from nnstreamer_tpu.elements.dynbatch import DynBatch
        from nnstreamer_tpu.elements.rate import TensorRate

        reg = MetricsRegistry()
        p = Pipeline(name="rd")
        rate = p.add(TensorRate(framerate="10/1", name="r"))
        dyn = p.add(DynBatch(max_batch=4, name="d"))
        tracer = p.attach_tracer(DropsTracer(registry=reg))
        tracer.start(p)
        ms = 1_000_000
        # 3 frames inside the same 100ms slot: 2 drops
        for pts in (0, 10 * ms, 20 * ms):
            rate.process(None, Frame.of(np.zeros((2,), np.float32), pts=pts))
        # a 350ms jump: slots 1..3 fill by duplication (3 dups)
        rate.process(None, Frame.of(np.zeros((2,), np.float32), pts=350 * ms))
        # a 3-frame dynbatch flush pads to bucket 4 (1 padding row)
        dyn._emit_batch([Frame.of(np.zeros((2,), np.float32))
                         for _ in range(3)])
        summ = tracer.summary()
        assert summ["r"]["rate_drop"] == 2 == rate.drop
        assert summ["r"]["rate_dup"] == 3 == rate.dup
        assert summ["d"] == {"dynbatch_flushes": 1, "dynbatch_pad_rows": 1}
        text = render_text(reg)
        assert ('nnstpu_dups_total{pipeline="rd",element="d",'
                'reason="dynbatch_pad"} 1') in text


class TestConfActivation:
    """NNSTPU_TRACERS / NNSTPU_METRICS_PORT: the GST_TRACERS analog."""

    def test_parse_tracer_names(self):
        assert parse_tracer_names("latency;stats") == ["latency", "stats"]
        assert parse_tracer_names(" latency, drops ") == ["latency", "drops"]
        assert parse_tracer_names("") == []
        with pytest.raises(ValueError, match="unknown tracer"):
            make_tracer("nope")

    def test_env_driven_tracers(self, monkeypatch):
        monkeypatch.setenv("NNSTPU_TRACERS", "latency;stats")
        got = []
        p = simple_pipeline(got)
        p.run(timeout=30)
        tr = p.stats()["tracers"]
        assert set(tr) == {"latency", "stats"}
        lat = tr["latency"]
        assert len(lat) == 1
        (key, s), = lat.items()
        assert key.endswith("->" + [n for n in p.nodes
                                    if "sink" in n or "tensorsink" in n][0]) \
            or s["count"] == 5
        assert s["count"] == 5
        # a second run must not attach duplicate tracers
        p.run(timeout=30)
        assert set(p.stats()["tracers"]) == {"latency", "stats"}

    def test_scrape_endpoint_serves_exposition(self, monkeypatch):
        """Acceptance: run with tracers on, then pull the text exposition
        over HTTP from the stdlib scrape endpoint."""
        from nnstreamer_tpu.obs import export

        monkeypatch.setenv("NNSTPU_TRACERS", "latency;stats")
        monkeypatch.setenv("NNSTPU_METRICS_PORT", "0")  # ephemeral bind
        got = []
        try:
            simple_pipeline(got).run(timeout=30)
            server = export._server
            assert server is not None
            with urllib.request.urlopen(server.url, timeout=10) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith("text/plain")
                body = resp.read().decode("utf-8")
            assert "nnstpu_e2e_latency_ms_bucket" in body
            assert "nnstpu_element_frames_total" in body
        finally:
            export.shutdown_server()

    def test_metrics_server_direct(self):
        reg = MetricsRegistry()
        reg.counter("hits_total").inc(3)
        with MetricsServer(port=0, registry=reg) as srv:
            with urllib.request.urlopen(srv.url, timeout=10) as resp:
                body = resp.read().decode("utf-8")
        assert "hits_total 3" in body


class TestHealthAndStatsEndpoints:
    """Satellite: /healthz liveness + /stats.json (pipeline + sched
    stats() merged) next to the Prometheus scrape path."""

    def _get(self, srv, path):
        url = f"http://{srv.host}:{srv.port}{path}"
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.headers["Content-Type"], resp.read()

    def test_healthz(self):
        import json as _json

        with MetricsServer(port=0, registry=MetricsRegistry()) as srv:
            status, ctype, body = self._get(srv, "/healthz")
        assert status == 200
        assert ctype.startswith("application/json")
        doc = _json.loads(body)
        assert doc["status"] == "ok"
        assert doc["failures"] == {} and doc["degraded"] == {}

    def test_healthz_degraded_carries_reason(self):
        """Satellite (fleet PR): a degraded-but-serving worker answers
        200 with the WHY in the JSON body — membership and operators see
        the reason, not just a flag — and /stats.json mirrors it under
        'health'."""
        import json as _json

        from nnstreamer_tpu.obs.export import (
            register_degraded,
            unregister_degraded,
        )

        fn = lambda: "jax:f: compile failed; pinned to CPU"  # noqa: E731
        register_degraded("jax:f", fn)
        try:
            with MetricsServer(port=0, registry=MetricsRegistry()) as srv:
                status, ctype, body = self._get(srv, "/healthz")
                s_status, _, s_body = self._get(srv, "/stats.json")
            assert status == 200  # degraded is NOT an outage
            doc = _json.loads(body)
            assert doc["status"] == "degraded"
            assert "pinned to CPU" in doc["degraded"]["jax:f"]
            stats = _json.loads(s_body)
            assert stats["health"]["status"] == "degraded"
            assert "pinned to CPU" in stats["health"]["degraded"]["jax:f"]
        finally:
            unregister_degraded("jax:f", fn)

    def test_stats_json_merges_providers(self):
        import json as _json

        from nnstreamer_tpu.obs.export import register_stats, unregister_stats

        fn = lambda: {"frames": 7, "note": "hi"}  # noqa: E731
        bad = lambda: 1 / 0  # noqa: E731
        register_stats("pipe_x", fn)
        register_stats("bad_prov", bad)
        try:
            with MetricsServer(port=0, registry=MetricsRegistry()) as srv:
                status, ctype, body = self._get(srv, "/stats.json")
            assert status == 200 and ctype.startswith("application/json")
            doc = _json.loads(body)
            assert doc["pipe_x"] == {"frames": 7, "note": "hi"}
            assert "error" in doc["bad_prov"]  # a bad provider never 500s
        finally:
            unregister_stats("pipe_x", fn)
            unregister_stats("bad_prov", bad)

    def test_pipeline_and_sched_register(self, monkeypatch):
        from nnstreamer_tpu.obs.export import stats_snapshot, unregister_stats
        from nnstreamer_tpu.sched import Scheduler

        got = []
        p = simple_pipeline(got)
        p.run(timeout=30)
        sch = Scheduler("fifo", name="statsrv", registry=MetricsRegistry())
        try:
            snap = stats_snapshot()
            assert "obs_test" in snap  # the pipeline's stats()
            assert snap["sched:statsrv"]["dispatched"] == 0
        finally:
            sch.close()
            unregister_stats("obs_test")
        assert "sched:statsrv" not in stats_snapshot()


class TestConfigurableBuckets:
    """Satellite: NNSTPU_METRICS_BUCKETS / [obs] buckets override the
    fixed latency-bucket list, resolved at histogram creation."""

    def test_env_override_short_spelling(self, monkeypatch):
        monkeypatch.setenv("NNSTPU_METRICS_BUCKETS", "1, 10; 100")
        reg = MetricsRegistry()
        h = reg.histogram("lat_custom_ms")
        assert h.buckets == (1.0, 10.0, 100.0)

    def test_conf_section_spelling(self, monkeypatch):
        monkeypatch.delenv("NNSTPU_METRICS_BUCKETS", raising=False)
        monkeypatch.setenv("NNSTPU_OBS_BUCKETS", "0.5,5")
        reg = MetricsRegistry()
        assert reg.histogram("lat_conf_ms").buckets == (0.5, 5.0)

    def test_default_and_malformed_fall_back(self, monkeypatch):
        from nnstreamer_tpu.obs.metrics import (
            LATENCY_BUCKETS_MS,
            configured_latency_buckets,
        )

        monkeypatch.delenv("NNSTPU_METRICS_BUCKETS", raising=False)
        assert configured_latency_buckets() == LATENCY_BUCKETS_MS
        monkeypatch.setenv("NNSTPU_METRICS_BUCKETS", "fast,slow")
        with pytest.warns(UserWarning, match="bucket"):
            assert configured_latency_buckets() == LATENCY_BUCKETS_MS

    def test_exposition_uses_override(self, monkeypatch):
        monkeypatch.setenv("NNSTPU_METRICS_BUCKETS", "2.5,25")
        reg = MetricsRegistry()
        got = []
        p = Pipeline(name="bkt")
        src = p.add(DataSrc(data=[np.zeros(4, np.float32)], name="s"))
        p.link(src, p.add(TensorSink(callback=got.append, name="out")))
        p.attach_tracer(LatencyTracer(registry=reg))
        p.run(timeout=30)
        text = render_text(reg)
        assert 'le="2.5"' in text and 'le="25"' in text
        assert 'le="0.05"' not in text  # the stock list is replaced


class TestProfilingRehome:
    def test_p99_ceil_rank_and_p90(self):
        """Satellite: the old floor-rank p99 returned the MAX for any
        n <= 100; ceil-based nearest rank must return the 99th of 100."""
        from nnstreamer_tpu.utils import profiling

        for v in range(1, 101):  # 1..100 ms
            profiling.record("el", v * 1_000_000)
        s = profiling.stats()["el"]
        assert s["p99_ms"] == 99.0  # not 100.0
        assert s["p90_ms"] == 90.0
        assert s["p50_ms"] == 50.0
        assert s["min_ms"] == 1.0 and s["max_ms"] == 100.0

    def test_record_feeds_obs_registry(self):
        from nnstreamer_tpu.obs.metrics import REGISTRY
        from nnstreamer_tpu.utils import profiling

        profiling.record("rehomed_node", 2_000_000)  # 2 ms
        hist = REGISTRY.get("nnstpu_node_invoke_latency_ms")
        assert hist is not None
        child = hist.labels(node="rehomed_node")
        assert child.count >= 1


class TestServingExport:
    def test_engine_stats_republished_as_gauges(self):
        from nnstreamer_tpu.serving import ContinuousBatcher

        eng = ContinuousBatcher(capacity=2, t_max=8, d_in=4, n_out=2,
                                d_model=8, n_heads=2, n_layers=1)
        reg = MetricsRegistry()
        handle = eng.publish_metrics(registry=reg)
        try:
            with eng.open_session() as sess:
                sess.feed(np.zeros((4,), np.float32))
                sess.get(timeout=10)
                text = render_text(reg)
                assert "nnstpu_serving_capacity 2" in text
                assert "nnstpu_serving_active_sessions 1" in text
                assert "nnstpu_serving_steps_total 1" in text
        finally:
            reg.remove_collector(handle)
            eng.stop()


class TestHistogramHygiene:
    """Satellites: duplicate bucket bounds collapse (a repeated bound
    would emit two identical cumulative `le` series, which Prometheus
    rejects) and re-registering with a DIFFERENT grid is an error, not a
    silent divergence between declared and exported buckets."""

    def test_duplicate_bounds_deduped(self):
        from nnstreamer_tpu.obs.metrics import parse_buckets

        reg = MetricsRegistry()
        h = reg.histogram("h_ms", buckets=(5.0, 1.0, 5.0, 1.0))
        assert h.buckets == (1.0, 5.0)
        h.observe(3.0)
        text = render_text(reg)
        assert text.count('le="5"') == 1
        assert parse_buckets("5, 1; 5,1") == (1.0, 5.0)

    def test_bucket_drift_raises(self):
        reg = MetricsRegistry()
        h = reg.histogram("h_ms", buckets=(1.0, 5.0))
        # identical grid (any ordering/duplication) is idempotent
        assert reg.histogram("h_ms", buckets=(5.0, 1.0, 5.0)) is h
        assert reg.histogram("h_ms") is h  # None = accept existing
        with pytest.raises(ValueError, match="buckets"):
            reg.histogram("h_ms", buckets=(1.0, 2.0))


class TestHistogramWindowHelpers:
    """Satellite: the ONE shared windowed-delta/quantile implementation
    (burn-rate engine, autoscaler, profiling all consume these)."""

    def test_deltas_are_windowed_not_lifetime(self):
        from nnstreamer_tpu.obs.metrics import histogram_deltas

        reg = MetricsRegistry()
        h = reg.histogram("h_ms", buckets=(10.0, 50.0), labelnames=("t",))
        prev = {}
        h.labels(t="a").observe(5.0)
        h.labels(t="a").observe(100.0)
        d1 = dict(histogram_deltas(h, prev))
        assert d1[10.0] == 1 and d1[float("inf")] == 1
        # second call sees only NEW observations (zero-growth buckets
        # are elided)
        h.labels(t="a").observe(30.0)
        d2 = dict(histogram_deltas(h, prev))
        assert d2 == {50.0: 1}

    def test_label_filter_scopes_children(self):
        from nnstreamer_tpu.obs.metrics import histogram_deltas

        reg = MetricsRegistry()
        h = reg.histogram("h_ms", buckets=(10.0,), labelnames=("t",))
        h.labels(t="a").observe(5.0)
        h.labels(t="b").observe(5.0)
        assert sum(n for _b, n in histogram_deltas(h, {}, {"t": "a"})) == 1

    def test_quantile_over_deltas(self):
        from nnstreamer_tpu.obs.metrics import histogram_quantile

        deltas = [(10.0, 90), (50.0, 9), (float("inf"), 1)]
        assert histogram_quantile(0.50, deltas) == 10.0
        assert histogram_quantile(0.95, deltas) == 50.0
        assert histogram_quantile(0.999, deltas, inf_value=1e9) == 1e9
        assert histogram_quantile(0.5, [], empty_value=-1.0) == -1.0


class TestExemplars:
    """Tentpole: per-bucket last-exemplar retention, stamped from the
    active span context, exposed in OpenMetrics syntax on demand."""

    def observe_traced(self, h, value):
        from nnstreamer_tpu.obs import spans as _spans

        tid = _spans.new_trace_id()
        tok = _spans.span_begin(tid, 0)
        try:
            h.labels(pipeline="p").observe(value)
        finally:
            _spans.span_end(tok, "unit", "test")
        return tid

    def test_exemplar_stamped_from_live_span(self):
        from nnstreamer_tpu.obs import spans as _spans

        reg = MetricsRegistry()
        h = reg.histogram("lat_ms", buckets=(1.0, 10.0),
                          labelnames=("pipeline",))
        _spans.enable()
        try:
            h.labels(pipeline="p").observe(0.5)  # no live span: no exemplar
            tid = self.observe_traced(h, 99.0)   # lands in +Inf
        finally:
            _spans.reset()
        ex = h.labels(pipeline="p").exemplars()
        assert ex[0] is None  # enabled alone is not enough — span required
        got_tid, value, ts = ex[2]
        assert got_tid == tid and value == 99.0 and ts > 0

    def test_no_exemplar_without_tracing(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_ms", buckets=(1.0,))
        h.observe(0.5)
        assert h.labels().exemplars() == [None, None]

    def test_openmetrics_exposition_golden(self):
        from nnstreamer_tpu.obs import spans as _spans

        reg = MetricsRegistry()
        h = reg.histogram("lat_ms", "Latency", buckets=(1.0, 10.0),
                          labelnames=("pipeline",))
        _spans.enable()
        try:
            tid = self.observe_traced(h, 99.0)
        finally:
            _spans.reset()
        plain = render_text(reg)
        assert "# {" not in plain  # default exposition stays 0.0.4-clean
        text = render_text(reg, exemplars=True)
        line = next(l for l in text.splitlines() if 'le="+Inf"' in l)
        assert line.startswith(
            f'lat_ms_bucket{{pipeline="p",le="+Inf"}} 1 '
            f'# {{trace_id="{tid:x}"}} 99 ')
        # buckets that never saw a traced observe stay exemplar-free
        assert '# {' not in next(
            l for l in text.splitlines() if 'le="1"' in l)

    def test_federation_preserves_exemplar(self):
        from nnstreamer_tpu.obs import spans as _spans
        from nnstreamer_tpu.obs.collector import federate_metrics

        reg = MetricsRegistry()
        h = reg.histogram("lat_ms", "Latency", buckets=(1.0,),
                          labelnames=("pipeline",))
        _spans.enable()
        try:
            tid = self.observe_traced(h, 99.0)
        finally:
            _spans.reset()
        merged = federate_metrics(
            {"w0": render_text(reg, exemplars=True)})
        line = next(l for l in merged.splitlines() if 'le="+Inf"' in l)
        assert line.startswith('lat_ms_bucket{worker="w0",pipeline="p"')
        assert f'# {{trace_id="{tid:x}"}} 99 ' in line

    def test_exemplar_trace_joins_merged_perfetto_doc(self):
        """The operator workflow the tentpole exists for: scrape an
        exemplar off a tail bucket, find that trace in the collector's
        merged Perfetto document."""
        from nnstreamer_tpu.obs import spans as _spans
        from nnstreamer_tpu.obs.collector import TraceCollector

        reg = MetricsRegistry()
        h = reg.histogram("lat_ms", buckets=(1.0,),
                          labelnames=("pipeline",))
        col = TraceCollector()
        col.add_local("unit")
        _spans.enable()
        try:
            tid = self.observe_traced(h, 99.0)
            doc = col.chrome_trace()
        finally:
            _spans.reset()
        got_tid, _v, _ts = h.labels(pipeline="p").exemplars()[-1]
        assert got_tid == tid
        ids = {e.get("args", {}).get("trace_id")
               for e in doc["traceEvents"] if e.get("ph") == "X"}
        assert f"{tid:x}" in ids
