"""The device utilization lane (obs/util.py + device-lane wiring):
roofline math over synthetic cost payloads, the ``device_kind`` peak table
(a device outside it has no MFU), busy-fraction windowing over overlapping
multi-device spans, per-dispatch cost attribution on a CPU host,
``device_idle`` dead-time spans, and live wire-health gauges.
"""

import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.backends.jax_backend import JaxBackend, JaxModel
from nnstreamer_tpu.buffer import Frame
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.graph.node import Node
from nnstreamer_tpu.obs import hooks, spans
from nnstreamer_tpu.obs import util as obs_util
from nnstreamer_tpu.obs.collector import attribute_trace
from nnstreamer_tpu.obs.device import DeviceTracer, cost_info
from nnstreamer_tpu.obs.export import render_text, unregister_stats
from nnstreamer_tpu.obs.metrics import MetricsRegistry
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec


def _wait_for(cond, timeout=10.0, step=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


@pytest.fixture(autouse=True)
def _reset_util_state():
    yield
    obs_util.clear_costs()
    obs_util.reset_wire_health()
    unregister_stats("wire_health")


# -- roofline math over synthetic cost_analysis payloads ----------------------

class TestRoofline:
    def test_compute_vs_bandwidth_bound(self):
        # peak 100 TFLOP/s over 100 GB/s -> ridge = 1000 flops/byte
        rl = obs_util.roofline(2e12, 1e9, 1.0, peak_tf=100.0, peak_gb=100.0)
        assert rl["intensity"] == 2000.0
        assert rl["bound"] == "compute_bound"
        assert rl["mfu"] == pytest.approx(0.02)
        assert rl["achieved_tflops"] == pytest.approx(2.0)
        assert rl["achieved_gbs"] == pytest.approx(1.0)
        low = obs_util.roofline(1e9, 1e9, 1.0, peak_tf=100.0, peak_gb=100.0)
        assert low["bound"] == "bandwidth_bound"
        assert low["intensity"] == 1.0

    def test_zero_and_missing_flops(self):
        """Zero/missing flops (flaky CPU cost_analysis) degrade to
        mfu=None + unknown — never an exception."""
        for flops in (None, 0, 0.0):
            rl = obs_util.roofline(flops, None, 0.5, peak_tf=100.0,
                                   peak_gb=100.0)
            assert rl["mfu"] is None
            assert rl["achieved_tflops"] is None
            assert rl["bound"] == "unknown"

    def test_bytes_only_entry_is_bandwidth_bound(self):
        rl = obs_util.roofline(None, 4e9, 1.0, peak_tf=100.0, peak_gb=100.0)
        assert rl["mfu"] is None
        assert rl["achieved_gbs"] == pytest.approx(4.0)
        assert rl["bound"] == "bandwidth_bound"

    def test_degenerate_duration_and_garbage(self):
        assert obs_util.roofline(1e9, 1e6, 0.0)["mfu"] is None
        assert obs_util.roofline(1e9, 1e6, -1.0)["bound"] == "unknown"
        assert obs_util.roofline("x", "y", "z")["mfu"] is None

    def test_cost_info_payload_shapes(self):
        """cost_analysis() as the installed jax returns it (one dict),
        with missing keys, and from a backend that doesn't implement it."""

        class DictCA:
            def cost_analysis(self):
                return {"flops": 10.0, "bytes accessed": 20.0}

        class ZeroFlops:
            def cost_analysis(self):
                return {"flops": 0.0, "bytes accessed": 7.0}

        class NoneCA:
            def cost_analysis(self):
                return None

        class Raises:
            def cost_analysis(self):
                raise RuntimeError("unimplemented")

        assert cost_info(DictCA()) == {"flops": 10.0, "bytes": 20.0}
        assert cost_info(ZeroFlops()) == {"bytes": 7.0}  # zero flops drops
        assert cost_info(NoneCA()) == {}
        assert cost_info(Raises()) == {}

    def test_cost_info_of_a_real_executable(self):
        import jax
        import jax.numpy as jnp

        compiled = jax.jit(lambda x: x @ x).lower(
            jnp.ones((16, 16), jnp.float32)).compile()
        info = cost_info(compiled)
        assert info["flops"] > 0 and info["bytes"] > 0


class TestPeakTable:
    """One table keyed by device_kind; outside it there is no MFU."""

    def test_v5e_row_is_the_published_peak(self):
        assert obs_util.peak_tflops("TPU v5 lite") == 197.0
        assert obs_util.peak_gbs("TPU v5 lite") == 819.0
        rl = obs_util.roofline(197e12 / 2, 1e9, 1.0,
                               peak_tf=obs_util.peak_tflops("TPU v5 lite"),
                               peak_gb=obs_util.peak_gbs("TPU v5 lite"))
        assert rl["mfu"] == pytest.approx(0.5)
        assert rl["ridge"] == pytest.approx(197e12 / 819e9, rel=1e-3)
        assert rl["bound"] == "compute_bound"

    def test_unknown_kind_and_cpu_have_no_peak(self):
        for kind in ("TPU v9 imaginary", "cpu", "NVIDIA H100", ""):
            assert obs_util.peak_tflops(kind) is None
            assert obs_util.peak_gbs(kind) is None
        # this process runs on the CPU: the default lookup finds no row
        assert obs_util.peak_tflops() is None
        assert obs_util.peak_gbs() is None

    def test_no_peak_means_no_mfu_and_no_roofline_share(self):
        rl = obs_util.roofline(2e12, 1e9, 1.0)  # CPU host: peaks unknown
        assert rl["mfu"] is None
        assert rl["ridge"] is None
        assert rl["bound"] == "unknown"
        # what the dispatch did is still reported
        assert rl["achieved_tflops"] == pytest.approx(2.0)
        assert rl["achieved_gbs"] == pytest.approx(1.0)
        assert rl["intensity"] == 2000.0

    def test_no_peak_override_knobs_remain(self, monkeypatch):
        """A peak is a fact about a device, not an option: the old env /
        ini overrides are gone, so a CPU run cannot be given an MFU."""
        from nnstreamer_tpu.conf import DEFAULTS, SHORT_ENV

        monkeypatch.setenv("NNSTPU_PEAK_TFLOPS", "1.0")
        monkeypatch.setenv("NNSTPU_OBS_PEAK_TFLOPS", "1.0")
        assert obs_util.peak_tflops() is None
        assert "peak_tflops" not in DEFAULTS["obs"]
        assert "NNSTPU_PEAK_TFLOPS" not in SHORT_ENV


class TestCostRegistry:
    def test_register_and_lookup(self):
        key = obs_util.register_cost("m:abc", flops=5.0, bytes=10.0,
                                     bucket=8, model="m")
        info = obs_util.cost_of(key)
        assert info["flops"] == 5.0 and info["bytes"] == 10.0
        assert info["bucket"] == 8
        assert obs_util.cost_of("missing") is None
        assert obs_util.cost_of(None) is None

    def test_costless_entry_registers_as_none(self):
        """A fused wrapper / CPU entry with no usable cost still
        registers — its dispatches must resolve to mfu=None, not
        vanish."""
        obs_util.register_cost("m:empty", flops=0, bytes=None)
        info = obs_util.cost_of("m:empty")
        assert info is not None
        assert info["flops"] is None and info["bytes"] is None

    def test_registry_bounded(self):
        for i in range(obs_util._COST_CAP + 10):
            obs_util.register_cost(f"k{i}", flops=1.0)
        assert obs_util.cost_of("k0") is None  # oldest evicted
        assert obs_util.cost_of(f"k{obs_util._COST_CAP + 9}") is not None


# -- busy/idle interval accounting --------------------------------------------

class TestIntervals:
    def test_merge_overlapping_multi_device_spans(self):
        merged = obs_util.merge_intervals(
            [(0, 10), (5, 15), (20, 30), (30, 40), (50, 50)])
        assert merged == [(0, 15), (20, 40)]

    def test_busy_fraction_windowing(self):
        ivs = [(0, 10), (5, 15), (20, 30)]
        # full window 0..40: covered 15 + 10 = 25
        assert obs_util.busy_fraction(ivs, 0, 40) == pytest.approx(25 / 40)
        # window clipped into an interval
        assert obs_util.busy_fraction(ivs, 25, 35) == pytest.approx(0.5)
        # window past every interval
        assert obs_util.busy_fraction(ivs, 100, 200) == 0.0
        # empty/inverted window
        assert obs_util.busy_fraction(ivs, 10, 10) is None

    def test_idle_gaps(self):
        ivs = [(10, 20), (21, 30), (50, 60)]
        assert obs_util.idle_gaps(ivs, min_gap=5) == [(30, 20)]
        assert obs_util.idle_gaps(ivs, min_gap=1) == [(20, 1), (30, 20)]
        # window edges count when given
        assert obs_util.idle_gaps(ivs, min_gap=5, t0=0, t1=80) == [
            (0, 10), (30, 20), (60, 20)]
        assert obs_util.idle_gaps([], min_gap=5, t0=0, t1=10) == [(0, 10)]

    def test_device_usage_windowed_fractions(self):
        usage = obs_util.DeviceUsage(cap=16)
        usage.add("cpu:0", 1_000, 2_000)
        usage.add("cpu:0", 1_500, 3_000)  # overlap coalesces
        usage.add("cpu:1", 2_000, 2_500)
        fr = usage.busy_fractions(window_ns=10_000, now_ns=3_000)
        # cpu:0 window clips to its oldest interval start (1000):
        # covered 2000 of [1000, 3000)
        assert fr["cpu:0"] == pytest.approx(1.0)
        assert fr["cpu:1"] == pytest.approx(0.5)
        # a wider real window dilutes
        fr = usage.busy_fractions(window_ns=2_000, now_ns=4_000)
        assert fr["cpu:0"] == pytest.approx(0.5)  # [2000,4000): 1000 busy


# -- live wire-health metrics -------------------------------------------------

class TestWireHealth:
    def test_publish_sets_gauges_and_stats_provider(self):
        reg = MetricsRegistry()
        rec = obs_util.publish_wire_health(
            {"put_150k_ms": 0.4, "dispatch_ms": 0.1}, reg)
        assert rec["regime"] == "fast"
        text = render_text(reg)
        assert 'nnstpu_wire_put_ms{addr="local"} 0.4' in text
        assert 'nnstpu_wire_regime{addr="local"} 0' in text
        from nnstreamer_tpu.obs.export import stats_snapshot

        snap = stats_snapshot()
        assert snap["wire_health"]["regime"] == "fast"
        # a sick probe flips the regime gauge
        obs_util.publish_wire_health({"put_150k_ms": 22.0}, reg)
        assert 'nnstpu_wire_regime{addr="local"} 1' in render_text(reg)
        assert obs_util.last_wire_health()["regime"] == "slow"

    def test_per_addr_probes_and_edge_registry(self):
        reg = MetricsRegistry()
        obs_util.publish_wire_health({"put_150k_ms": 0.4}, reg)
        obs_util.publish_wire_health({"put_150k_ms": 9.0}, reg,
                                     addr="10.0.0.2:5000")
        text = render_text(reg)
        assert 'nnstpu_wire_put_ms{addr="local"} 0.4' in text
        assert 'nnstpu_wire_put_ms{addr="10.0.0.2:5000"} 9' in text
        by_addr = obs_util.wire_health_by_addr()
        assert by_addr["local"]["regime"] == "fast"
        assert by_addr["10.0.0.2:5000"]["regime"] == "slow"
        # the edge's record is addressable, never shadowing local
        assert obs_util.last_wire_health()["regime"] == "fast"
        assert obs_util.last_wire_health("10.0.0.2:5000")["regime"] == "slow"
        # stats provider: flat local shape + edges map
        from nnstreamer_tpu.obs.export import stats_snapshot

        snap = stats_snapshot()["wire_health"]
        assert snap["regime"] == "fast"
        assert snap["edges"]["10.0.0.2:5000"]["regime"] == "slow"
        # edge probers register/unregister for the watchdog walk
        obs_util.register_wire_edge("10.0.0.2:5000",
                                    lambda: {"put_150k_ms": 1.0})
        assert "10.0.0.2:5000" in obs_util.wire_edges()
        obs_util.unregister_wire_edge("10.0.0.2:5000")
        assert obs_util.wire_edges() == {}

    def test_regime_classification(self):
        assert obs_util.wire_regime(0.3) == "fast"
        assert obs_util.wire_regime(5.1) == "slow"
        assert obs_util.wire_regime(None) == "unknown"

    def test_probe_runs_on_cpu_host(self):
        h = obs_util.probe_wire_health(n=2, nbytes=1024)
        assert h["put_150k_ms"] >= 0 and h["dispatch_ms"] >= 0


# -- the wired-up device lane on a CPU host -----------------------------------

def _matmul_model(dim=64):
    import jax.numpy as jnp

    w = np.random.default_rng(0).standard_normal((dim, dim)).astype(
        np.float32)
    return JaxModel(
        apply=lambda p, x: jnp.tanh(x @ w),
        input_spec=TensorsSpec.of(
            TensorSpec(dtype=np.float32, shape=(dim,))),
    )


class TestUtilizationLane:
    def test_cost_stamped_spans_but_no_mfu_on_cpu(self):
        """The acceptance pipeline: a jax filter + DeviceTracer on a CPU
        host yields nnstpu_device_busy_fraction series and cost-stamped
        device_exec span args — but NO nnstpu_mfu series, no MFU in the
        by_device summary and no roofline class: the CPU's device_kind
        is not in the peak table."""
        reg = MetricsRegistry()
        p = Pipeline(name="util_lane")
        src = p.add(DataSrc(
            data=[np.ones(64, np.float32) for _ in range(6)], name="s"))
        filt = p.add(TensorFilter(framework="jax", model=_matmul_model(),
                                  name="f"))
        p.link_chain(src, filt, p.add(TensorSink(name="o")))
        tracer = p.attach_tracer(DeviceTracer(registry=reg))
        p.run(timeout=60)
        assert _wait_for(lambda: tracer.summary()["completed"] == 6)
        summ = tracer.summary()
        (label, dev), = summ["by_device"].items()
        assert dev["count"] == 6
        assert dev["mfu"] is None
        assert 0.0 <= dev["busy_fraction"] <= 1.0
        assert dev["cost_missing"] == 0

        execs = [r for r in spans.snapshot()
                 if r[0] == spans.PH_COMPLETE and r[4] == "device_exec"]
        assert len(execs) == 6
        args = execs[-1][9]
        assert args["flops"] > 0 and args["bytes"] > 0
        assert args["achieved_tflops"] > 0 and args["achieved_gbs"] > 0
        assert args["mfu"] is None
        assert args["roofline"] == "unknown"
        assert args["cost_key"]

        text = render_text(reg)
        assert "nnstpu_mfu{" not in text
        assert 'nnstpu_device_busy_fraction{device="%s"}' % label in text
        assert "nnstpu_roofline_dispatches_total" in text

    def test_costless_dispatch_included_with_mfu_none(self):
        """A dispatch whose executable lacks cost info (no backend, or a
        backend without cost_analysis) still lands in by_device — with
        mfu=None and a cost_missing count, never silently omitted."""
        reg = MetricsRegistry()
        p = Pipeline(name="util_nocost")
        node = p.add(Node(name="f"))  # no .backend: no cost key
        tracer = DeviceTracer(registry=reg, capacity=8)
        p._tracers.append(tracer)
        tracer.start(p)
        try:
            hooks.emit("device_dispatch", node,
                       Frame.of(np.zeros(4, np.float32)),
                       (np.zeros(4, np.float32),), time.perf_counter_ns())
            assert _wait_for(lambda: tracer.summary()["completed"] == 1)
            summ = tracer.summary()
            dev = summ["by_device"]["host"]
            assert dev["count"] == 1
            assert dev["mfu"] is None
            assert dev["cost_missing"] == 1
            execs = [r for r in spans.snapshot()
                     if r[0] == spans.PH_COMPLETE and r[4] == "device_exec"]
            assert execs[-1][9]["mfu"] is None
            assert execs[-1][9]["roofline"] == "unknown"
        finally:
            tracer.stop()

    def test_device_idle_gap_spans_and_attribution_leg(self, monkeypatch):
        """A gap >= [obs] device_idle_gap_ms between completions becomes
        a device_idle span on the device track, attributed to the
        waiting dispatch's trace — and attribute_trace reports it as the
        device_idle leg."""
        monkeypatch.setenv("NNSTPU_OBS_DEVICE_IDLE_GAP_MS", "10")
        reg = MetricsRegistry()
        p = Pipeline(name="util_idle")
        node = p.add(Node(name="f"))
        tracer = DeviceTracer(registry=reg, capacity=8)
        p._tracers.append(tracer)
        tracer.start(p)
        trace_id = spans.new_trace_id()
        frame = Frame.of(np.zeros(4, np.float32))
        frame.meta[spans.META_KEY] = [trace_id, 7, 0, None]
        try:
            hooks.emit("device_dispatch", node, frame,
                       (np.zeros(4, np.float32),), time.perf_counter_ns())
            assert _wait_for(lambda: tracer.summary()["completed"] == 1)
            time.sleep(0.05)  # 50 ms idle >> the 10 ms threshold
            hooks.emit("device_dispatch", node, frame,
                       (np.zeros(4, np.float32),), time.perf_counter_ns())
            assert _wait_for(lambda: tracer.summary()["completed"] == 2)
            idles = [r for r in spans.snapshot()
                     if r[0] == spans.PH_COMPLETE and r[4] == "device_idle"]
            assert len(idles) == 1
            args = idles[0][9]
            assert args["gap_ms"] >= 10
            assert args["reason"] in ("host_dispatch", "queue_wait", "wire")
            assert idles[0][6] == trace_id
            # the collector decomposition grows a device_idle leg
            recs = [r for r in spans.snapshot()
                    if r[0] == spans.PH_COMPLETE and r[6] == trace_id]
            legs = attribute_trace(recs)
            assert legs["device_idle"] > 0
            assert legs["device"] > 0
        finally:
            tracer.stop()

    def test_overlapping_multi_device_busy_windowing(self):
        """Mesh-style shards: overlapping spans on distinct devices keep
        distinct busy fractions; overlaps within one device coalesce."""
        usage = obs_util.DeviceUsage()
        t0 = 1_000_000
        for dev in ("tpu:0", "tpu:1"):
            usage.add(dev, t0, t0 + 1_000_000)
        usage.add("tpu:0", t0 + 500_000, t0 + 1_500_000)  # overlap
        fr = usage.busy_fractions(window_ns=2_000_000, now_ns=t0 + 2_000_000)
        assert fr["tpu:0"] == pytest.approx(0.75)
        assert fr["tpu:1"] == pytest.approx(0.5)


class TestBackendCostRegistration:
    def test_compile_registers_cost_and_hit_restores_key(self):
        be = JaxBackend()
        poly = JaxModel(
            apply=lambda p, x: x * 2,
            input_spec=TensorsSpec.of(
                TensorSpec(dtype=np.float32, shape=(None,))),
        )
        be.open(poly, custom="compile_cache=4")
        spec = TensorsSpec.of(TensorSpec(dtype=np.float32, shape=(64,)))
        be.reconfigure(spec)
        key1 = be.cost_key()
        assert key1
        info = obs_util.cost_of(key1)
        assert info is not None and info["bucket"] == 64
        # a second geometry gets its own key; re-selecting the first via
        # the LRU restores the first key
        spec2 = TensorsSpec.of(TensorSpec(dtype=np.float32, shape=(32,)))
        be.reconfigure(spec2)
        key2 = be.cost_key()
        assert key2 and key2 != key1
        be.reconfigure(spec)
        assert be.cost_key() == key1
