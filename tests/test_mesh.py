"""Mesh-sharded dispatch: the forced-host 8-device correctness harness.

conftest.py pins ``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
so every test here exercises a REAL 8-device mesh (CPU devices, same XLA
partitioner as a v5e-8): spec parsing/conf activation, batch-axis-sharded
executables numerically equivalent to the single-device path (padded
tails included), executable-cache keying by (geometry, mesh), per-shard
bucket sizing in tensor_dynbatch and the query server, and the device
lane's per-mesh-device Perfetto tracks and metric series.
"""

import time

import jax
import numpy as np
import pytest

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.backends.jax_backend import JaxBackend, JaxModel
from nnstreamer_tpu.elements.dynbatch import DynBatch, DynUnbatch, mesh_bucket
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.parallel import mesh as pmesh
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec


@pytest.fixture(autouse=True)
def _mesh_isolation(monkeypatch):
    """Every test starts with mesh mode OFF and a cold spec cache; tests
    opt in via ``monkeypatch.setenv("NNSTPU_MESH", ...)`` + reset."""
    monkeypatch.delenv("NNSTPU_MESH", raising=False)
    monkeypatch.delenv("NNSTPU_MESH_SPEC", raising=False)
    pmesh.reset_dispatch_mesh()
    yield
    pmesh.reset_dispatch_mesh()


def _mesh_on(monkeypatch, spec="dp:8"):
    monkeypatch.setenv("NNSTPU_MESH", spec)
    pmesh.reset_dispatch_mesh()


def _affine_model(batch=None):
    w = np.arange(16, dtype=np.float32).reshape(4, 4) / 7.0
    spec = TensorsSpec.of(TensorSpec(dtype=np.float32, shape=(batch, 4)))
    return JaxModel(
        apply=lambda p, x: x @ p["w"] + 1.5,
        params={"w": w},
        input_spec=spec,
        name="affine",
    ), w


class TestMeshSpec:
    def test_parse_variants(self):
        assert pmesh.parse_mesh_spec("") == ("dp", 1)
        assert pmesh.parse_mesh_spec("off") == ("dp", 1)
        assert pmesh.parse_mesh_spec("0") == ("dp", 1)
        assert pmesh.parse_mesh_spec("1") == ("dp", 1)
        assert pmesh.parse_mesh_spec("auto") == ("dp", 0)
        assert pmesh.parse_mesh_spec("dp:8") == ("dp", 8)
        assert pmesh.parse_mesh_spec("data") == ("data", 0)
        assert pmesh.parse_mesh_spec("4") == ("dp", 4)
        assert pmesh.parse_mesh_spec("DP:2") == ("dp", 2)
        with pytest.raises(ValueError):
            pmesh.parse_mesh_spec("dp:eight")

    def test_off_by_default(self):
        assert pmesh.dispatch_mesh() is None
        assert pmesh.dispatch_mesh_devices() == 1

    def test_env_activation_and_clamp(self, monkeypatch):
        _mesh_on(monkeypatch, "dp:8")
        mesh = pmesh.dispatch_mesh()
        assert mesh is not None and mesh.devices.size == 8
        assert pmesh.dispatch_mesh_devices() == 8
        assert pmesh.dispatch_mesh_axis() == "dp"
        # more devices than the host has: auto-clamp to what exists
        _mesh_on(monkeypatch, "dp:64")
        assert pmesh.dispatch_mesh().devices.size == len(jax.devices())
        _mesh_on(monkeypatch, "auto")
        assert pmesh.dispatch_mesh().devices.size == len(jax.devices())
        _mesh_on(monkeypatch, "dp:1")
        assert pmesh.dispatch_mesh() is None

    def test_conf_ini_form(self, monkeypatch):
        # the [mesh] spec key maps to NNSTPU_MESH_SPEC; the short
        # spelling NNSTPU_MESH wins over it
        monkeypatch.setenv("NNSTPU_MESH_SPEC", "dp:4")
        pmesh.reset_dispatch_mesh()
        assert pmesh.dispatch_mesh().devices.size == 4
        monkeypatch.setenv("NNSTPU_MESH", "dp:2")
        pmesh.reset_dispatch_mesh()
        assert pmesh.dispatch_mesh().devices.size == 2

    def test_mesh_cache_key_identity(self):
        m8 = pmesh.make_mesh((8,), ("dp",))
        m4 = pmesh.make_mesh((4,), ("dp",))
        assert pmesh.mesh_cache_key(None) is None
        assert pmesh.mesh_cache_key(m8) == pmesh.mesh_cache_key(
            pmesh.make_mesh((8,), ("dp",)))
        assert pmesh.mesh_cache_key(m8) != pmesh.mesh_cache_key(m4)


class TestMeshBucket:
    def test_single_device_ladder(self):
        assert [mesh_bucket(n, 8) for n in (1, 2, 3, 5, 8, 9)] == \
            [1, 2, 4, 8, 8, 8]

    def test_per_shard_ladder(self):
        # max_batch is PER SHARD: totals are ndev × pow-2
        assert mesh_bucket(1, 8, 8) == 8
        assert mesh_bucket(8, 8, 8) == 8
        assert mesh_bucket(9, 8, 8) == 16
        assert mesh_bucket(17, 8, 8) == 32
        assert mesh_bucket(33, 8, 8) == 64
        assert mesh_bucket(64, 8, 8) == 64
        assert mesh_bucket(100, 8, 8) == 64  # capped at ndev × max_batch
        # every bucket divides the mesh
        for n in range(1, 70):
            assert mesh_bucket(n, 8, 8) % 8 == 0


class TestMeshBackend:
    def _compile_events(self):
        events = []
        from nnstreamer_tpu.obs import hooks

        def on_compile(backend, key, result, dur_ns, info):
            events.append(result)

        hooks.connect("compile", on_compile)
        return events, lambda: hooks.disconnect("compile", on_compile)

    def test_sharded_matches_single_device(self, monkeypatch):
        model, w = _affine_model()
        x = np.random.default_rng(0).standard_normal((16, 4)).astype(
            np.float32)
        single = JaxBackend()
        single.open(model)
        single.reconfigure(TensorsSpec.from_arrays((x,)))
        (ref,) = single.invoke((x,))
        ref = np.asarray(ref)

        _mesh_on(monkeypatch, "dp:8")
        sharded = JaxBackend()
        sharded.open(model)
        sharded.reconfigure(TensorsSpec.from_arrays((x,)))
        assert sharded._mesh is not None
        (out,) = sharded.invoke((x,))
        assert len(out.sharding.device_set) == 8
        # two partitionings of one float32 dot of 4 terms may add them in
        # another order: a few ulps of the output's scale, no more
        ulps = 4 * np.finfo(np.float32).eps * np.abs(ref).max()
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5,
                                   atol=ulps)
        np.testing.assert_allclose(ref, x @ w + 1.5, rtol=1e-5, atol=ulps)

    def test_unshardable_geometry_falls_back(self, monkeypatch):
        _mesh_on(monkeypatch, "dp:8")
        model, w = _affine_model()
        b = JaxBackend()
        b.open(model)
        x = np.ones((3, 4), np.float32)  # 3 % 8 != 0
        b.reconfigure(TensorsSpec.from_arrays((x,)))
        assert b._mesh is None  # this geometry compiled single-device
        (out,) = b.invoke((x,))
        np.testing.assert_allclose(np.asarray(out), x @ w + 1.5, rtol=1e-5)

    def test_executable_cache_keys_by_mesh(self, monkeypatch):
        """One compile per (geometry, mesh); repeats hit; a mesh flip on
        the same geometry is a distinct executable, not a stale reuse."""
        model, _ = _affine_model(batch=None)
        b = JaxBackend()
        b.open(model)
        events, detach = self._compile_events()
        try:
            x = np.ones((16, 4), np.float32)
            spec = TensorsSpec.from_arrays((x,))
            b.reconfigure(spec)
            for _ in range(5):
                b.invoke((x,))
            assert events.count("miss") == 1  # no per-frame churn
            _mesh_on(monkeypatch, "dp:8")
            b.reconfigure(spec)
            assert b._mesh is not None
            for _ in range(5):
                b.invoke((x,))
            assert events.count("miss") == 2  # same geometry, new mesh
            # back to single-device: the cached unsharded executable hits
            monkeypatch.delenv("NNSTPU_MESH")
            pmesh.reset_dispatch_mesh()
            b.reconfigure(spec)
            assert events.count("miss") == 2
            assert events.count("hit") >= 1
        finally:
            detach()

    def test_wire_rule_and_upload_sharding(self, monkeypatch):
        """With a mesh the wire keeps the batch dim and
        ``wire_input_sharding`` hands tensor_upload the batch-axis
        NamedSharding so uploads land pre-distributed."""
        from nnstreamer_tpu.backends.jax_backend import (
            batched_wire_shape, flat_wire_shape)

        model = JaxModel(
            apply=lambda p, x: x * 2.0,
            input_spec=TensorsSpec.of(
                TensorSpec(dtype=np.float32, shape=(16, 4, 4))))
        b = JaxBackend()
        b.open(model)
        assert b._wire_shape((16, 4, 4)) == flat_wire_shape((16, 4, 4)) \
            == (256,)
        _mesh_on(monkeypatch, "dp:8")
        assert b._wire_shape((16, 4, 4)) == batched_wire_shape((16, 4, 4)) \
            == (16, 16)
        b.reconfigure(TensorsSpec.of(
            TensorSpec(dtype=np.float32, shape=(16, 4, 4))))
        sh = b.wire_input_sharding(0)
        assert sh is not None and len(sh.device_set) == 8
        # the sharded put round-trips the payload
        put = jax.device_put(np.ones((16, 16), np.float32), sh)
        assert len(put.sharding.device_set) == 8

    def test_degraded_backend_never_shards(self, monkeypatch):
        _mesh_on(monkeypatch, "dp:8")
        model, _ = _affine_model()
        b = JaxBackend()
        b.open(model)
        b._degraded = "synthetic: device lost"
        assert b._mesh_config() == (None, "dp")
        assert b.mesh_devices() == 1


class TestDynBatchMesh:
    def _run_pipeline(self, n_frames, max_batch=4):
        got = []
        model = JaxModel(apply=lambda p, x: x * 3.0 + 0.5, input_spec=None)
        p = Pipeline(name="mesh_dyn")
        src = p.add(DataSrc(
            data=[np.full((4,), i, np.float32) for i in range(n_frames)],
            name="s"))
        db = p.add(DynBatch(max_batch=max_batch, name="db"))
        filt = p.add(TensorFilter(framework="jax", model=model, name="f"))
        un = p.add(DynUnbatch(name="un"))
        p.link_chain(src, db, filt, un,
                     p.add(TensorSink(callback=got.append, name="out")))
        p.run(timeout=120)
        return got, db

    def test_e2e_equivalent_with_padded_tails(self, monkeypatch):
        """dynbatch → mesh filter → dynunbatch returns exactly the
        single-device stream: 11 frames never divide 8, so every flush
        pads to the per-shard bucket and dynunbatch strips it."""
        ref, _ = self._run_pipeline(11)
        assert len(ref) == 11
        _mesh_on(monkeypatch, "dp:8")
        got, db = self._run_pipeline(11)
        assert len(got) == 11
        assert db._mesh_dev == 8
        ref_vals = sorted(float(f.tensors[0][0]) for f in ref)
        got_vals = sorted(float(f.tensors[0][0]) for f in got)
        np.testing.assert_allclose(got_vals, ref_vals, rtol=1e-6)
        np.testing.assert_allclose(
            got_vals, [i * 3.0 + 0.5 for i in range(11)], rtol=1e-6)

    def test_per_device_spans_and_metrics(self, monkeypatch):
        """One sharded dispatch yields ndev device_exec spans on ndev
        ``device:<platform>:<ordinal>`` Perfetto rows and ndev
        ``nnstpu_device_exec_seconds{device=...}`` series — shard skew is
        visible per chip."""
        from nnstreamer_tpu.obs import spans
        from nnstreamer_tpu.obs.device import DeviceTracer
        from nnstreamer_tpu.obs.export import render_text
        from nnstreamer_tpu.obs.metrics import MetricsRegistry

        _mesh_on(monkeypatch, "dp:8")
        reg = MetricsRegistry()
        got = []
        model = JaxModel(apply=lambda p, x: x + 1.0, input_spec=None)
        p = Pipeline(name="mesh_obs")
        src = p.add(DataSrc(
            data=[np.full((4,), i, np.float32) for i in range(16)],
            name="s"))
        db = p.add(DynBatch(max_batch=8, name="db"))
        filt = p.add(TensorFilter(framework="jax", model=model, name="f"))
        un = p.add(DynUnbatch(name="un"))
        p.link_chain(src, db, filt, un,
                     p.add(TensorSink(callback=got.append, name="out")))
        tracer = p.attach_tracer(DeviceTracer(registry=reg))
        p.run(timeout=120)
        assert len(got) == 16
        deadline = time.time() + 30
        while time.time() < deadline:
            s = tracer.summary()
            if s["completed"] == s["dispatches"] and s["dispatches"] > 0:
                break
            time.sleep(0.05)
        summ = tracer.summary()
        assert summ["dispatches"] >= 1 and summ["dropped"] == 0
        assert len(summ["by_device"]) == 8, summ["by_device"]

        doc = spans.chrome_trace(p.flight_snapshot())
        events = doc["traceEvents"]
        rows = {e["tid"]: e["args"]["name"] for e in events
                if e.get("ph") == "M" and e["name"] == "thread_name"}
        dev_rows = sorted(v for v in rows.values()
                          if v.startswith("device:cpu:"))
        assert dev_rows == [f"device:cpu:{i}" for i in range(8)], dev_rows
        execs = [e for e in events
                 if e.get("ph") == "X" and e["name"] == "device_exec"]
        assert {e["args"]["device"] for e in execs} == \
            {f"cpu:{i}" for i in range(8)}
        # ndev spans per dispatch, all flow-linked from ONE host dispatch
        assert len(execs) == 8 * summ["dispatches"]

        text = render_text(reg)
        series = [ln for ln in text.splitlines()
                  if ln.startswith("nnstpu_device_exec_seconds_count")]
        assert len(series) == 8, series
        assert any('device="cpu:7"' in ln for ln in series)

    def test_compile_once_per_bucket_no_frame_churn(self, monkeypatch):
        """The acceptance bar: a steady stream through a mesh dynbatch
        compiles once per (bucket, mesh) pair — nnstpu_compile_total
        shows no per-frame churn."""
        from nnstreamer_tpu.obs import hooks

        misses = []

        def on_compile(backend, key, result, dur_ns, info):
            if result == "miss":
                misses.append(key)

        _mesh_on(monkeypatch, "dp:8")
        hooks.connect("compile", on_compile)
        try:
            got, _ = self._run_pipeline(48, max_batch=4)
        finally:
            hooks.disconnect("compile", on_compile)
        assert len(got) == 48
        # buckets are ndev×pow-2 ≤ ndev×max_batch: at most 3 distinct
        # geometries (8, 16, 32 rows) regardless of 48 frames served.
        # Which of them a run meets goes by how fast the source pushes;
        # the negotiation's one-row probe compiles once more, on one
        # device (its key carries no mesh), and is no bucket
        meshed = [k for k in misses if k[1] is not None]
        assert 1 <= len(meshed) <= 3, misses
        assert len(misses) - len(meshed) <= 1, misses


class TestChainedMeshFilters:
    def test_device_resident_hop_between_sharded_filters(self, monkeypatch):
        """mux → batch → filter → unbatch → batch → filter → unbatch →
        demux with BOTH filters mesh-sharded: the device-resident hop
        between them produces arrays committed with a different sharding
        (the replicated re-stack), which invoke() must re-place instead
        of tripping pjit's committed-sharding check."""
        from nnstreamer_tpu.elements.batch import TensorBatch, TensorUnbatch
        from nnstreamer_tpu.elements.demux import TensorDemux
        from nnstreamer_tpu.elements.mux import TensorMux

        _mesh_on(monkeypatch, "dp:8")
        n = 8
        m1 = JaxModel(apply=lambda p, x: x + 1.0, input_spec=None)
        m2 = JaxModel(apply=lambda p, x: x * 2.0, input_spec=None)
        got = []
        p = Pipeline()
        mux = p.add(TensorMux(sync_mode="nosync"))
        for i in range(n):
            src = p.add(DataSrc(
                name=f"s{i}",
                data=[np.full((4,), i, np.float32) for _ in range(4)]))
            p.link(src, f"{mux.name}.sink_{i}")
        b1 = p.add(TensorBatch())
        f1 = p.add(TensorFilter(framework="jax", model=m1, name="f1"))
        u1 = p.add(TensorUnbatch())
        b2 = p.add(TensorBatch())
        f2 = p.add(TensorFilter(framework="jax", model=m2, name="f2"))
        u2 = p.add(TensorUnbatch())
        demux = p.add(TensorDemux())
        p.link_chain(mux, b1, f1, u1, b2, f2, u2, demux)
        for i in range(n):
            p.link(f"{demux.name}.src_{i}",
                   p.add(TensorSink(name=f"o{i}", callback=got.append)))
        p.run(timeout=120)
        vals = sorted({float(f.tensors[0][0]) for f in got})
        assert vals == [(i + 1.0) * 2.0 for i in range(n)], vals


class TestQueryMeshSizing:
    """Serving-side dispatch sizing: with a mesh, max_batch is per shard
    (chunks of max_batch × ndev) and buckets stay mesh-divisible."""

    @staticmethod
    def _poly_model():
        return JaxModel(
            apply=lambda p, x: x * 2.0,
            input_spec=TensorsSpec.of(
                TensorSpec(dtype=np.float32, shape=(None, 4))))

    def test_group_spans_all_chips_in_one_dispatch(self, monkeypatch):
        from nnstreamer_tpu.elements.query import QueryServer

        _mesh_on(monkeypatch, "dp:8")
        with QueryServer(framework="jax", model=self._poly_model(),
                         batch=2, batch_window_ms=1.0, max_batch=4) as srv:
            assert srv.stats()["mesh_devices"] == 8
            # 20 rows: single-device would split at 4; the mesh chunk is
            # 4 × 8 = 32 so the whole group dispatches ONCE, padded to
            # the per-shard bucket (8 × bucket(ceil(20/8)) = 32 rows)
            xs = [np.arange(r * 4, dtype=np.float32).reshape(r, 4)
                  for r in (12, 8)]
            group = [srv._Pending(TensorsSpec.from_arrays((x,)), (x,))
                     for x in xs]
            invokes0 = srv.batched_invokes
            srv._dispatch_group(group)
            for g, x in zip(group, xs):
                assert g.error is None, g.error
                np.testing.assert_allclose(g.outs[0], 2.0 * x, rtol=1e-6)
            assert srv.batched_invokes - invokes0 == 1
            assert srv.batched_splits == 0

    def test_oversized_group_still_splits(self, monkeypatch):
        from nnstreamer_tpu.elements.query import QueryServer

        _mesh_on(monkeypatch, "dp:2")
        with QueryServer(framework="jax", model=self._poly_model(),
                         batch=2, batch_window_ms=1.0, max_batch=2) as srv:
            x = np.arange(9 * 4, dtype=np.float32).reshape(9, 4)
            group = [srv._Pending(TensorsSpec.from_arrays((x,)), (x,))]
            srv._dispatch_group(group)
            assert group[0].error is None
            np.testing.assert_allclose(group[0].outs[0], 2.0 * x,
                                       rtol=1e-6)
            # chunk cap 2 × 2 = 4: 9 rows → 3 sub-dispatches
            assert srv.batched_invokes == 3
            assert srv.batched_splits == 1
