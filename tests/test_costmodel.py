"""The cost observatory (obs/costmodel.py + tools/perfdiff.py): per-stage
leg aggregation off the hook bus, COST_MODEL.json persistence (idempotent
merge, concurrent writers, bounded run history), the ``cost_model`` stats
provider + ``nnstpu_stage_cost_us`` gauges, and perfdiff's typed
regression verdicts (self-compare pins ``flat``)."""

import json
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.backends.jax_backend import JaxModel
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.queue import Queue
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.obs import costmodel
from nnstreamer_tpu.obs.costmodel import (
    CostModelTracer,
    LegStat,
    combine_legs,
    leg_std_us,
    load_cost_model,
    merge_cost_model,
)
from nnstreamer_tpu.obs.device import DeviceTracer
from nnstreamer_tpu.obs.export import stats_snapshot, unregister_stats
from nnstreamer_tpu.obs.metrics import MetricsRegistry
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec
from tools import perfdiff


def _wait_for(cond, timeout=10.0, step=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


@pytest.fixture(autouse=True)
def _isolated_costmodel(tmp_path, monkeypatch):
    """Every test writes its own COST_MODEL.json and leaves the
    process-global live-tracer registry clean."""
    monkeypatch.setenv("NNSTPU_OBS_COSTMODEL_PATH",
                       str(tmp_path / "COST_MODEL.json"))
    yield
    with costmodel._live_lock:
        costmodel._live.clear()
    unregister_stats("cost_model")
    costmodel._provider_registered = False


def _jax_model(shape=(4,)):
    return JaxModel(
        apply=lambda params, x: x * 2,
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.float32, shape=shape)))


def _run_cost_pipeline(name="costp", frames=6, registry=None):
    reg = registry or MetricsRegistry()
    got = []
    p = Pipeline(name=name)
    src = p.add(DataSrc(data=[np.full(4, i, np.float32)
                              for i in range(frames)], name="s"))
    filt = p.add(TensorFilter(framework="jax", model=_jax_model(), name="f"))
    q = p.add(Queue(max_size_buffers=4, name="q"))
    p.link_chain(src, filt, q, p.add(TensorSink(callback=got.append,
                                                name="out")))
    dev = p.attach_tracer(DeviceTracer(registry=reg))
    cm = p.attach_tracer(CostModelTracer(registry=reg))
    p.run(timeout=60)
    assert _wait_for(lambda: dev.summary()["completed"] >= frames)
    assert _wait_for(lambda: len(got) == frames)
    p.stop()
    return cm, reg, p


# -- the Welford/EWMA leg aggregate -------------------------------------------

class TestLegStat:
    def test_mean_std_and_ewma(self):
        s = LegStat()
        vals = [100.0, 120.0, 80.0, 110.0, 90.0]
        for v in vals:
            s.add(v, alpha=0.5)
        snap = s.snapshot()
        assert snap["count"] == 5
        assert snap["mean_us"] == pytest.approx(np.mean(vals), rel=1e-6)
        assert leg_std_us(snap) == pytest.approx(np.std(vals, ddof=1),
                                                 rel=1e-6)
        # the EWMA seeds at the first sample, then smooths
        assert snap["ewma_us"] != snap["mean_us"]

    def test_std_undefined_below_two_samples(self):
        s = LegStat()
        assert leg_std_us(s.snapshot()) is None
        s.add(5.0, alpha=0.2)
        assert leg_std_us(s.snapshot()) is None

    def test_combine_is_exact_pooling(self):
        rng = np.random.default_rng(7)
        a_vals = rng.normal(100, 10, 40)
        b_vals = rng.normal(140, 25, 25)
        a, b = LegStat(), LegStat()
        for v in a_vals:
            a.add(float(v), 0.2)
        for v in b_vals:
            b.add(float(v), 0.2)
        pooled = combine_legs(a.snapshot(), b.snapshot())
        allv = np.concatenate([a_vals, b_vals])
        assert pooled["count"] == 65
        assert pooled["mean_us"] == pytest.approx(np.mean(allv), rel=1e-4)
        assert leg_std_us(pooled) == pytest.approx(np.std(allv, ddof=1),
                                                   rel=1e-3)
        # pooling with an empty side is the identity
        assert combine_legs({}, a.snapshot())["count"] == 40
        assert combine_legs(a.snapshot(), {})["mean_us"] == \
            a.snapshot()["mean_us"]


# -- end-to-end aggregation off the hook bus ----------------------------------

class TestCostModelTracer:
    def test_pipeline_legs_gauges_and_provider(self):
        cm, reg, _ = _run_cost_pipeline(name="cmsmoke")
        stages = cm.summary()["stages"]
        # the jax filter has dispatch + TRUE device legs, both sampled
        f = stages["f"]
        assert f["legs"]["dispatch"]["count"] == 6
        assert f["legs"]["device_exec"]["count"] >= 6
        assert f["legs"]["dispatch"]["mean_us"] > 0
        assert f["bucket"] == 4 and f["mesh"] == 1
        assert f["compute_us"] is not None
        # queue residency lands on the QUEUE node, from the push/pop
        # FIFO — one sample per pop: 6 frames + the EOS event (a pop
        # that overtakes its push hook still counts, as ~0 residency)
        assert stages["q"]["legs"]["queue_wait"]["count"] == 7
        assert stages["q"]["legs"]["queue_wait"]["mean_us"] > 0
        # events (EOS) are not frames
        assert f["frames"] == 6
        # gauges carry (pipeline, node, leg) children
        reg.collect()
        gauge = reg.get("nnstpu_stage_cost_us")
        labels = {k for k, _ in gauge.children()}
        assert ("cmsmoke", "f", "dispatch") in labels
        assert ("cmsmoke", "f", "device_exec") in labels
        assert ("cmsmoke", "q", "queue_wait") in labels
        # the merged stats provider view
        snap = stats_snapshot()
        assert "cmsmoke" in snap["cost_model"]

    def test_stage_snapshots_reconcile_with_device_tracer(self):
        """Acceptance cross-check: the cost model's device_exec totals
        must agree with the device lane's own accounting (both feed off
        the same reaper observations)."""
        cm, reg, p = _run_cost_pipeline(name="cmrecon", frames=8)
        dev_summary = [t for t in p._tracers
                       if isinstance(t, DeviceTracer)][0].summary()
        stages = cm.stage_snapshots()
        key = [k for k in stages if "|f|" in k][0]
        leg = stages[key]["legs"]["device_exec"]
        cm_total_us = leg["mean_us"] * leg["count"]
        dev_total_us = dev_summary["device_ns"] / 1e3
        assert cm_total_us == pytest.approx(dev_total_us, rel=0.05)

    def test_autosave_flush_on_stop(self):
        _run_cost_pipeline(name="cmsave")
        doc = load_cost_model()
        keys = [k for k in doc["stages"] if k.startswith("cmsave|")]
        assert any("|f|" in k for k in keys)


# -- persistence --------------------------------------------------------------

class TestPersistence:
    def test_flush_idempotent(self):
        cm, _, _ = _run_cost_pipeline(name="cmidem")
        d1 = cm.flush()
        d2 = cm.flush()
        assert d1["stages"].keys() == d2["stages"].keys()
        for k in d1["stages"]:
            assert d1["stages"][k]["legs"] == d2["stages"][k]["legs"]

    def test_merge_pools_across_runs_and_bounds_history(self, tmp_path):
        path = str(tmp_path / "cm.json")
        legs = {"dispatch": {"count": 10, "mean_us": 100.0, "m2": 90.0,
                             "ewma_us": 100.0}}
        snap = {"pipeline": "p", "node": "f", "bucket": 4, "mesh": 1,
                "legs": legs}
        key = costmodel.stage_key("p", "f", 4, 1)
        for i in range(costmodel.MAX_RUNS + 3):
            merge_cost_model({key: snap}, f"run{i}", path)
        doc = load_cost_model(path)
        entry = doc["stages"][key]
        assert len(entry["runs"]) == costmodel.MAX_RUNS
        pooled = entry["legs"]["dispatch"]
        assert pooled["count"] == 10 * costmodel.MAX_RUNS
        assert pooled["mean_us"] == pytest.approx(100.0)
        # re-merging an EXISTING run replaces, never double-counts
        merge_cost_model({key: snap}, f"run{costmodel.MAX_RUNS + 2}", path)
        doc2 = load_cost_model(path)
        assert doc2["stages"][key]["legs"]["dispatch"]["count"] == \
            10 * costmodel.MAX_RUNS

    def test_concurrent_writers_one_file(self, tmp_path):
        """Two pipelines' tracers flushing to ONE COST_MODEL.json from
        threads: every writer's stages land, the file stays valid JSON,
        and repeated flushes stay idempotent."""
        path = str(tmp_path / "cm.json")

        def writer(pipeline, node, mean):
            legs = {"dispatch": {"count": 5, "mean_us": mean, "m2": 10.0,
                                 "ewma_us": mean}}
            key = costmodel.stage_key(pipeline, node, 4, 1)
            for _ in range(20):
                merge_cost_model(
                    {key: {"pipeline": pipeline, "node": node, "bucket": 4,
                           "mesh": 1, "legs": legs}},
                    f"run-{pipeline}", path)

        threads = [
            threading.Thread(target=writer, args=("pipeA", "f", 100.0)),
            threading.Thread(target=writer, args=("pipeB", "g", 250.0)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        with open(path) as f:
            doc = json.load(f)  # valid JSON, no torn write
        a = doc["stages"][costmodel.stage_key("pipeA", "f", 4, 1)]
        b = doc["stages"][costmodel.stage_key("pipeB", "g", 4, 1)]
        # 20 flushes of the same run replace, never accumulate
        assert a["legs"]["dispatch"] == {"count": 5, "mean_us": 100.0,
                                         "m2": 10.0}
        assert b["legs"]["dispatch"]["mean_us"] == 250.0

    def test_load_tolerates_missing_and_foreign(self, tmp_path):
        assert load_cost_model(str(tmp_path / "absent.json")) == {
            "schema": costmodel.SCHEMA_VERSION, "stages": {}}
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert load_cost_model(str(bad))["stages"] == {}
        foreign = tmp_path / "foreign.json"
        foreign.write_text(json.dumps({"schema": 999, "stages": {"x": 1}}))
        assert load_cost_model(str(foreign))["stages"] == {}


# -- perfdiff: typed verdicts -------------------------------------------------

def _doc_with(mean, count=20, m2=2000.0):
    legs = {"dispatch": {"count": count, "mean_us": mean, "m2": m2}}
    return {"schema": 1, "stages": {
        "p|f|b4|mesh1": {"pipeline": "p", "node": "f", "legs": legs}}}


class TestPerfdiff:
    def test_self_compare_is_flat(self):
        doc = _doc_with(1000.0)
        verdicts = perfdiff.diff_cost_models(doc, doc)
        assert [v["verdict"] for v in verdicts] == ["flat"]
        assert perfdiff.overall_verdict(verdicts) == "flat"

    def test_regressed_names_the_leg(self):
        base, cur = _doc_with(1000.0), _doc_with(2000.0)
        (v,) = perfdiff.diff_cost_models(base, cur)
        assert v["verdict"] == "regressed" and v["leg"] == "dispatch"
        reg = MetricsRegistry()
        rep = perfdiff.report([v], registry=reg)
        assert rep["verdict"] == "regressed"
        assert rep["regressed_legs"] == {"dispatch": 1}
        counter = reg.get("nnstpu_perf_regression_total")
        assert dict(counter.children())[("dispatch",)].value == 1

    def test_improved_and_noise_band(self):
        (v,) = perfdiff.diff_cost_models(_doc_with(1000.0),
                                         _doc_with(500.0))
        assert v["verdict"] == "improved"
        # a delta inside 3 sigma of a NOISY baseline stays flat:
        # std = sqrt(m2/(n-1)), here ~229 us -> band ~688 us
        noisy = _doc_with(1000.0, count=20, m2=1_000_000.0)
        (v,) = perfdiff.diff_cost_models(noisy, _doc_with(1500.0))
        assert v["verdict"] == "flat"

    def test_cli_self_compare_exits_zero_flat(self, tmp_path, capsys):
        path = tmp_path / "cm.json"
        path.write_text(json.dumps(_doc_with(1000.0)))
        rc = perfdiff.main(["--baseline", str(path), "--current",
                            str(path), "--json"])
        assert rc == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["verdict"] == "flat" and rep["compared"] == 1

    def test_cli_strict_exits_nonzero_on_regression(self, tmp_path):
        b, c = tmp_path / "b.json", tmp_path / "c.json"
        b.write_text(json.dumps(_doc_with(1000.0)))
        c.write_text(json.dumps(_doc_with(4000.0)))
        assert perfdiff.main(["--baseline", str(b), "--current",
                              str(c)]) == 0  # non-fatal by default
        assert perfdiff.main(["--baseline", str(b), "--current", str(c),
                              "--strict"]) == 1
