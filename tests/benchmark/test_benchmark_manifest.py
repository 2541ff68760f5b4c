"""BENCHMARK.json against the files it names: a cell, a configuration, a
traffic mix or a per-layer metric is added by new files and new entries
alone, so every entry is checked the same way, whatever its name."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

MAN = manifest.load_manifest(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E = {m["name"]: m for m in MAN["end_to_end"]}
CELLS = [w["name"] for w in MAN["workloads"]]


def ids(entries):
    return [e["name"] for e in entries]


def reporting(metric):
    return metric.get("workloads", CELLS)


def test_paths_hold_the_benchmark_and_its_tests():
    assert "benchmark" in MAN["paths"] and "tests/benchmark" in MAN["paths"]
    for word in MAN["command"][1:]:
        if os.sep in word:
            assert any(word.startswith(p + "/") for p in MAN["paths"]), word
            assert os.path.isfile(os.path.join(ROOT, word)), word
    assert 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) < 64 * 1024


@pytest.mark.parametrize("cfg", MAN["configs"], ids=ids(MAN["configs"]))
def test_configuration_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"])
    assert 1 <= len(cfg["source"]) <= 200 and 1 <= len(cfg["why"]) <= 200
    assert any(cfg["file"].startswith(p + "/") for p in MAN["paths"])
    data = manifest.load_config(MAN, cfg["name"], ROOT)
    assert data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    assert data["assumed"], "sizes the source does not give are listed"
    assert any(w["config"] == cfg["name"] for w in MAN["workloads"])
    assert [c["file"] for c in MAN["configs"]].count(cfg["file"]) == 1
    for key in manifest.CONFIG_KEYS:
        assert key in data, key
    assert data["limits"] and data["rehearsal_limits"]
    # its kind and its plain reference are found by name, and offer what
    # run.py, compile_rehearsal.py and the readers call
    kind = manifest.module("model_kinds", data["kind"])
    for fn in manifest.MODEL_KIND:
        assert callable(getattr(kind, fn)), fn
    reference = manifest.module("references", data["reference"])
    for fn in manifest.REFERENCE:
        assert callable(getattr(reference, fn)), fn
    for rehearsal in (False, True):
        sizes = kind.sizes(data, rehearsal)
        assert kind.param_count(sizes) > 0
        assert kind.frame_flops(sizes)["total"] > 0
        assert all(int(n) > 0 for n in kind.frame_shape(sizes))
        # a mark is names and/or dims, and a label with a work function
        # counts FLOPs and bytes
        for label, mark in kind.marks(sizes).items():
            assert NAME.match(label), label
            assert set(mark) <= {"names", "dims"} and any(mark.values()), label
            assert all(isinstance(n, str) and n for n in mark.get("names", ()))
            assert all(len(d) >= 1 and all(int(n) > 0 for n in d)
                       for d in mark.get("dims", ()))
            work = getattr(kind, f"{label}_work", None)
            if work is not None:
                assert set(work(sizes)) >= {"flops", "bytes"}, label


@pytest.mark.parametrize("cell", MAN["workloads"], ids=CELLS)
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in ids(MAN["configs"])
    mix = manifest.load_traffic(cell["traffic"])
    for key in manifest.TRAFFIC_KEYS:
        assert key in mix, key
    traffic = manifest.module("traffic_kinds", mix["kind"])
    for fn in manifest.TRAFFIC_KIND:
        assert callable(getattr(traffic, fn)), fn
    assert mix["rehearsal"], "a mix carries its tiny rehearsal size"
    # what the executable takes in this traffic: the batch, then the
    # kind's frame
    data = manifest.load_config(MAN, cell["config"], ROOT)
    kind = manifest.module("model_kinds", data["kind"])
    sizes = kind.sizes(data)
    shape, dtype, front = traffic.example_input(mix, data, kind, sizes, 3)
    assert tuple(shape) == (3,) + tuple(kind.frame_shape(sizes))
    assert callable(front) and dtype is not None
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert pairs.count((cell["config"], cell["traffic"])) == 1
    # every cell reports setup_s, another end-to-end and a per-layer metric
    e2e = [m["name"] for m in manifest.cell_metrics(MAN, "end_to_end", cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.cell_metrics(MAN, "per_layer", cell["name"])


@pytest.mark.parametrize("metric", MAN["end_to_end"], ids=ids(MAN["end_to_end"]))
def test_end_to_end_metric_entry(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert set(reporting(metric)) <= set(CELLS)


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=ids(MAN["per_layer"]))
def test_per_layer_metric_entry(metric):
    assert set(metric) - {"workloads"} == {
        "name", "unit", "better", "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert 1 <= len(metric["layer"]) <= 200
    data = manifest.load_layer_metric(metric["name"])
    for key in ("layer", "unit", "moves", "source"):
        assert data[key] == metric[key], key
    reader = manifest.module("layer_metrics", data["reader"])
    assert callable(getattr(reader, data["function"]))
    # what it should move is an end-to-end metric that every cell reporting
    # the layer metric reports too
    moved = E2E[metric["moves"]]
    assert set(reporting(metric)) <= set(reporting(moved))
    if "roofline" in metric["name"] or "mfu" in metric["name"].split("_"):
        assert metric["unit"] == "%"


def test_names_are_unique():
    for group in (MAN["configs"], MAN["workloads"],
                  MAN["end_to_end"] + MAN["per_layer"]):
        names = ids(group)
        assert len(names) == len(set(names))
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1


def test_a_roofline_stands_beside_the_whole_steps_mfu():
    per_layer = {m["name"]: m for m in MAN["per_layer"]}
    for m in per_layer.values():
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"].split("_") and o["moves"] == m["moves"]
                       for o in per_layer.values()), m["name"]


ROOFLINES = [m for m in MAN["per_layer"] if m["name"].endswith("_roofline")]


@pytest.mark.parametrize("metric", ROOFLINES, ids=ids(ROOFLINES))
def test_a_kernels_roofline_lists_the_cells_whose_kind_marks_it(metric):
    """A metric with no ``workloads`` is asked of every later cell that
    reports what it moves, of kinds that have no such kernel too.  So
    ``<label>_roofline`` lists its cells, and in each of them the kind marks
    ``label`` and counts its work."""
    label = metric["name"][:-len("_roofline")]
    assert metric.get("workloads"), "a kernel's roofline lists its cells"
    for name in metric["workloads"]:
        cell = manifest.find(MAN["workloads"], name, "cell")
        data = manifest.load_config(MAN, cell["config"], ROOT)
        kind = manifest.module("model_kinds", data["kind"])
        assert label in kind.marks(kind.sizes(data)), (label, name)
        assert set(getattr(kind, f"{label}_work")(kind.sizes(data))) >= {
            "flops", "bytes"}, (label, name)
