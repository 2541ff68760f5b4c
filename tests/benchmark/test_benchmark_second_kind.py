"""A second kind of model under a second kind of traffic, added to a copy of
the benchmark by new files and new ``BENCHMARK.json`` entries alone.

``fixtures/second_kind`` holds, as data for these tests only, what a later PR
would bring: a configuration of a model kind that is no ViT (int32 token
frames, a pytree of its own, a ``mix`` mark), its plain reference, a traffic
kind that is no camera (on ``closed_loop``), a mix, a cell and a per-layer
metric with its reader.  The copy takes them without an edit to any file
that was there, passes the manifest tests and the kind-neutral weight test,
and rehearses ``correct``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SECOND = os.path.join(HERE, "fixtures", "second_kind")
CELL = "toy_tokens.tok8"
SEVEN = ["host_gap_ms_mean", "gap_return_ms_mean", "gap_collect_ms_mean",
         "gap_invoke_ms_mean", "gap_unnamed_pct", "ticket_wait_ms_p50",
         "compile_s"]


def files_under(top):
    return sorted(os.path.relpath(os.path.join(d, f), top)
                  for d, _, fs in os.walk(top) for f in fs
                  if "__pycache__" not in d)


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of ``BENCHMARK.json`` and its ``paths`` with the second kind's
    files laid over it and its entries appended."""
    top = tmp_path_factory.mktemp("grown")
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        man = json.load(f)
    for path in man["paths"]:
        shutil.copytree(os.path.join(ROOT, path), top / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    added = files_under(os.path.join(SECOND, "benchmark"))
    for rel in added:
        target = top / "benchmark" / rel
        assert not target.exists(), f"{rel} would edit a file that is there"
        shutil.copy(os.path.join(SECOND, "benchmark", rel), target)
    with open(os.path.join(SECOND, "entries.json"), encoding="utf-8") as f:
        entries = json.load(f)
    before = json.loads(json.dumps(man))
    for group, new in entries.items():
        man[group] = man[group] + new
    with open(top / "BENCHMARK.json", "w", encoding="utf-8") as f:
        json.dump(man, f, indent=2)
    return top, before, man, added


def in_copy(top, *argv, timeout=240):
    """``argv`` under python in the copy: its own ``benchmark`` package, the
    repo's program."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, *argv], cwd=top, env=env,
                          capture_output=True, text=True, timeout=timeout)


def rehearsed(top, *extra):
    out = in_copy(top, "benchmark/run.py", "--workload", CELL, "--seed",
                  str(2**31 + 33), "--seconds", "0.5", "--rehearsal", *extra)
    assert out.returncode == 3, out.stderr[-3000:]
    assert out.stdout.strip() == ""
    last = out.stderr.strip().splitlines()[-1]
    head = "rehearsal (control flow only, not a result): "
    assert last.startswith(head), last[:200]
    return json.loads(last[len(head):])


def test_nothing_that_was_there_is_edited(grown):
    top, before, man, added = grown
    assert len(added) >= 7
    for path in before["paths"]:
        for rel in files_under(os.path.join(ROOT, path)):
            with open(os.path.join(ROOT, path, rel), "rb") as a, \
                    open(top / path / rel, "rb") as b:
                assert a.read() == b.read(), rel
    for key, value in before.items():
        if isinstance(value, list) and value and isinstance(value[0], dict):
            assert man[key][:len(value)] == value, key
        else:
            assert man[key] == value, key
    assert [w["name"] for w in man["workloads"]][-1] == CELL


def test_the_manifest_and_weight_tests_pass_in_the_copy(grown):
    out = in_copy(grown[0], "-m", "pytest", "-v", "-p", "no:cacheprovider",
                  "tests/benchmark/test_benchmark_manifest.py",
                  "tests/benchmark/test_benchmark_rehearsal.py",
                  "-k", "manifest or follow_their_seed")
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    # the new entries were tested: one case a configuration, a cell and a
    # per-layer metric, and a weight case a configuration
    for case in ("test_configuration_entry[toy_tokens]",
                 f"test_cell_entry[{CELL}]",
                 "test_per_layer_metric_entry[mix_roofline]",
                 "follow_their_seed[toy_tokens]",
                 "follow_their_seed[siglip2_gopt16_384]"):
        assert any(case in ln and "PASSED" in ln
                   for ln in out.stdout.splitlines()), case


def test_the_new_cell_rehearses_correct(grown):
    line = rehearsed(grown[0])
    assert line["correct"] is True and line["failed"] == 0
    values = line["values"]
    # frames in equal frames out
    assert values["attempted"] > 0
    assert values["attempted"] == values["arrived"] == values["latency_samples"]
    _, _, man, _ = grown
    assert set(line["metrics"]) == {m["name"] for m in man["end_to_end"]}
    assert all(values[name] > 0 for name in line["metrics"])
    err = line["compared"]["logit_err"]
    assert err["value"] <= err["limit"]
    assert line["fail_notes"] == {"missing": 0, "order": 0, "label": 0,
                                  "score": 0}


def test_the_span_metrics_report_in_the_new_cell(grown):
    """The seven program-span metrics list no cells: a traced run of a cell
    of another kind reports them, read by element class."""
    line = rehearsed(grown[0], "--trace", "1")
    assert line["correct"] is True
    assert set(SEVEN) <= set(line["metrics"])
    assert line["metrics"]["host_gap_ms_mean"]["value"] > 0
    # no device trace on the CPU: a roofline reads nothing, never 0
    assert "mix_roofline" not in line["metrics"]
    assert "attention_roofline" not in line["metrics"]


def test_the_new_cell_is_asked_for_no_kernel_its_kind_lacks(grown):
    """On the chip a traced run that lacks a metric its cell is asked for is
    refused.  The new cell is asked for its own mark's roofline and for the
    metrics that list no cells, and none of those is a kernel's: the ViT's
    ``attention_roofline`` lists the ViT's cell."""
    _, _, man, _ = grown
    asked = [m["name"] for m in man["per_layer"]
             if "workloads" not in m or CELL in m["workloads"]]
    assert "mix_roofline" in asked and set(SEVEN) <= set(asked)
    assert [n for n in asked if n.endswith("_roofline")] == ["mix_roofline"]
    out = in_copy(grown[0], "-m", "pytest", "-v", "-p", "no:cacheprovider",
                  "tests/benchmark/test_benchmark_manifest.py", "-k", "kernels_roofline")
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    for case in ("[attention_roofline]", "[mix_roofline]"):
        assert any(case in ln and "PASSED" in ln
                   for ln in out.stdout.splitlines()), case


def test_the_new_kinds_control_is_refused(grown):
    line = rehearsed(grown[0], "--control")
    assert line["correct"] is False and line["failed"] == 0
    err = line["compared"]["logit_err"]
    assert err["value"] > 10 * err["limit"]
