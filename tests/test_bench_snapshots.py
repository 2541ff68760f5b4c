"""bench.py's run contract.

- no TPU → exit 2 before any leg, nothing printed on stdout (no probe
  child, no CPU fallback: a CPU timing is never written under a device
  metric's name);
- a snapshot after every leg (stdout + atomic BENCH_PARTIAL.json), each
  naming the device it ran on;
- a leg that raises does not stop the remaining legs, but the exit code is
  1 and ``failed_legs`` names it;
- SIGTERM → final JSON from the last snapshot + exit 143; the hard watchdog
  ends a stuck run with valid JSON + exit 124 — never a zero exit code for
  a run that did not finish.

The in-process tests stand a fake device in for ``require_tpu`` (the legs
are skipped on 0 frames, so nothing is measured on the CPU).
"""

import importlib
import json
import os
import pathlib
import signal
import subprocess
import sys
import textwrap
import time

import pytest

REPO = str(pathlib.Path(__file__).resolve().parents[1])


FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
# legs whose work is a frame count: 0 makes each a typed skip
ZERO_FRAME_VARS = (
    "BENCH_FRAMES", "BENCH_UPLOAD_FRAMES", "BENCH_DYNBATCH_FRAMES",
    "BENCH_QUANT_FRAMES", "BENCH_SSD_FRAMES", "BENCH_POSE_FRAMES",
    "BENCH_CASCADE_FRAMES", "BENCH_LSTM_STEPS", "BENCH_KV_STEPS",
    "BENCH_SEQ_WINDOWS", "BENCH_MUX_FRAMES", "BENCH_BREAKDOWN_FRAMES",
    "BENCH_SEGMENT_FRAMES", "BENCH_PARTITION_FRAMES")
# the diagnostics that have no frame count are left out by name
FRAME_LEGS = ("config1 jax leg,config1 upload leg,config1 dynbatch leg,"
              "config1 dynupload leg,config5 mux leg,config1 quant leg,"
              "config2 ssd leg,config2c cascade leg,segment ab leg,"
              "partition ab leg,config3 pose leg,config4 lstm leg,"
              "config4b seq leg,config4c kvdecode leg,config4d contbatch leg,"
              "baselines,breakdown,mfu")


@pytest.fixture()
def bench_mod(monkeypatch, tmp_path):
    monkeypatch.syspath_prepend(REPO)
    monkeypatch.setenv("BENCH_PARTIAL_PATH", str(tmp_path / "partial.json"))
    monkeypatch.setenv("BENCH_NOTES_PATH", str(tmp_path / "notes.md"))
    monkeypatch.setenv("BENCH_SKIP_BASELINES", "1")
    monkeypatch.setenv("BENCH_MFU_BATCHES", "")
    monkeypatch.setenv("BENCH_LEGS", FRAME_LEGS)
    for var in ZERO_FRAME_VARS:
        monkeypatch.setenv(var, "0")
    import bench

    importlib.reload(bench)
    monkeypatch.setattr(bench, "require_tpu", lambda: dict(FAKE_DEVICE))
    return bench


def test_exits_2_without_a_tpu_and_prints_no_result(tmp_path):
    """The real entry point under JAX_PLATFORMS=cpu: the platform is
    checked in-process before any leg; nothing lands on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_PARTIAL_PATH=str(tmp_path / "partial.json"),
               BENCH_NOTES_PATH=str(tmp_path / "notes.md"))
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          capture_output=True, text=True, env=env,
                          timeout=120)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
    assert not (tmp_path / "partial.json").exists()
    assert not (tmp_path / "notes.md").exists()


def test_survival_apparatus_is_gone(bench_mod):
    for name in ("probe_accelerator", "pin_cpu", "save_tpu_cache",
                 "load_tpu_cache", "merge_ladder_bank", "make_wire_gate",
                 "measure_wire_health", "measure_cold_start",
                 "sentinel_ladder_run", "TPU_CACHE_PATH"):
        assert not hasattr(bench_mod, name), name


def test_snapshots_stream_and_final_line(bench_mod, capsys):
    out, rc = bench_mod.main()
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.strip().splitlines() if ln]
    parsed = [json.loads(ln) for ln in lines]
    # a snapshot landed after every leg: many lines, all valid JSON
    assert len(parsed) > 5
    assert all(p.get("partial") for p in parsed[:-1])
    final = parsed[-1]
    assert final == out
    assert "partial" not in final
    assert final["unit"] == "frames/sec/chip"
    # every line names the device; every partial the leg it followed
    assert all(p["device"] == FAKE_DEVICE for p in parsed)
    assert all("snapshot_after" in p and "budget" in p for p in parsed[:-1])
    assert final["extra"]["queue_backend"] in ("native", "python")
    assert final["extra"]["compile_cache_dir"]


def test_partial_file_is_valid_json_at_end(bench_mod, capsys):
    bench_mod.main()
    capsys.readouterr()
    with open(os.environ["BENCH_PARTIAL_PATH"]) as f:
        snap = json.load(f)
    # finalize rewrites the partial file with the final (non-partial) result
    assert "partial" not in snap
    assert snap["unit"] == "frames/sec/chip"


def test_legs_filter_limits_what_runs(bench_mod, monkeypatch, capsys):
    monkeypatch.setenv("BENCH_LEGS", "config1 jax leg,config5 mux leg")
    bench_mod.main()
    out = capsys.readouterr()
    final = json.loads(out.out.strip().splitlines()[-1])
    errs = final.get("error", "")
    # the two filtered-in legs ran (and skipped on 0 frames); the others
    # never even produced a skip row
    assert "config1 jax leg: skipped (0 frames)" in errs
    assert "config2 ssd leg" not in errs
    assert "config3 pose leg" not in errs


def test_finalize_async_uses_last_snapshot_and_is_idempotent(
        bench_mod, capsys):
    rep = bench_mod.Reporter(budget_s=100.0)
    rep.device = dict(FAKE_DEVICE)
    rep.current_leg = "config1 jax leg"
    rep.results["config1_stream_fps"] = 42.0
    rep.snapshot()
    out = rep.finalize(async_ctx=True)
    assert out is not None
    assert "interrupted during leg 'config1 jax leg'" in out["error"]
    assert "partial" not in out
    # second finalize is a no-op (exactly one final emission)
    assert rep.finalize() is None
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1])["error"] == out["error"]


def test_over_budget_skips_legs_but_still_finalizes(
        bench_mod, monkeypatch, capsys):
    monkeypatch.setenv("BENCH_BUDGET_S", "0")
    _, rc = bench_mod.main()
    final = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert final["unit"] == "frames/sec/chip"
    assert "skipped" in final.get("error", "")
    assert rc == 0 and "failed_legs" not in final  # a skip is not a failure


def test_raising_leg_fails_the_run_after_the_remaining_legs(
        bench_mod, monkeypatch, capsys):
    """config1 raises; the later legs still get their turn (and skip on 0
    frames); the exit code is 1 and the JSON names the failed leg."""
    def boom(*a, **kw):
        raise RuntimeError("device said no")

    monkeypatch.setenv("BENCH_FRAMES", "4")
    monkeypatch.setenv("BENCH_LEGS", "config1 jax leg,config5 mux leg")
    monkeypatch.setattr(bench_mod, "run_pipeline_fps", boom)
    out, rc = bench_mod.main()
    final = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1
    assert final["failed_legs"] == ["config1 jax leg"]
    assert "device said no" in final["error"]
    assert "config5 mux leg: skipped (0 frames)" in final["error"]
    assert final["value"] is None  # nothing stands in for the failed leg


def test_ladder_matrix_is_complete_and_a_raising_cell_is_recorded(
        bench_mod, monkeypatch):
    """12 cells, measured in this run (no bank, no carry-forward); a cell
    that needs more chips than exist is a typed skip; one that raises
    carries its error."""
    def point(batch, dtype, ndev, image_size=224):
        if (batch, dtype, ndev) == (32, "int8", 1):
            raise RuntimeError("vmem")
        return {"step_ms": 1.0, "mfu": 0.02 * batch / 8}

    monkeypatch.setattr(bench_mod, "ladder_point", point)
    monkeypatch.setattr(bench_mod, "LADDER_MESHES", (1, 64))
    res = bench_mod.measure_mfu_ladder()
    cells = res["cells"]
    assert len(cells) == 12
    assert all(c["skipped"]["reason"] == "no_mesh"
               for k, c in cells.items() if k.endswith("/x64"))
    assert "vmem" in cells["b32/int8/x1"]["error"]
    measured = [c for c in cells.values() if "mfu" in c]
    assert len(measured) == 5
    assert res["best_cell"] in ("b128/fp32/x1", "b128/int8/x1")
    assert "bank" not in res and "banked_cells" not in res


_DRIVER = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, {repo!r})
    import bench

    rep = bench.Reporter(budget_s={budget})
    rep.device = {{"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    rep.current_leg = "config1 jax leg"
    rep.results["config1_stream_fps"] = 33.3
    rep.snapshot()
    bench.install_signal_handlers(rep)
    bench.arm_watchdog(rep, {hard})
    print("READY", file=sys.stderr, flush=True)
    time.sleep(60)  # simulates a wedged leg
""")


def _spawn(tmp_path, budget, hard):
    env = dict(os.environ,
               BENCH_PARTIAL_PATH=str(tmp_path / "partial.json"),
               BENCH_NOTES_PATH=str(tmp_path / "notes.md"))
    return subprocess.Popen(
        [sys.executable, "-c", _DRIVER.format(repo=REPO, budget=budget,
                                              hard=hard)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
    )


def _wait_ready(proc, timeout=60.0):
    t0 = time.time()
    line = ""
    while time.time() - t0 < timeout:
        line = proc.stderr.readline()
        if "READY" in line:
            return
    raise AssertionError(f"driver never became ready: {line!r}")


def test_sigterm_yields_final_json_and_rc143(tmp_path):
    """A ``timeout`` kill sends SIGTERM: the last snapshot becomes the
    final JSON line, and the exit code says the run was interrupted."""
    proc = _spawn(tmp_path, budget=100.0, hard=100.0)
    try:
        _wait_ready(proc)
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
    finally:
        proc.kill()
    assert proc.returncode == 128 + signal.SIGTERM
    final = json.loads(out.strip().splitlines()[-1])
    assert final["extra"]["config1_stream_fps"] == 33.3
    assert "interrupted" in final["error"]


def test_watchdog_force_finishes_a_stuck_run(tmp_path):
    """A leg stuck in a C call can't be interrupted by signals between
    bytecodes; the watchdog thread must emit the final snapshot and
    os._exit(124) once the hard limit passes."""
    proc = _spawn(tmp_path, budget=0.5, hard=2.0)
    try:
        out, _ = proc.communicate(timeout=90)
    finally:
        proc.kill()
    assert proc.returncode == 124
    final = json.loads(out.strip().splitlines()[-1])
    assert final["extra"]["config1_stream_fps"] == 33.3
    assert "interrupted" in final["error"]
