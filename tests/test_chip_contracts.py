"""Contracts that keep a chip run honest, checked here on the CPU.

- one function places jax's persistent compile cache: the environment wins
  and nothing is set in code; otherwise a fixed path inside the checkout,
  the same in every process;
- ``chip_smoke.py`` cannot pass without a TPU, nor outside the repo, and
  its CPU rehearsal cannot print a pass;
- the native queue library is rebuilt when its source's content hash
  changes, and a broken build is an error where it is asked for by name;
- a spawned fleet worker's stderr reaches the parent's.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from conftest import cpu_subprocess_env

REPO = str(pathlib.Path(__file__).resolve().parents[1])

# each entry point, reduced to the call that places the cache
ENTRY_POINTS = {
    "jax backend": (
        "from nnstreamer_tpu.backends.jax_backend import JaxBackend, JaxModel\n"
        "JaxBackend().open(JaxModel(apply=lambda p, x: x))\n"),
    "continuous batcher": (
        "from nnstreamer_tpu.serving import ContinuousBatcher\n"
        "ContinuousBatcher(capacity=1, t_max=8, d_in=4, n_out=2, d_model=8,"
        " n_heads=2, n_layers=1).stop()\n"),
    "python -m nnstreamer_tpu": (
        "from nnstreamer_tpu.__main__ import main\n"
        "main(['videotestsrc num-buffers=1 width=8 height=8 ! "
        "tensor_converter ! tensor_filter framework=jax "
        "model=" + REPO + "/tests/fixtures/identity_model.py ! "
        "tensor_sink'])\n"),
}
REPORT = "import jax\nprint('CACHE_DIR', jax.config.jax_compilation_cache_dir)\n"


def _cache_dir_after(code, env):
    proc = subprocess.run([sys.executable, "-c", code + REPORT], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("CACHE_DIR ")]
    return lines[-1].split(" ", 1)[1]


class TestCompileCachePlacement:
    @pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
    def test_environment_wins_and_nothing_is_set(self, entry, tmp_path):
        env = cpu_subprocess_env()
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
        got = _cache_dir_after(ENTRY_POINTS[entry], env)
        assert got == str(tmp_path / "placed")

    def test_unset_gives_the_same_checkout_path_in_two_processes(self):
        env = cpu_subprocess_env()
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        # a different cwd and a different entry point: still one place
        a = _cache_dir_after(ENTRY_POINTS["jax backend"], env)
        b = _cache_dir_after(ENTRY_POINTS["python -m nnstreamer_tpu"], env)
        assert a == b == os.path.join(REPO, ".jax_cache")

    def test_repo_stores_do_not_move_the_xla_cache(self, tmp_path):
        """[compile] cache_dir keeps the repo's exec/ and autotune/ stores;
        jax's own cache is no longer wired underneath it."""
        env = cpu_subprocess_env()
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env["NNSTPU_COMPILE_CACHE_DIR"] = str(tmp_path / "stores")
        got = _cache_dir_after(ENTRY_POINTS["jax backend"], env)
        assert got == os.path.join(REPO, ".jax_cache")

    def test_one_update_site_in_the_tree(self):
        hits = []
        for root in ("nnstreamer_tpu", "tools", "examples"):
            for path in pathlib.Path(REPO, root).rglob("*.py"):
                if "jax_compilation_cache_dir" in path.read_text():
                    hits.append(str(path.relative_to(REPO)))
        for name in ("chip_smoke.py", "__graft_entry__.py"):
            if "jax_compilation_cache_dir" in pathlib.Path(
                    REPO, name).read_text():
                hits.append(name)
        assert hits == ["nnstreamer_tpu/backends/exec_cache.py"]

    def test_measurement_entry_points_name_no_cache_directory(self):
        """No [compile] cache_dir pointed at a temporary name: they take
        what the one function gives them."""
        for name in ("chip_smoke.py", "__graft_entry__.py",
                     "benchmark/run.py"):
            assert "COMPILE_CACHE" not in pathlib.Path(
                REPO, name).read_text(), name


class TestChipSmokeCannotPassOffChip:
    def test_exits_2_before_building_anything_without_a_tpu(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            env=cpu_subprocess_env(), capture_output=True, text=True,
            timeout=120)
        assert proc.returncode == 2
        assert proc.stdout.strip() == ""  # no header, no phase, no result
        assert "no TPU" in proc.stderr

    def test_fails_alone_in_a_directory(self, tmp_path):
        """A copy of the script without the program beside it."""
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env = cpu_subprocess_env()
        env["PYTHONPATH"] = ""
        for argv in ([], ["--cpu-rehearsal"]):
            proc = subprocess.run(
                [sys.executable, "chip_smoke.py"] + argv, env=env,
                cwd=tmp_path, capture_output=True, text=True, timeout=120)
            assert proc.returncode not in (0, 3), argv
            assert '"ok"' not in proc.stdout

    def test_cpu_rehearsal_runs_every_phase_but_cannot_pass(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"),
             "--cpu-rehearsal"],
            env=cpu_subprocess_env(), capture_output=True, text=True,
            timeout=600)
        assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
        lines = proc.stdout.strip().splitlines()
        assert not lines[-1].startswith("{")  # no JSON result line
        assert '"ok": true, "device"' not in lines[-1]
        summary = json.loads(next(
            ln for ln in lines if ln.startswith("SUMMARY "))[8:])
        assert summary["rehearsal"] is True and summary["failed"] == []
        assert summary["device"]["platform"] == "cpu"
        phases = {p["phase"]: p for p in summary["phases"]}
        assert list(phases) == [
            "labeling", "serving_door", "multi_stream", "decode_session",
            "kernels", "attention_paths", "four_chips",
            "attention_on_four_chips"]  # conftest exports 8 virtual devices
        assert all(p["ok"] for p in summary["phases"])
        assert phases["kernels"]["compiled_by"] == "interpreter"
        assert phases["attention_paths"]["chip_path"] == "plain"
        assert phases["four_chips"]["shards"] == 4
        assert phases["attention_on_four_chips"]["pipelined_path"] == "plain"


class TestNativeLoaderBuildsFromSource:
    @pytest.fixture()
    def sandbox(self, tmp_path, monkeypatch):
        """The loader pointed at a private copy of the source."""
        from nnstreamer_tpu import native

        src = tmp_path / "frame_queue.cpp"
        shutil.copy(native._SRC, src)
        build = tmp_path / "_build"
        so = build / "libnns_runtime.so"
        monkeypatch.setattr(native, "_SRC", str(src))
        monkeypatch.setattr(native, "_BUILD_DIR", str(build))
        monkeypatch.setattr(native, "_SO", str(so))
        monkeypatch.setattr(native, "_STAMP", str(so) + ".stamp")
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load_error", None)
        return native, src, so

    def test_rebuilds_when_the_source_hash_changes(self, sandbox,
                                                   monkeypatch):
        native, src, so = sandbox
        builds = []
        real_build = native._build
        monkeypatch.setattr(native, "_build",
                            lambda key: (builds.append(key), real_build(key)))
        assert native.load() is not None
        assert len(builds) == 1 and so.exists()
        # same source, fresh process state: the stamp vouches, no rebuild
        monkeypatch.setattr(native, "_lib", None)
        assert native.load() is not None
        assert len(builds) == 1
        # source changes but the .so stays NEWER than it: mtime would keep
        # the stale library, the content hash does not
        src.write_text(src.read_text() + "\n// changed\n")
        os.utime(src, (1, 1))
        monkeypatch.setattr(native, "_lib", None)
        assert native.load() is not None
        assert len(builds) == 2 and builds[0] != builds[1]

    def test_a_foreign_library_without_a_stamp_is_rebuilt(self, sandbox):
        native, _, so = sandbox
        so.parent.mkdir()
        so.write_bytes(b"not a library")  # e.g. copied from another tree
        assert native.load() is not None  # rebuilt, not dlopen'd
        assert so.read_bytes() != b"not a library"

    def test_broken_build_is_an_error_where_native_is_asked_for(
            self, sandbox, monkeypatch):
        native, src, _ = sandbox
        src.write_text("this is not C++")
        assert native.load() is None  # elements still get their Python twin
        assert native.available() is False
        with pytest.raises(RuntimeError, match="native_runtime is on"):
            native.queue_backend()  # chip_smoke.py stops here
        monkeypatch.setenv("NNSTPU_COMMON_NATIVE_RUNTIME", "off")
        assert native.queue_backend() == "python"  # asked for: fine


def test_spawned_worker_stderr_reaches_the_parent(capfd):
    """A worker that cannot start says why (it used to go to DEVNULL)."""
    from nnstreamer_tpu.fleet.supervisor import (
        SpawnError,
        SubprocWorkerFactory,
    )

    factory = SubprocWorkerFactory(
        worker_args=["--no-such-flag"], env=cpu_subprocess_env(),
        line_timeout_s=60.0)
    with pytest.raises(SpawnError):
        factory.spawn("w0")
    assert "--no-such-flag" in capfd.readouterr().err
