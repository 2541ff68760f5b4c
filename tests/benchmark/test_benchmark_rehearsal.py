"""``run.py --rehearsal``: the whole control flow of a run on the CPU at the
configuration's tiny widths and the cell's real stream count.  The harness's
look for a chip is skipped, the rest is driven as in a run: frames in equal
frames out per stream in order, the comparison passes, and it fails with the
timed path broken underneath or with the lower-precision control in the
program's place.  No test here describes a TPU topology."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, run as bench_run  # noqa: E402

MAN = manifest.load_manifest(ROOT)
CELLS = [w["name"] for w in MAN["workloads"]]
RUN_PY = os.path.join(ROOT, "benchmark", "run.py")


def cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def rehearse(cell, seed, *extra, break_output=None):
    args = bench_run.parse_args(["--workload", cell, "--seed", str(seed),
                                 "--seconds", "0.5", "--rehearsal", *extra])
    return bench_run.run_cell(args, break_output=break_output)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_frames_in_equal_frames_out_and_the_comparison_passes(cell):
    code, report = rehearse(cell, seed=2**31 + 11)
    res, line = report.result, report.line
    assert code == 3
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and res.drained
    streams = len(res.push_ns)
    assert streams == manifest.load_traffic(
        manifest.find(MAN["workloads"], cell, "cell")["traffic"])["streams"]
    for s in range(streams):
        # every pushed frame came back at its own stream's sink, in order
        assert len(res.sink_ns[s]) == len(res.push_ns[s]) == len(res.labels[s])
        assert [pts for _, _, pts in res.labels[s]] == [
            k * res.pts_step for k in range(len(res.labels[s]))]
        assert all(b >= a for a, b in zip(res.push_ns[s], res.sink_ns[s]))
    assert len(res.logits) == len(res.push_ns[0])
    for name in ("frames_per_s", "frame_latency_p50_ms", "frame_latency_p95_ms",
                 "setup_s"):
        assert report.values[name] > 0
    assert list(line)[-1] == "compared"
    assert line["compared"]["logit_err"]["value"] <= line["compared"]["logit_err"]["limit"]


def _rolled(apply):
    import jax.numpy as jnp

    return lambda p, x: jnp.roll(apply(p, x), 1, axis=0)


def _one_answer_altered(apply):
    return lambda p, x: apply(p, x).at[3, 0].add(0.5)


def _output_in_fp8(apply):
    import jax.numpy as jnp

    return lambda p, x: apply(p, x).astype(jnp.float8_e4m3fn).astype(jnp.float32)


@pytest.mark.parametrize("fault", [_rolled, _one_answer_altered, _output_in_fp8],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_timed_path_comes_out_not_correct(fault):
    """Rows routed to the wrong streams, one answer altered where it is
    produced, the output computed in a lower precision: each leaves every
    frame flowing and every label the argmax of its own row, and each is
    refused by the comparison with the reference."""
    code, report = rehearse(CELLS[0], seed=7, break_output=fault)
    assert code == 3
    assert report.line["failed"] == 0 and report.line["attempted"] > 0
    assert report.line["correct"] is False
    err = report.line["compared"]["logit_err"]
    assert err["value"] > err["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_lower_precision_control_comes_out_not_correct(seed):
    """The control of ``correct``: the program's own W8A8 path in the
    program's place, at the rehearsal size (on the chip at the cell's size:
    ``PERF.md``), against the same seed's sound run."""
    _, sound = rehearse(CELLS[0], seed=seed)
    _, control = rehearse(CELLS[0], seed, "--control")
    assert sound.line["correct"] is True
    assert control.line["correct"] is False
    assert (control.line["compared"]["logit_err"]["value"]
            > 1.5 * sound.line["compared"]["logit_err"]["value"])


def host_arrays(tree):
    """The arrays of a weights pytree; plain ints beside them (a head count)
    are no weights."""
    import jax

    return [x for x in jax.tree_util.tree_leaves(tree)
            if not isinstance(x, int)]


@pytest.mark.parametrize("cfg", [c["name"] for c in MAN["configs"]])
def test_the_harness_weights_stay_on_the_host_and_follow_their_seed(cfg):
    """What the harness needs of any kind's weights.  Nothing of the
    harness's may sit on the device while the window runs
    (``memory_peak_bytes`` is the program's alone): the weights are numpy
    arrays in the configuration's served type, as a checkpoint's ``np.load``
    leaves them, as many values as the kind counts, and follow their seed."""
    import ml_dtypes  # noqa: F401  (gives numpy bfloat16 by name)
    import numpy as np

    data = manifest.load_config(MAN, cfg, ROOT)
    kind = manifest.module("model_kinds", data["kind"])
    sizes = kind.sizes(data, rehearsal=True)
    a, b, c = (host_arrays(kind.init_weights(sizes, seed))
               for seed in (30, 30, 31))
    dtype = np.dtype(data["dtype"])
    assert a and all(type(x) is np.ndarray and x.dtype == dtype for x in a)
    assert sum(x.size for x in a) == kind.param_count(sizes)
    assert [x.shape for x in a] == [x.shape for x in b] == [x.shape for x in c]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not any(np.array_equal(x, y) for x, y in zip(a, c))


VITS = [c["name"] for c in MAN["configs"]
        if manifest.load_config(MAN, c["name"], ROOT)["kind"] == "vit"]


@pytest.mark.parametrize("cfg", VITS)
def test_the_vit_kinds_pytree(cfg):
    """The ``vit`` kind alone: the pytree ``vit.build`` takes, 12 arrays a
    block and 7 around them, no two blocks alike, a LayerNorm gain about 1."""
    import numpy as np

    data = manifest.load_config(MAN, cfg, ROOT)
    kind = manifest.module("model_kinds", data["kind"])
    sizes = kind.sizes(data, rehearsal=True)
    a = kind.init_weights(sizes, 30)
    assert len(host_arrays(a)) == 12 * sizes["n_layers"] + 7
    assert a["n_heads"] == sizes["n_heads"]
    w0, w1 = (np.asarray(blk["ff1"]["w"], np.float32) for blk in a["blocks"][:2])
    assert not np.array_equal(w0, w1)
    assert abs(np.asarray(a["ln_f"]["scale"], np.float32).mean() - 1) < 0.1


@pytest.mark.parametrize("size,grid", [(378, 7), (384, 7), (28, 7), (30, 4)])
def test_frames_follow_the_seed_at_any_size(size, grid):
    import numpy as np

    from benchmark.traffic_kinds import mux_saturated as kind

    a = kind.make_frames(2**31 + 5, 3, 2, (size, size, 3), grid)
    b = kind.make_frames(2**31 + 5, 3, 2, (size, size, 3), grid)
    c = kind.make_frames(2**31 + 6, 3, 2, (size, size, 3), grid)
    assert a.shape == (3, 2, size, size, 3) and a.dtype == np.uint8
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # no two frames of a run alike: every stream and every frame of its pool
    flat = a.reshape(6, -1)
    assert len({row.tobytes() for row in flat}) == 6
    # the coarse grid survives the noise: cell means differ across the frame
    cells = a[0, 0].astype(float).reshape(size, size * 3)
    assert cells[: size // grid].mean() != cells[-(size // grid):].mean()


def test_rehearsal_cli_exits_3_and_prints_no_result_line():
    out = subprocess.run(
        [sys.executable, RUN_PY, "--workload", CELLS[0], "--seed", "5",
         "--seconds", "0.5", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=cpu_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode == 3, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "compared logit_err" in out.stderr


def test_without_a_tpu_the_run_exits_2_and_prints_nothing():
    out = subprocess.run(
        [sys.executable, RUN_PY, "--workload", CELLS[0], "--seed", "5",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=cpu_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_a_directory_with_the_benchmark_alone_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in MAN["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = cpu_env()
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0], "--seed",
         "5", "--seconds", "1", "--trace", "0", "--rehearsal"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode not in (0, 3)
    assert out.stdout.strip() == ""


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no cell named"):
        rehearse("no_such.cell", seed=1)
    assert json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]
