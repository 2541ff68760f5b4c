#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that owns the chip: builds the cell, warms it, measures for
``--seconds``, checks what the timed path produced against the plain
reference, prints one JSON line and exits.  Exit 2 and no line where jax
finds no TPU or fewer chips than the cell asks for.  ``--rehearsal`` runs the
same control flow at the configuration's tiny widths on whatever jax finds,
prints no result line and exits 3: it cannot pass.
"""

from __future__ import annotations

import time

T_PROC_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, manifest, peaks, trace_reduce  # noqa: E402

WORK_DIR = os.path.join(ROOT, ".bench_work")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileWatch:
    """When XLA compiled: none may fall inside the measured window."""

    def __init__(self):
        from jax import monitoring

        self.at_ns = []
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.at_ns.append(time.perf_counter_ns())

    def inside(self, t0_ns: int, t1_ns: int) -> int:
        return sum(1 for t in self.at_ns if t0_ns <= t < t1_ns)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny widths on whatever jax finds; exits 3")
    ap.add_argument("--control", action="store_true",
                    help="run the configuration's lower-precision control in "
                         "the program's place (it has to come out not correct)")
    return ap.parse_args(argv)


def say_memory(devices, when: str) -> None:
    stats = devices[0].memory_stats() or {}
    say(f"device memory {when}: in use {stats.get('bytes_in_use')}, "
        f"peak {stats.get('peak_bytes_in_use')}, "
        f"limit {stats.get('bytes_limit')}")


def device_report(devices, chips):
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def layer_metrics(specs, ctx):
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns nothing and the metric is left out."""
    out = {}
    for spec in specs:
        data = manifest.load_layer_metric(spec["name"])
        reader = manifest.module("layer_metrics", data["reader"])
        value = getattr(reader, data["function"])(ctx)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def traced_metrics(line, man, workload, res, ctx) -> None:
    """The ``--trace 1`` half of the result: reduce the trace, run the cell's
    per-layer readers over it, add ``busy_s``/``window_s`` and the breakdown."""
    device = line["device"]
    ctx.slices = []
    if res.trace_dir is not None:
        ctx.slices = trace_reduce.reduce_trace(
            trace_reduce.load(trace_reduce.find_xplane(res.trace_dir)),
            ctx.kind.marks(ctx.sizes))
        shutil.rmtree(res.trace_dir, ignore_errors=True)
    ctx.peak = peaks.peak_for(device["kind"]) if ctx.slices else None
    line["metrics"] = layer_metrics(
        manifest.cell_metrics(man, "per_layer", workload), ctx)
    if ctx.slices:
        n = len(ctx.slices)
        device["busy_s"] = sum(s.busy_ns for s in ctx.slices) / n / 1e9
        device["window_s"] = sum(s.window_ns for s in ctx.slices) / n / 1e9
        line["breakdown"] = {"device_ops": ctx.slices[0].device_ops,
                             "idle_gaps": ctx.slices[0].idle_gaps}
        line["notes"] = dict(ctx.notes, traced_steps=ctx.slices[0].steps)


def run_cell(args, break_output=None):
    """Returns ``(exit code, report)``; the report's ``line`` is the result."""
    man = manifest.load_manifest()
    cell = manifest.find(man["workloads"], args.workload, "cell")
    cfg = manifest.load_config(man, cell["config"])
    mix = manifest.load_traffic(cell["traffic"])
    if args.rehearsal:
        mix.update(mix["rehearsal"])

    if not args.rehearsal:
        # the compile cache is this checkout's, at one fixed path, whatever
        # directory or cap the environment names: the program takes the one it
        # is given here
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)

    import jax

    devices = jax.devices()
    if not args.rehearsal and (devices[0].platform != "tpu"
                               or len(devices) < cell["chips"]):
        say(f"benchmark/run.py: {args.workload} needs {cell['chips']} TPU "
            f"chip(s); jax found {len(devices)} x {devices[0].platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
        return 2, None
    if not args.rehearsal:
        from nnstreamer_tpu.backends.exec_cache import ensure_compile_cache

        say(f"compile cache: {ensure_compile_cache()}")
    say_memory(devices, "before the program")
    watch = CompileWatch()
    kind = manifest.module("model_kinds", cfg["kind"])
    traffic = manifest.module("traffic_kinds", mix["kind"])
    sizes = kind.sizes(cfg, args.rehearsal)
    limits = dict(cfg["rehearsal_limits" if args.rehearsal else "limits"])

    t = time.perf_counter()
    weights = kind.init_weights(sizes, int(cfg["weights_seed"]))
    model = kind.build_program(sizes, weights, int(mix["streams"]),
                               control=args.control)
    say(f"weights (on the host) and model: {time.perf_counter() - t:.1f} s")
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(WORK_DIR, f"trace_{args.workload}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    res = traffic.run(mix, model, cfg, kind, sizes, args.seed, args.seconds,
                      trace_dir, break_output)
    device = device_report(devices, cell["chips"])
    say_memory(devices, "after the window")
    measured = [tp for s in res.push_ns for tp in s if tp >= res.t0_ns]
    setup_s = ((min(measured) if measured else res.t0_ns) - T_PROC_NS) / 1e9

    # the peak is read and the program's state goes; only now does anything
    # of the harness's (the reference and its copy of the weights) reach the
    # device
    del model
    traffic.per_frame_faults(res)
    numbers = {"frames_failed": float(res.failed),
               "undrained": 0.0 if res.drained else 1.0,
               "degraded": 0.0 if res.degraded is None else 1.0,
               "compiles_in_window": float(watch.inside(res.t0_ns, res.t1_ns))}
    limits.update({k: 0.0 for k in numbers})
    t = time.perf_counter()
    frames, program, picks = traffic.sample(res, mix, args.seed)
    if frames is None:
        numbers["logit_err"] = float("inf")
    else:
        reference = manifest.module("references", cfg["reference"]).forward(
            sizes, cfg, weights, frames)
        numbers["logit_err"] = check.logit_err(program, reference)
    say(f"reference over {len(picks)} frames: {time.perf_counter() - t:.1f} s")
    compared = check.verdict(numbers, limits)
    correct = check.passes(compared) and res.window["attempted"] > 0

    values = dict(res.window, setup_s=setup_s)
    line = {"correct": bool(correct),
            "attempted": int(res.window["attempted"]),
            "failed": int(res.failed),
            "metrics": {}, "device": device}
    if args.trace:
        traced_metrics(line, man, args.workload, res, SimpleNamespace(
            result=res, kind=kind, sizes=sizes, chips=cell["chips"],
            frames_per_step=int(mix["streams"]), notes={}))
    else:
        for spec in manifest.cell_metrics(man, "end_to_end", args.workload):
            if spec["name"] in values:
                line["metrics"][spec["name"]] = {"value": values[spec["name"]],
                                                 "unit": spec["unit"]}
    line["fail_notes"] = res.fail_notes
    line["compared"] = compared
    return (3 if args.rehearsal else 0), SimpleNamespace(
        line=line, result=res, compared=compared, values=values)


def main(argv=None) -> int:
    args = parse_args(argv)
    code, report = run_cell(args)
    if report is None:
        return code
    for name, c in report.compared.items():
        say(f"compared {name} = {c['value']!r} limit {c['limit']!r}")
    if args.rehearsal:
        say("rehearsal (control flow only, not a result): "
            + json.dumps({**report.line, "values": report.values}))
        return code
    print(json.dumps(report.line), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
