#!/usr/bin/env python
"""Where does the mux path's host machinery spend its time per stream?

Pinned to the CPU on purpose: with an identity model the
mux->batch->filter->unbatch->demux path is pure host machinery, and on a
CPU host its aggregate throughput DECLINES per added stream where
batching should at worst be flat.
This tool isolates where the per-stream cost lands:

- sweeps STREAM COUNTS (1, 2, 4, 8 by default) at a fixed TOTAL frame
  budget, identity jax model, CPU pin — so the filter's work is constant
  and any decline is pure machinery;
- attributes wall time per element via the obs hook bus
  (``dispatch_exit`` carries wall-ns per sink-pad dispatch): mux collect
  vs batch concat vs filter invoke vs unbatch/demux fan-out;
- reports source/sink thread counts per config (each added stream adds a
  source thread and a sink dispatch — on a GIL'd 1-core host those time-
  slice rather than parallelize);
- accounts hot-path host memcpy via the ``copy`` hook (the zero-copy
  path's tracer signal, ``nnstreamer_tpu/pool.py``): bytes-copied and
  fresh allocations per frame ride as sweep-table columns, so the
  pooled slot-wise assembly / RowBatch concat-skip savings are visible
  next to the fps they buy;
- separates TRUE device time from host machinery via the device lane
  (``nnstreamer_tpu/obs/device.py``): a ``DeviceTracer`` completion
  probe per dispatch yields a ``dev us/fr`` column — on an async
  backend the ``dispatch_exit`` attribution only times the enqueue, so
  without this column device compute hides inside whichever element
  blocks first — and a ``hostdisp`` column: summed
  ``device_idle{reason=host_dispatch}`` span µs per frame (gaps where
  the chip sat starved with nothing enqueued — the dead time
  whole-segment compilation folds away, docs/performance.md);
- rides the cost observatory (``nnstreamer_tpu/obs/costmodel.py``)
  over every measured run: ``cm disp`` / ``cm qwait`` columns are the
  summed per-stage mean host-dispatch and queue-wait µs from the same
  per-leg aggregates the ``costmodel`` tracer persists to
  COST_MODEL.json — the sweep table and the persisted model can be
  cross-checked against each other;
- shows UTILIZATION, not just latency (the obs/util.py lane): ``mfu``
  (cost_analysis flops over measured device time vs the configured
  peak) and ``busy`` (windowed device_exec coverage per device)
  columns ride the same sweep, so "8 streams decline" separates into
  "chip idle" vs "chip busy on machinery".

Usage: ``python tools/profile_mux_overhead.py [--mesh[=SPEC]]
[--lanes[=N]] [TOTAL_FRAMES] [SWEEP...]`` e.g. ``python
tools/profile_mux_overhead.py 2000 1 2 4 8 16 32 64``.  ``--mesh``
(default spec ``dp:8``) sweeps the mesh-sharded dispatch lane over a
forced 8-device host mesh and adds chips-used / per-shard-batch
columns.  ``--lanes`` (default ``auto``) runs the sweep
on the dispatcher-lane runtime (``graph/lanes.py``) instead of
thread-per-element; either way a ``lanes`` column reports the mode and
the run ends with a lane-vs-thread A/B at the widest point (the other
mode re-measured) plus the 8→widest flatness verdict — thread mode
multiplies host threads per stream and declines, lanes must hold the
widest point within ~10% of the 8-stream point.
``NNSTPU_POOL_ENABLED=false NNSTPU_POOL_CONCAT_THRESHOLD=0`` reproduces
the pre-pool behavior for an A/B.  A host-dispatch profile, never a
device metric.
"""
import os
import sys
import threading
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# --lanes[=N|auto]: run the sweep on the dispatcher-lane runtime
# ([dispatch] lanes); the A/B verdict at the end measures the other mode
LANES = None
for _arg in list(sys.argv):
    if _arg == "--lanes" or _arg.startswith("--lanes="):
        LANES = _arg.partition("=")[2] or "auto"
        sys.argv.remove(_arg)

# --mesh[=SPEC] (default dp:8): sweep the mesh-sharded dispatch lane —
# must export NNSTPU_MESH and the forced host device count BEFORE jax
# initializes its CPU client
MESH = None
for _arg in list(sys.argv):
    if _arg == "--mesh" or _arg.startswith("--mesh="):
        MESH = _arg.partition("=")[2] or "dp:8"
        sys.argv.remove(_arg)
if MESH is not None:
    os.environ["NNSTPU_MESH"] = MESH
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

# the per-run cost-model tracers are sweep probes, not evidence: they
# must not write COST_MODEL.json on every stop (explicit env wins)
os.environ.setdefault("NNSTPU_OBS_COSTMODEL_AUTOSAVE", "false")
# the hostdisp column prices every starvation gap ≥50 µs — the default
# 5 ms floor is tuned for alerting, not for a µs-scale identity sweep
os.environ.setdefault("NNSTPU_OBS_DEVICE_IDLE_GAP_MS", "0.05")

import jax

jax.config.update("jax_platforms", "cpu")
import numpy as np

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.backends.jax_backend import JaxModel
from nnstreamer_tpu.elements.batch import TensorBatch, TensorUnbatch
from nnstreamer_tpu.elements.demux import TensorDemux
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.mux import TensorMux
from nnstreamer_tpu.elements.queue import Queue
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.obs import hooks
from nnstreamer_tpu.obs import spans as obs_spans
from nnstreamer_tpu.obs.costmodel import CostModelTracer
from nnstreamer_tpu.obs.device import DeviceTracer
from nnstreamer_tpu.obs.metrics import MetricsRegistry
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

TOTAL = int(sys.argv[1]) if len(sys.argv) > 1 else 2000
SWEEP = [int(a) for a in sys.argv[2:]] or [1, 2, 4, 8, 16, 32, 64]
# identity isolates the collect/batch machinery; matmul emulates the
# compute-bound config5 regime (is the decline machinery or model?)
MODEL = os.environ.get("MUX_PROFILE_MODEL", "identity")
D = int(os.environ.get("MUX_PROFILE_DIM",
                       "16" if MODEL == "identity" else "1024"))
arr = np.zeros((D,), np.float32)
_W = None


def model_for(streams):
    shape = (D,) if streams == 1 else (streams, D)
    spec = TensorsSpec.of(TensorSpec(dtype=np.float32, shape=shape))
    if MODEL == "identity":
        return JaxModel(apply=lambda p, x: x, input_spec=spec)
    global _W
    if _W is None:
        import jax.numpy as jnp

        _W = jnp.asarray(np.random.default_rng(0)
                         .standard_normal((D, D)).astype(np.float32))

    def apply(p, x):
        h = x
        for _ in range(8):  # ~8 * D^2 flops/frame: compute-bound on CPU
            h = jax.numpy.tanh(h @ _W)
        return h

    return JaxModel(apply=apply, input_spec=spec)


class Attribution:
    """Per-element busy wall-ns from the dispatch_exit hook."""

    def __init__(self):
        self.ns = defaultdict(int)
        self.calls = defaultdict(int)
        self._lock = threading.Lock()

    def __call__(self, node, pad, item, dur_ns):
        with self._lock:
            self.ns[type(node).__name__] += dur_ns
            self.calls[type(node).__name__] += 1

    def table(self):
        return sorted(self.ns.items(), key=lambda kv: -kv[1])


class CopyCount:
    """Hot-path host memcpy accounting from the ``copy`` hook."""

    def __init__(self):
        self.nbytes = 0
        self.copies = 0
        self.allocs = 0
        self._lock = threading.Lock()

    def __call__(self, node, nbytes, allocs):
        with self._lock:
            self.nbytes += int(nbytes)
            self.copies += 1
            self.allocs += int(allocs)


def run_mux(streams, frames_per_stream, attribute=False, lanes=None,
            wide=None):
    """One measured pipeline run.  ``lanes``: None = whatever the
    environment says, ``0`` = force thread-per-element, ``N``/``auto``
    = force the dispatcher-lane runtime.  ``wide`` forces the
    independent-chains topology regardless of stream count (used to
    anchor the flatness verdict within ONE topology)."""
    if lanes is not None:
        os.environ["NNSTPU_DISPATCH_LANES"] = str(lanes)
    use_wide = (streams > 16) if wide is None else bool(wide)
    state = {"count": 0, "t0": None}
    _cb_lock = threading.Lock()

    def cb(frame):
        with _cb_lock:
            if state["t0"] is None:
                state["t0"] = time.perf_counter()
            state["count"] += 1

    p = Pipeline()
    if streams == 1 and not use_wide:
        src = p.add(DataSrc(name="s0", data=[arr.copy() for _ in
                                             range(frames_per_stream)]))
        filt = p.add(TensorFilter(name="f", framework="jax",
                                  model=model_for(1)))
        sink = p.add(TensorSink(name="o0", callback=cb))
        p.link_chain(src, filt, sink)
    elif use_wide:
        # TensorMux caps at 16 sink pads, and past 16 streams the
        # question changes anyway: this is the fleet-worker regime —
        # N INDEPENDENT chains per host (src → queue → filter → sink),
        # where thread-per-element pays 2 threads per stream and the
        # dispatcher lanes pay none.  The filters are host-side
        # (framework=custom): what this regime measures is pure
        # scheduling machinery — per-chain jax backends would each
        # compile inside the measured window and drown it.
        filt = None
        for i in range(streams):
            src = p.add(DataSrc(name=f"s{i}", data=[
                arr.copy() for _ in range(frames_per_stream)]))
            qn = p.add(Queue(name=f"q{i}", max_size_buffers=16))
            fn = p.add(TensorFilter(name=f"f{i}", framework="custom",
                                    model=lambda x: x * 2.0))
            p.link_chain(src, qn, fn,
                         p.add(TensorSink(name=f"o{i}", callback=cb)))
            if filt is None:
                filt = fn
    else:
        mux = p.add(TensorMux(sync_mode="nosync"))
        for i in range(streams):
            src = p.add(DataSrc(name=f"s{i}", data=[arr.copy() for _ in
                                                    range(frames_per_stream)]))
            p.link(src, f"{mux.name}.sink_{i}")
        batch = p.add(TensorBatch())
        filt = p.add(TensorFilter(name="f", framework="jax",
                                  model=model_for(streams)))
        unb = p.add(TensorUnbatch())
        demux = p.add(TensorDemux())
        p.link_chain(mux, batch, filt, unb, demux)
        for i in range(streams):
            p.link(f"{demux.name}.src_{i}",
                   p.add(TensorSink(name=f"o{i}", callback=cb)))
    attr = Attribution()
    copies = CopyCount()
    obs_spans.reset()  # fresh recorder per run; the tracer re-activates
    dev = p.attach_tracer(DeviceTracer(registry=MetricsRegistry()))
    cm = p.attach_tracer(CostModelTracer(registry=MetricsRegistry()))
    hooks.connect("copy", copies)
    if attribute:
        hooks.connect("dispatch_exit", attr)
    nlanes = 0
    host_threads = 0
    try:
        t_start = time.perf_counter()
        p.start()
        nlanes = p._lanes.nlanes if p._lanes is not None else 0
        # threads the graph OWNS (spawned sources/workers, or lanes +
        # promoted helpers) — active_count() would under-count fast
        # finite sources that exit before the sweep ends
        if p._lanes is not None:
            host_threads = nlanes + len(p._lanes._helpers)
        else:
            host_threads = len(p.threads)
        if not p.wait(600):
            raise RuntimeError("sweep pipeline did not finish")
        p.stop()
        wall = time.perf_counter() - t_start
    finally:
        hooks.disconnect("copy", copies)
        if attribute:
            hooks.disconnect("dispatch_exit", attr)
    done = state["count"] - max(1, streams)  # exclude the clock-start frame(s)
    fps = done / (time.perf_counter() - state["t0"])
    total_in = streams * frames_per_stream
    copies.per_frame = copies.nbytes / max(1, total_in)
    copies.allocs_per_frame = copies.allocs / max(1, total_in)
    # stop() drained the completion-probe queue: summary is final
    dsum = dev.summary()
    copies.dev_us_per_frame = dsum["device_ns"] / 1e3 / max(1, total_in)
    copies.dev_dispatches = dsum["completed"]
    # host-dispatch starvation: device_idle spans whose gap began with an
    # empty probe queue — dead time between device programs that
    # whole-segment compilation (graph/segments.py) exists to remove
    idle = [r for r in obs_spans.snapshot()
            if r[0] == obs_spans.PH_COMPLETE and r[4] == "device_idle"
            and r[9].get("reason") == "host_dispatch"]
    copies.hostdisp_us = sum(r[2] for r in idle) / 1e3 / max(1, total_in)
    # utilization columns (obs/util.py lane): aggregate MFU and mean
    # busy fraction across the devices this config touched — so the
    # 1→8 stream sweep shows whether added streams buy chip utilization
    # or only host machinery (mfu None = no cost_analysis on this host)
    devs = list(dsum["by_device"].values())
    mfus = [d["mfu"] for d in devs if d.get("mfu") is not None]
    copies.mfu = sum(mfus) / len(mfus) if mfus else None
    busys = [d["busy_fraction"] for d in devs
             if d.get("busy_fraction") is not None]
    copies.busy = sum(busys) / len(busys) if busys else None
    # mesh columns: chips the LAST compiled executable actually spanned
    # (an indivisible leading dim falls back to 1) and the per-shard rows
    mesh = getattr(filt.backend, "_mesh", None)
    copies.chips = int(mesh.devices.size) if mesh is not None else 1
    copies.per_shard = max(1, streams) / copies.chips
    copies.lanes = nlanes
    copies.host_threads = host_threads
    # cost-model columns (obs/costmodel.py): the same per-stage legs
    # the observatory persists, summed across nodes — mean host-dispatch
    # and queue-wait µs per event, next to the fps they explain
    cm_stages = cm.summary()["stages"]

    def _leg_sum(leg):
        vals = [st["legs"][leg]["mean_us"] for st in cm_stages.values()
                if leg in st["legs"]]
        return sum(vals) if vals else None

    copies.cm_dispatch_us = _leg_sum("dispatch")
    copies.cm_queue_us = _leg_sum("queue_wait")
    return fps, wall, attr, copies


def main():
    ncpu = os.cpu_count()
    mode_lanes = LANES if LANES is not None else 0
    print(f"mux overhead sweep: total={TOTAL} frames, host cpus={ncpu}, "
          f"mode={'lanes=' + str(mode_lanes) if LANES is not None else 'thread-per-element'}")
    if MESH is not None:
        print(f"mesh-sharded dispatch: NNSTPU_MESH={MESH!r} over "
              f"{len(jax.devices())} host devices")
    def fmt_mfu(v):
        return f"{v * 100:>8.3f}%" if v is not None else f"{'-':>9}"

    def fmt_busy(v):
        return f"{v * 100:>6.1f}%" if v is not None else f"{'-':>7}"

    def fmt_cm(v):
        return f"{v:>9.1f}" if v is not None else f"{'-':>9}"

    run_mux(1, 50, lanes=mode_lanes)
    base_fps, _, _, base_cp = run_mux(1, TOTAL, lanes=mode_lanes)
    print(f"\n{'streams':>7} {'lanes':>6} {'agg fps':>10} {'us/frame':>10} "
          f"{'vs 1-stream':>11} {'copy KB/fr':>11} {'allocs/fr':>10} "
          f"{'dev us/fr':>10} {'hostdisp':>9} {'mfu':>9} {'busy':>7} "
          f"{'chips':>6} {'b/shard':>8} {'cm disp':>9} {'cm qwait':>9}")
    print(f"{1:>7} {base_cp.lanes:>6} {base_fps:>10.0f} "
          f"{1e6 / base_fps:>10.1f} {'1.00x':>11} "
          f"{base_cp.per_frame / 1024:>11.1f} "
          f"{base_cp.allocs_per_frame:>10.3f} "
          f"{base_cp.dev_us_per_frame:>10.1f} "
          f"{base_cp.hostdisp_us:>9.1f} "
          f"{fmt_mfu(base_cp.mfu)} {fmt_busy(base_cp.busy)} "
          f"{base_cp.chips:>6} {base_cp.per_shard:>8.2f} "
          f"{fmt_cm(base_cp.cm_dispatch_us)} {fmt_cm(base_cp.cm_queue_us)}")
    results = {1: base_fps}
    last_cp = base_cp
    for s in [s for s in SWEEP if s != 1]:
        run_mux(s, max(8, 160 // s), lanes=mode_lanes)  # warm the s-wide exe
        fps, _, _, cp = run_mux(s, TOTAL // s, lanes=mode_lanes)
        results[s] = fps
        last_cp = cp
        print(f"{s:>7} {cp.lanes:>6} {fps:>10.0f} {1e6 / fps:>10.1f} "
              f"{fps / base_fps:>10.2f}x {cp.per_frame / 1024:>11.1f} "
              f"{cp.allocs_per_frame:>10.3f} {cp.dev_us_per_frame:>10.1f} "
              f"{cp.hostdisp_us:>9.1f} "
              f"{fmt_mfu(cp.mfu)} {fmt_busy(cp.busy)} "
              f"{cp.chips:>6} {cp.per_shard:>8.2f} "
              f"{fmt_cm(cp.cm_dispatch_us)} {fmt_cm(cp.cm_queue_us)}")

    # lane-vs-thread A/B at the widest point: re-measure in the OTHER
    # mode, then judge flatness per mode — widest vs the 8-stream point
    # measured in the SAME topology (past 16 streams the sweep switches
    # to independent chains, so the anchor is re-run wide too)
    widest = max(SWEEP)
    other = 0 if LANES is not None else "auto"
    run_mux(widest, max(8, 160 // widest), lanes=other)
    ab_fps, _, _, ab_cp = run_mux(widest, TOTAL // widest, lanes=other)
    this_label = f"lanes={mode_lanes}" if LANES is not None else "threads"
    other_label = "threads" if LANES is not None else f"lanes({ab_cp.lanes})"
    this_threads = last_cp.host_threads
    print(f"\nA/B at {widest} streams: {this_label} {results[widest]:.0f} "
          f"fps on {this_threads} host threads vs {other_label} "
          f"{ab_fps:.0f} fps on {ab_cp.host_threads} host threads "
          f"({results[widest] / max(ab_fps, 1e-9):.2f}x fps, "
          f"{ab_cp.host_threads / max(this_threads, 1)}x the threads)")
    if widest > 16:
        wide = widest > 16
        run_mux(8, 20, lanes=mode_lanes, wide=wide)
        anchor, _, _, _ = run_mux(8, TOTAL // 8, lanes=mode_lanes,
                                  wide=wide)
        run_mux(8, 20, lanes=other, wide=wide)
        anchor_ab, _, _, _ = run_mux(8, TOTAL // 8, lanes=other, wide=wide)
    else:
        anchor = anchor_ab = results.get(8) or results[
            min(results, key=lambda k: abs(k - 8))]
    flat = results[widest] / max(anchor, 1e-9)
    flat_ab = ab_fps / max(anchor_ab, 1e-9)
    if LANES is not None:
        verdict = "FLAT (within 10%)" if flat >= 0.90 else "DECLINING"
        print(f"lane flatness: {widest}-stream agg is {flat:.2f}x the "
              f"8-stream point (same topology) -> {verdict}; thread mode: "
              f"{flat_ab:.2f}x its own 8-stream point")
    else:
        print(f"thread flatness: {widest}-stream agg is {flat:.2f}x the "
              f"8-stream point (same topology); lane mode: {flat_ab:.2f}x "
              f"its own 8-stream point")

    # attribution pass at the widest sweep point (sweep mode)
    run_mux(widest, 30, lanes=mode_lanes)
    fps, wall, attr, cp = run_mux(widest, TOTAL // widest, attribute=True,
                                  lanes=mode_lanes)
    print(f"\nper-element busy time at {widest} streams "
          f"({TOTAL // widest} frames/stream, wall {wall:.2f}s; "
          "dispatch_exit hook, sink-pad wall-ns):")
    total_busy = sum(attr.ns.values()) or 1
    for name, ns in attr.table():
        per_call = ns / max(1, attr.calls[name]) / 1e3
        print(f"  {name:<14} {ns / 1e9:>8.3f}s  {100 * ns / total_busy:>5.1f}%"
              f"  {per_call:>8.1f} us/dispatch  x{attr.calls[name]}")
    busy_frac = total_busy / 1e9 / wall
    print(f"  busy/wall = {busy_frac:.2f} "
          f"(the rest is source threads + queue waits + GIL slicing)")
    print(f"  hot-path copies at {widest} streams: "
          f"{cp.per_frame / 1024:.1f} KB/frame, "
          f"{cp.allocs_per_frame:.3f} fresh allocs/frame "
          f"({cp.copies} memcpys, {cp.nbytes / 1e6:.1f} MB total)")
    print(f"  true device time at {widest} streams: "
          f"{cp.dev_us_per_frame:.1f} us/frame over {cp.dev_dispatches} "
          f"probed dispatches (device lane; host attribution above times "
          f"the enqueue only)")
    print(f"  host-dispatch starvation at {widest} streams: "
          f"{cp.hostdisp_us:.1f} us/frame of device_idle with an empty "
          f"probe queue (the gap whole-segment compilation folds away; "
          f"docs/performance.md)")
    mfu_s = f"{cp.mfu * 100:.3f}%" if cp.mfu is not None \
        else "n/a (no cost_analysis)"
    busy_s = f"{cp.busy * 100:.1f}%" if cp.busy is not None else "n/a"
    print(f"  utilization at {widest} streams: mfu {mfu_s}, device busy "
          f"fraction {busy_s} (the rest of the device window is idle — "
          f"host dispatch, queue wait, or wire; see device_idle spans)")


if __name__ == "__main__":
    main()
