#!/usr/bin/env python
"""Benchmark: streaming-pipeline throughput on one TPU, vs tflite-CPU.

North-star metric (BASELINE.md / BASELINE.json): frames/sec/chip through the
``tensor_filter`` invoke path on the image-labeling pipeline, with tflite-CPU
(the reference's flagship backend) as ``vs_baseline``.

Contract:
- the run needs a TPU: the platform is checked in this process before any
  leg, and without one the program prints why on stderr and exits 2 — it
  never measures the CPU under a device metric's name;
- this one process owns the chip from start to end; no child needs a device
  (the tflite/torch baseline children in tools/bench_baselines.py run on
  the CPU only);
- every leg is individually guarded so one failed leg does not stop the
  rest, but a leg that raised makes the exit code 1 once the remaining legs
  have run (``failed_legs`` in the JSON names them);
- legs run in VALUE ORDER (config1 variants → config5 → quant → the rest)
  under a time budget (BENCH_BUDGET_S, default 480 s); legs past the budget
  are skipped and listed;
- after EVERY leg a complete JSON snapshot (marked ``"partial": true``) is
  printed to stdout and atomically written to ``BENCH_PARTIAL.json`` — the
  LAST stdout line is the result, and a killed run leaves the previous
  snapshot as evidence.  SIGTERM and the hard watchdog emit the last
  snapshot and exit non-zero (143 / 124): an interrupted run is not a run;
- every result names the device it ran on (``device``: platform,
  ``device_kind``, count); everything else goes to stderr.

Also measured (recorded in BENCH_NOTES.md + the JSON "extra" field):
- config #5: mux(4 streams) → batch → jax filter → unbatch → demux;
- MFU estimate for the MobileNet-v2 forward (XLA cost analysis / step time);
- Pallas int8_matmul vs plain-XLA.
"""

import json
import os
import subprocess
import sys
import threading
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _apply_mesh_flag(argv):
    """``--mesh[=SPEC]`` (default auto): bench the mesh-sharded dispatch
    lane — exports NNSTPU_MESH for the whole run.  Must run before any
    jax backend initializes."""
    for arg in list(argv):
        if arg == "--mesh" or arg.startswith("--mesh="):
            os.environ["NNSTPU_MESH"] = arg.partition("=")[2] or "auto"
            argv.remove(arg)


_apply_mesh_flag(sys.argv)

import numpy as np  # noqa: E402

NORMALIZE = "typecast:float32,add:-127.5,div:127.5"


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def require_tpu() -> dict:
    """The device this run measures, as jax reports it — or exit 2.

    Checked in-process (this process is the one that will hold the chip);
    there is no probe child and no CPU fallback."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"bench.py: jax found no TPU (platform={dev.platform!r}, "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); this "
            "benchmark measures the chip and does not fall back to the CPU")
        sys.exit(2)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


# ------------------------------------------------------------- MFU ladder

LADDER_CONFIG = "mobilenet_v2_224"
LADDER_BATCHES = (8, 32, 128)
LADDER_DTYPES = ("fp32", "int8")
LADDER_MESHES = (1, 8)
# BENCH_NOTES targets on a v5e chip (batch -> minimum MFU); ~15-20% is
# the realistic depthwise-bound asymptote for this model
LADDER_TARGETS = {8: 0.01, 32: 0.03, 128: 0.10}


class _Skipped(RuntimeError):
    """A leg deliberately skipped (0-frame env override): recorded in the
    errors list for transparency; not a failure."""


# ------------------------------------------------------------ pipeline legs


def run_pipeline_fps(framework, model, frames, warmup=3, normalize=True,
                     decoder=None, custom="", accel=True, timeout_s=600,
                     upload=False, pipelined=True):
    """Stream frames through datasrc → transform(normalize) → tensor_filter
    [→ queue → tensor_decoder] → sink; frames/sec.  On the jax path the
    transform fuses into the model's XLA program, so raw uint8 crosses
    host→device.  ``decoder`` is an optional (mode, options-dict) pair —
    a ``queue`` is inserted before it so the decoder's blocking read of
    frame N's device result runs in its own thread while the source thread
    dispatches frame N+1 (the reference's queue-element pipelining;
    without it, a host decoder serializes the stream at one full device
    round trip per frame).  ``pipelined=False`` drops that queue — the
    serialized chain the segment.ab leg measures, where the host decode
    sits between device programs and its dead time shows up as
    ``device_idle{reason=host_dispatch}`` spans.  ``accel=False`` keeps
    the normalize on host numpy (the CPU-baseline configuration)."""
    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.elements.decoder import TensorDecoder
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc
    from nnstreamer_tpu.elements.transform import TensorTransform

    state = {"first": None, "out": None, "count": 0}

    def sink_cb(frame):
        state["count"] += 1
        state["out"] = frame.tensors[0]
        if state["first"] is None:
            state["first"] = time.perf_counter()

    def run(n):
        state.update(first=None, out=None, count=0)
        p = Pipeline()
        src = p.add(DataSrc(data=frames[:n]))
        chain = [src]
        if normalize:
            chain.append(p.add(TensorTransform(mode="arithmetic", option=NORMALIZE,
                                               acceleration=accel)))
        fcustom = custom
        if upload:
            # transfer/dispatch overlap: the source thread device_puts wire
            # bytes, the queue worker only dispatches (docs/performance.md)
            from nnstreamer_tpu.elements.queue import Queue
            from nnstreamer_tpu.elements.upload import TensorUpload

            chain.append(p.add(TensorUpload()))
            chain.append(p.add(Queue(max_size_buffers=16)))
            # linear chain: the uploaded buffer is single-use → donate it
            fcustom = f"{custom},donate=1" if custom else "donate=1"
        chain.append(p.add(TensorFilter(framework=framework, model=model,
                                        custom=fcustom)))
        if decoder is not None:
            from nnstreamer_tpu.elements.queue import Queue

            mode, options = decoder
            if pipelined:
                chain.append(p.add(Queue(max_size_buffers=64)))
            chain.append(p.add(TensorDecoder(mode=mode, **options)))
        chain.append(p.add(TensorSink(callback=sink_cb)))
        p.link_chain(*chain)
        p.run(timeout=timeout_s)
        out = state["out"]
        if out is not None and hasattr(out, "block_until_ready"):
            out.block_until_ready()  # drain async device work before timing
        if state["first"] is None or state["count"] < 2:
            raise RuntimeError(
                f"pipeline delivered {state['count']} frames (expected {n}) — "
                "stalled or wedged backend"
            )
        dt = time.perf_counter() - state["first"]
        # steady-state rate: frames after the first (which pays compile/
        # startup) over the time since the first arrived
        return (state["count"] - 1) / dt

    run(warmup)  # compile + cache
    return run(len(frames))


def dynbatch_max() -> int:
    """dynbatch's batch cap: 8 keeps latency low and the executable-bucket
    set small; BENCH_DYNBATCH_MAX overrides (rounded down to a power of
    two, which DynBatch requires)."""
    env = os.environ.get("BENCH_DYNBATCH_MAX")
    if not env:
        return 8
    v = int(env)
    if v < 1:
        raise ValueError(f"BENCH_DYNBATCH_MAX={env!r} must be >= 1")
    return 1 << (v.bit_length() - 1)


def poly_wire_model(base, image_size: int):
    """Batch-polymorphic uint8 wire wrapper around a built model: the
    NORMALIZE chain fuses into the program, raw uint8 crosses the wire,
    and the leading batch dim stays open for dynbatch's buckets.  One
    definition for every dynbatch leg (mobilenet / pose / cascade)."""
    import jax.numpy as jnp

    from nnstreamer_tpu.backends.jax_backend import JaxModel
    from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

    return JaxModel(
        apply=lambda p, x: base.apply(
            base.params, (x.astype(jnp.float32) - 127.5) / 127.5
        ),
        input_spec=TensorsSpec.of(
            TensorSpec(dtype=np.uint8,
                       shape=(None, image_size, image_size, 3))
        ),
    )


def run_dynbatch_fps(frames, max_batch=8, upload=False, poly_model=None,
                     decoder=None):
    """Config #1d: adaptive micro-batching on ONE stream — datasrc →
    tensor_dynbatch → jax filter (polymorphic batch, normalize fused in
    the model fn) → tensor_dynunbatch → sink.  Frames that pile up behind
    the device coalesce into bucketed batched invokes; transfer+dispatch
    amortize over the pile-up automatically.

    With ``upload=True`` (config #1du) a tensor_upload+queue pair sits
    between dynbatch and the filter: the coalesced batch crosses the wire
    in the dynbatch worker thread while the queue worker dispatches the
    PREVIOUS batch — transfer/dispatch overlap on top of amortization,
    the full stack of the streaming machinery.

    ``poly_model`` overrides the default MobileNet classifier with any
    batch-polymorphic JaxModel over wire frames (pose and the cascade
    ride the same machinery); ``decoder`` is the
    optional (mode, options) post-stage, queue-decoupled like
    :func:`run_pipeline_fps`.

    EVERY bucket executable is pre-compiled into the backend's LRU cache
    and the warm backend is injected into the filter — which pile-ups
    occur mid-run is timing-dependent, and an in-run XLA compile would
    otherwise skew the measurement."""
    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.backends.base import get_backend
    from nnstreamer_tpu.elements.dynbatch import DynBatch, DynUnbatch
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc
    from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

    if poly_model is None:
        from nnstreamer_tpu.models import mobilenet_v2

        poly_model = poly_wire_model(
            mobilenet_v2.build(num_classes=1001, image_size=224), 224)
    frame0 = np.asarray(frames[0])
    frame_shape, frame_dtype = tuple(frame0.shape), frame0.dtype
    backend = get_backend("jax")
    # linear dynbatch chain: coalesced upload buffers are single-use
    backend.open(poly_model, custom="donate=1" if upload else "")
    ndev = backend.mesh_devices() if hasattr(backend, "mesh_devices") else 1
    b = 1
    while b <= max_batch:  # prime every bucket's executable (LRU-cached);
        backend.reconfigure(TensorsSpec.of(  # mesh buckets are ndev × pow-2
            TensorSpec(dtype=frame_dtype, shape=(b * ndev,) + frame_shape)
        ))
        b <<= 1

    state = {"first": None, "count": 0, "out": None, "batches": None}

    def cb(frame):
        state["count"] += 1
        state["out"] = frame.tensors[0]
        if state["first"] is None:
            state["first"] = time.perf_counter()

    p = Pipeline()
    src = p.add(DataSrc(data=frames))
    dyn = p.add(DynBatch(max_batch=max_batch))
    chain = [src, dyn]
    if upload:
        from nnstreamer_tpu.elements.queue import Queue
        from nnstreamer_tpu.elements.upload import TensorUpload

        chain.append(p.add(TensorUpload()))
        chain.append(p.add(Queue(max_size_buffers=8)))
    filt = p.add(TensorFilter(framework="jax", backend=backend))
    unb = p.add(DynUnbatch())
    chain += [filt, unb]
    if decoder is not None:
        from nnstreamer_tpu.elements.decoder import TensorDecoder
        from nnstreamer_tpu.elements.queue import Queue

        mode, options = decoder
        chain.append(p.add(Queue(max_size_buffers=64)))
        chain.append(p.add(TensorDecoder(mode=mode, **options)))
    sink = p.add(TensorSink(callback=cb))
    chain.append(sink)
    p.link_chain(*chain)
    p.run(timeout=600)
    state["batches"] = dyn.batches_emitted
    if state["first"] is None or state["count"] < 2:
        raise RuntimeError(
            f"dynbatch pipeline delivered {state['count']} frames"
        )
    fps = (state["count"] - 1) / (time.perf_counter() - state["first"])
    return fps, state["batches"], len(frames)


def run_mux_batched_fps(model, n_streams, frames_per_stream, image_u8,
                        framework="jax", custom="", accel=True,
                        upload=False):
    """Config #5: src×N → mux → batch → filter → unbatch → demux →
    sink×N.  Throughput counted in *frames* (N per batched invoke).
    ``upload=True`` inserts tensor_upload+queue after the (fused-away)
    normalize so the batched wire transfer overlaps the previous round's
    dispatch — without it the mux worker pays transfer+dispatch serially
    per round."""
    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.elements.batch import TensorBatch, TensorUnbatch
    from nnstreamer_tpu.elements.demux import TensorDemux
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.mux import TensorMux
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc
    from nnstreamer_tpu.elements.transform import TensorTransform

    state = {"first": None, "count": 0, "out": None}

    def sink_cb(frame):
        state["count"] += 1
        state["out"] = frame.tensors[0]
        if state["first"] is None:
            state["first"] = time.perf_counter()

    def run(per_stream):
        state.update(first=None, count=0, out=None)
        data = [image_u8.copy() for _ in range(per_stream)]
        p = Pipeline()
        mux = p.add(TensorMux(sync_mode="nosync"))
        for i in range(n_streams):
            src = p.add(DataSrc(data=list(data), name=f"cam{i}"))
            p.link(src, f"{mux.name}.sink_{i}")
        batch = p.add(TensorBatch())
        norm = p.add(TensorTransform(mode="arithmetic", option=NORMALIZE,
                                     acceleration=accel))
        mids = [batch, norm]
        fcustom = custom
        if upload:
            from nnstreamer_tpu.elements.queue import Queue
            from nnstreamer_tpu.elements.upload import TensorUpload

            mids.append(p.add(TensorUpload()))
            mids.append(p.add(Queue(max_size_buffers=8)))
            # linear mux→batch→filter chain: uploaded buffer is single-use
            fcustom = f"{custom},donate=1" if custom else "donate=1"
        filt = p.add(TensorFilter(framework=framework, model=model, custom=fcustom))
        unbatch = p.add(TensorUnbatch())
        demux = p.add(TensorDemux())
        p.link_chain(mux, *mids, filt, unbatch, demux)
        for i in range(n_streams):
            sink = p.add(TensorSink(callback=sink_cb, name=f"out{i}"))
            p.link(f"{demux.name}.src_{i}", sink)
        p.run(timeout=600)
        out = state["out"]
        if out is not None and hasattr(out, "block_until_ready"):
            out.block_until_ready()
        if state["first"] is None or state["count"] <= n_streams:
            raise RuntimeError(
                f"mux pipeline delivered {state['count']} frames — stalled"
            )
        dt = time.perf_counter() - state["first"]
        return (state["count"] - n_streams) / dt  # first batched round pays startup

    run(2)  # warmup/compile
    return run(frames_per_stream)


def run_lstm_recurrence_fps(steps, hidden=64, framework="jax", model=None,
                            custom=""):
    """Config #4: custom LSTM recurrent filter through repo-slot cycles
    (the reference's tests/nnstreamer_repo_lstm topology).  steps/sec —
    dominated by the per-frame repo handoff + filter invoke."""
    import nnstreamer_tpu as nns
    from nnstreamer_tpu.buffer import SECOND, Frame
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.repo import TensorRepoSink, TensorRepoSrc
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.tee import Tee
    from nnstreamer_tpu.elements.testsrc import DataSrc
    from nnstreamer_tpu.models import lstm
    from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

    if model is None:
        model = lstm.build_cell(input_size=hidden, hidden_size=hidden)
    caps = TensorsSpec(tensors=(TensorSpec(dtype=np.float32, shape=(hidden,)),))
    dur = SECOND // 30

    def run(n):
        data = [
            Frame.of(np.full((hidden,), 0.01 * i, np.float32), pts=i * dur,
                     duration=dur)
            for i in range(n)
        ]
        state = {"first": None, "count": 0}

        def cb(frame):
            state["count"] += 1
            if state["first"] is None:
                state["first"] = time.perf_counter()

        p = nns.Pipeline()
        h_src = p.add(TensorRepoSrc(name="h", slot_index=90, caps=caps))
        c_src = p.add(TensorRepoSrc(name="c", slot_index=91, caps=caps))
        x_src = p.add(DataSrc(name="x", data=data))
        mux = p.add(nns.make("tensor_mux", sync_mode="nosync"))
        filt = p.add(TensorFilter(framework=framework, model=model, custom=custom))
        demux = p.add(nns.make("tensor_demux"))
        tee = p.add(Tee())
        out = p.add(TensorSink(callback=cb))
        p.link(h_src, f"{mux.name}.sink_0")
        p.link(c_src, f"{mux.name}.sink_1")
        p.link(x_src, f"{mux.name}.sink_2")
        p.link_chain(mux, filt, demux)
        p.link(f"{demux.name}.src_0", tee)
        p.link(tee, p.add(TensorRepoSink(name="hs", slot_index=90)))
        p.link(tee, out)
        p.link(f"{demux.name}.src_1", p.add(TensorRepoSink(name="cs", slot_index=91)))
        p.run(timeout=600)
        from nnstreamer_tpu.elements.repo import GLOBAL_REPO

        GLOBAL_REPO.reset(90)
        GLOBAL_REPO.reset(91)
        if state["first"] is None or state["count"] < 2:
            raise RuntimeError(f"lstm pipeline delivered {state['count']} steps")
        return (state["count"] - 1) / (time.perf_counter() - state["first"])

    run(3)  # compile
    return run(steps)


# THE decode cell for configs 4c/4d (stepwise, continuous batching, and
# prefill all measure this exact model — one definition so their ratios
# can never silently compare different shapes)
DECODE_CELL = dict(t_max=128, d_in=64, n_out=16, d_model=256, n_heads=8,
                   n_layers=2)


def run_kvdecode_fps(steps, cell_kw=None):
    """Config #4c: transformer KV-cache decode cell through repo slots
    (models/transformer.py decode_step — the transformer-era analog of the
    reference's repo-LSTM, ``tests/nnstreamer_repo_lstm/runTest.sh:10-22``).
    The (L, 2, T_max, d) cache rides a repo slot as a device-resident jax
    Array — only the (n_out,) output row ever needs the host — so steps/sec
    measures the dispatch-bound recurrence with state kept on device."""
    import nnstreamer_tpu as nns
    from nnstreamer_tpu.buffer import SECOND, Frame
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.repo import GLOBAL_REPO, TensorRepoSink, TensorRepoSrc
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc
    from nnstreamer_tpu.models import transformer
    from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

    kw = {**DECODE_CELL, **(cell_kw or {})}
    t_max, d_model, n_layers = kw["t_max"], kw["d_model"], kw["n_layers"]
    d_in, n_out = kw["d_in"], kw["n_out"]
    model = transformer.build_decode_cell(**kw)
    cache_spec = TensorsSpec(tensors=(
        TensorSpec(dtype=np.float32, shape=(n_layers, 2, t_max, d_model)),))
    pos_spec = TensorsSpec(tensors=(TensorSpec(dtype=np.int32, shape=(1,)),))
    dur = SECOND // 30

    def run(n):
        data = [
            Frame.of(np.full((d_in,), 0.01 * i, np.float32), pts=i * dur,
                     duration=dur)
            for i in range(n)
        ]
        state = {"first": None, "count": 0}

        def cb(frame):
            state["count"] += 1
            if state["first"] is None:
                state["first"] = time.perf_counter()

        p = nns.Pipeline()
        x_src = p.add(DataSrc(name="x", data=data))
        cache_src = p.add(TensorRepoSrc(name="kv", slot_index=92,
                                        caps=cache_spec))
        pos_src = p.add(TensorRepoSrc(name="pos", slot_index=93,
                                      caps=pos_spec))
        mux = p.add(nns.make("tensor_mux", sync_mode="nosync"))
        filt = p.add(TensorFilter(framework="jax", model=model))
        demux = p.add(nns.make("tensor_demux"))
        out = p.add(TensorSink(callback=cb))
        p.link(x_src, f"{mux.name}.sink_0")
        p.link(cache_src, f"{mux.name}.sink_1")
        p.link(pos_src, f"{mux.name}.sink_2")
        p.link_chain(mux, filt, demux)
        p.link(f"{demux.name}.src_0", out)
        p.link(f"{demux.name}.src_1",
               p.add(TensorRepoSink(name="kvs", slot_index=92)))
        p.link(f"{demux.name}.src_2",
               p.add(TensorRepoSink(name="poss", slot_index=93)))
        p.run(timeout=600)
        GLOBAL_REPO.reset(92)
        GLOBAL_REPO.reset(93)
        if state["first"] is None or state["count"] < 2:
            raise RuntimeError(f"kv-decode pipeline delivered {state['count']} steps")
        return (state["count"] - 1) / (time.perf_counter() - state["first"])

    run(3)  # compile
    return run(steps)


def run_contbatch_fps(steps, capacity=8, cell_kw=None):
    """Config #4d: continuous batching (nnstreamer_tpu.serving) — the same
    transformer decode cell as config4c (``DECODE_CELL``), but
    ``capacity`` independent streams share ONE compiled step per tick.
    Aggregate steps/sec: the batch multiplies MXU arithmetic intensity at
    the same per-tick dispatch cost, which is the TPU-era serving answer
    to config4c's dispatch-bound single stream."""
    from nnstreamer_tpu.serving import ContinuousBatcher

    rng = np.random.default_rng(3)
    kw = {**DECODE_CELL, **(cell_kw or {})}
    d_in = kw["d_in"]
    with ContinuousBatcher(capacity=capacity, **kw) as eng:
        sessions = [eng.open_session(timeout=60) for _ in range(capacity)]
        warm = rng.standard_normal(d_in).astype(np.float32)
        for s in sessions:  # warmup tick pays the compile
            s.feed(warm)
        for s in sessions:
            s.get(timeout=600)
        feeds = [rng.standard_normal(d_in).astype(np.float32)
                 for _ in range(steps)]
        t0 = time.perf_counter()
        for x in feeds:  # everything queued up front: ticks coalesce fully
            for s in sessions:
                s.feed(x)
        for s in sessions:
            for _ in range(steps):
                s.get(timeout=600)
        dt = time.perf_counter() - t0
        ticks = eng.ticks
    return capacity * steps / dt, ticks


def measure_mfu(batches=None, image_size=224, model_name="mobilenet_v2"):
    """MFU sweep, in consistent units.  The model
    computes in **bfloat16** (its production configuration — ``entry()``
    uses the same) from a device-resident uint8 batch, against the bf16
    peak of this ``device_kind`` (``obs.util.DEVICE_PEAKS``; no MFU for a
    device outside the table).  XLA cost-analysis flops / measured step
    time / peak.

    Two models tell the two halves of the MFU story:
    - ``mobilenet_v2`` (the benched pipeline's model): depthwise convs do
      ~1 MAC per weight, so its MXU ceiling is intrinsically low — this
      sweep shows where the *flagship pipeline* sits.
    - ``vit_b16`` (ViT-Base/16): dense matmul-dominated — this sweep shows
      what the *framework + XLA path* achieves when the model shape is
      MXU-friendly, i.e. the framework overhead ceiling itself."""
    if batches is None:
        env_key = ("BENCH_MFU_BATCHES" if model_name == "mobilenet_v2"
                   else "BENCH_MFU_VIT_BATCHES")
        default = "8,32,128" if model_name == "mobilenet_v2" else "16,64"
        batches = tuple(
            int(b) for b in os.environ.get(env_key, default).split(",") if b
        )
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.models import mobilenet_v2, vit
    from nnstreamer_tpu.obs import util as obs_util
    from nnstreamer_tpu.obs.device import cost_info

    peak_tflops = obs_util.peak_tflops()
    rng = np.random.default_rng(0)
    out = {"peak_tflops": peak_tflops, "compute_dtype": "bfloat16",
           "model": model_name}
    def point(batch):
        if model_name == "vit_b16":
            model = vit.build(
                num_classes=1000, image_size=image_size, patch=16,
                d_model=768, n_heads=12, n_layers=12, batch=batch,
            )
        else:
            model = mobilenet_v2.build(
                num_classes=1001, image_size=image_size, batch=batch
            )
        fn = jax.jit(lambda x, m=model: m.apply(
            m.params, (x.astype(jnp.float32) - 127.5) / 127.5
        ))
        x = jax.device_put(
            rng.integers(0, 256, (batch, image_size, image_size, 3))
            .astype(np.uint8)
        )
        x.block_until_ready()
        compiled = fn.lower(x).compile()
        flops = cost_info(compiled).get("flops")
        t0 = time.perf_counter()
        compiled(x).block_until_ready()  # warm + step estimate
        est = time.perf_counter() - t0
        # ~2s per point, at most 20 iterations
        n = max(2, min(20, int(2.0 / max(est, 1e-4))))
        # Two trip counts from a FIXED bucket set (they become fori_loop
        # trip counts, i.e. part of the compiled program — a continuous n
        # would defeat the persistent compile cache across runs)
        n1 = max(b for b in (2, 5, 10) if b <= max(2, n))
        n2 = n1 * 2
        timing = "dispatch-loop"
        step = overhead_ms = None
        # Guard: if even one compiled call is this slow, the chained pair
        # below would eat minutes of budget — keep the cheap dispatch-loop
        # estimate and flag it.
        chain_ok = est * (n1 + n2) * 3 < float(
            os.environ.get("BENCH_MFU_POINT_CAP_S", "90"))
        if not chain_ok:
            timing = f"dispatch-loop(est {est*1e3:.0f} ms/call too slow " \
                     "for chained timing)"
        try:
            if not chain_ok:
                raise _Skipped("slow est")
            # Run the chain at TWO trip counts and DIFFERENCE them.  step =
            # (t(n2) - t(n1)) / (n2 - n1) cancels every per-call constant
            # exactly — dispatch latency, scalar readback, fixed loop setup;
            # the residual t(n1) - n1*step is reported as overhead_ms so
            # the per-call cost is visible instead of leaking into the step
            # time.  The scalar carry fed back into the input forces a data
            # dependency so XLA cannot collapse or reorder the iterations.
            from jax import lax

            def build_chain(trips):
                def chain(a):
                    def body(i, c):
                        y = model.apply(
                            model.params,
                            (a.astype(jnp.float32) - 127.5) / 127.5 + c,
                        )
                        return jnp.mean(y).astype(jnp.float32) * 1e-9
                    return lax.fori_loop(0, trips, body, jnp.float32(0.0))
                return jax.jit(chain).lower(x).compile()

            c1, c2 = build_chain(n1), build_chain(n2)
            jax.block_until_ready(c1(x))  # warm (compile outside timing)
            jax.block_until_ready(c2(x))
            t1s, t2s = [], []
            for _ in range(2):
                t0 = time.perf_counter()
                jax.block_until_ready(c1(x))
                t1s.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                jax.block_until_ready(c2(x))
                t2s.append(time.perf_counter() - t0)
            t1, t2 = min(t1s), min(t2s)
            if t2 > t1:
                step = (t2 - t1) / (n2 - n1)
                overhead_ms = round(max(0.0, t1 - n1 * step) * 1e3, 3)
                timing = f"chained-fori-diff(n={n1},{n2})"
            else:
                # differencing degenerate (noise floor): the larger chain's
                # per-trip time is the best upper bound we have
                step = t2 / n2
                timing = (f"chained-fori(n={n2}; diff degenerate "
                          f"t1={t1*1e3:.1f}>=t2={t2*1e3:.1f} ms)")
        except _Skipped:
            pass
        except Exception as exc:
            log(f"# mfu chained timing failed ({exc!r}); dispatch-loop")
        if step is None:
            t0 = time.perf_counter()
            for _ in range(n):
                res = compiled(x)
            res.block_until_ready()
            step = (time.perf_counter() - t0) / n
        mfu = (flops / step / (peak_tflops * 1e12)
               if flops and peak_tflops else None)
        row = {
            "batch": batch,
            "step_ms": round(step * 1e3, 3),
            "fps": round(batch / step, 1),
            "achieved_tflops": round(flops / step / 1e12, 3) if flops else None,
            "mfu": round(mfu, 4) if mfu else None,
            "timing": timing,
        }
        if overhead_ms is not None:
            row["per_call_overhead_ms"] = overhead_ms
        return row

    sweep = []
    for batch in batches:
        try:  # one failing batch point must not discard measured ones
            sweep.append(point(batch))
            log(f"# mfu batch={batch}: {sweep[-1]}")
        except Exception as exc:
            out[f"batch{batch}_error"] = repr(exc)[:200]
            log(f"# mfu batch={batch} failed: {exc!r}")
    out["sweep"] = sweep
    best = max((s for s in sweep if s.get("mfu")), key=lambda s: s["mfu"],
               default=None)
    if best:
        out["best_mfu"] = best["mfu"]
        out["best_batch"] = best["batch"]
    return out


def ladder_point(batch, dtype, ndev, image_size=224):
    """One MFU-ladder cell: MobileNet-v2 at ``batch`` in ``dtype``
    (fp32, or the static-scale full-int8 path) across ``ndev`` chips
    (batch-axis NamedSharding).  Returns the measured row; MFU is
    PER-CHIP (whole-program flops / ndev / chip peak) so every cell
    reads against the same BENCH_NOTES per-chip targets.  The int8 peak
    is 2× the table's bf16 peak (v5e spec: 393 TOP/s)."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.models import mobilenet_v2
    from nnstreamer_tpu.obs import util as obs_util
    from nnstreamer_tpu.obs.device import cost_info

    if dtype == "int8":
        model = mobilenet_v2.build_quantized(
            num_classes=1001, image_size=image_size, batch=batch,
            int8_convs=True, static_scales=True)
    else:
        model = mobilenet_v2.build(
            num_classes=1001, image_size=image_size, batch=batch,
            dtype=jnp.float32)

    def fwd(x):
        return model.apply(model.params,
                           (x.astype(jnp.float32) - 127.5) / 127.5)

    kwargs = {}
    sharding = None
    if ndev > 1:
        from nnstreamer_tpu.parallel.mesh import batch_sharding, make_mesh

        mesh = make_mesh((ndev,), ("dp",), devices=jax.devices()[:ndev])
        sharding = batch_sharding(mesh, 4)
        kwargs["in_shardings"] = (sharding,)
    jitted = jax.jit(fwd, **kwargs)
    rng = np.random.default_rng(0)
    x_host = rng.integers(
        0, 256, (batch, image_size, image_size, 3)).astype(np.uint8)
    compiled = jitted.lower(x_host).compile()
    info = cost_info(compiled)
    x = jax.device_put(x_host, sharding) if sharding is not None \
        else jax.device_put(x_host)
    jax.block_until_ready(x)
    t0 = time.perf_counter()
    jax.block_until_ready(jitted(x))  # warm + step estimate
    est = time.perf_counter() - t0
    n = max(2, min(20, int(1.5 / max(est, 1e-4))))

    def reps():
        t0 = time.perf_counter()
        for _ in range(n):
            out = jitted(x)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    profile_summary = None
    if os.environ.get("BENCH_LADDER_PROFILE") == "1":
        # BENCH_LADDER_PROFILE=1: wrap the timed reps in a deep-profiling
        # window (obs/profiler.py) so the banked cell carries the op-level
        # WHY next to its MFU sample.  A busy window (or any capture
        # failure) degrades to an unprofiled measurement — the ladder's
        # numbers must never depend on the profiler.
        try:
            from nnstreamer_tpu.obs.profiler import profiled_window

            with profiled_window(
                    label=f"ladder:b{batch}/{dtype}/x{ndev}",
                    trigger="bench") as holder:
                elapsed = reps()
            profile_summary = holder.get("summary")
        except Exception as exc:  # noqa: BLE001 — measure unprofiled
            log(f"# ladder profile capture skipped: {exc!r}")
            elapsed = reps()
    else:
        elapsed = reps()
    step = elapsed / n
    peak, peak_gb = obs_util.peak_tflops(), obs_util.peak_gbs()
    if peak is not None and dtype == "int8":
        peak *= 2.0
    # both peaks scale by ndev: MFU normalizes per chip and the ridge
    # point stays the single-chip ratio
    rl = obs_util.roofline(info.get("flops"), info.get("bytes"), step,
                           peak_tf=peak and peak * ndev,
                           peak_gb=peak_gb and peak_gb * ndev)
    row = {
        "step_ms": round(step * 1e3, 3),
        "fps": round(batch / step, 1),
        "per_chip_fps": round(batch / step / ndev, 1),
        "reps": n,
        "peak_tflops_per_chip": peak,
        "mfu": round(rl["mfu"], 5) if rl["mfu"] is not None else None,
        "roofline": rl["bound"],
    }
    if rl["achieved_tflops"] is not None:
        row["achieved_tflops"] = round(rl["achieved_tflops"], 3)
    if rl["achieved_gbs"] is not None:
        row["achieved_gbs"] = round(rl["achieved_gbs"], 2)
    if rl["intensity"] is not None:
        row["intensity"] = round(rl["intensity"], 2)
    if profile_summary is not None:
        row["op_table"] = {
            "capture_id": profile_summary.get("capture_id"),
            "parser": profile_summary.get("parser"),
            "device_planes": profile_summary.get("device_planes"),
            "ops": profile_summary.get("ops") or [],
            "op_categories": profile_summary.get("op_categories") or {},
        }
    return row


def measure_mfu_ladder(rep=None):
    """The ladder campaign as code: batch {8,32,128} × {fp32, int8} ×
    {1,8 chips} against the BENCH_NOTES per-chip MFU targets.  Every cell
    is measured in this run and reported with it; a cell that needs more
    chips than the host has records ``skipped: {reason: "no_mesh"}`` so
    the matrix stays complete, and a cell that raises records its
    ``error`` (the leg fails once the remaining cells have run)."""
    import jax

    out = {
        "config": LADDER_CONFIG,
        "targets": {str(b): t for b, t in LADDER_TARGETS.items()},
        "cells": {},
    }
    ndev_avail = len(jax.devices())
    for ndev in LADDER_MESHES:
        for dtype in LADDER_DTYPES:
            for batch in LADDER_BATCHES:
                label = f"b{batch}/{dtype}/x{ndev}"
                cell = {"batch": batch, "dtype": dtype, "mesh": ndev,
                        "target_mfu": LADDER_TARGETS[batch]}
                out["cells"][label] = cell
                if rep is not None and rep.remaining() < 0:
                    cell["skipped"] = {"reason": "budget"}
                    continue
                if ndev > ndev_avail:
                    cell["skipped"] = {"reason": "no_mesh",
                                       "devices_available": ndev_avail}
                    continue
                try:
                    cell.update(ladder_point(batch, dtype, ndev))
                    if cell.get("mfu") is not None:
                        cell["meets_target"] = (
                            cell["mfu"] >= LADDER_TARGETS[batch])
                    log(f"# mfu.ladder {label}: {cell}")
                except Exception as exc:
                    cell["error"] = repr(exc)[:200]
                    log(f"# mfu.ladder {label} failed: {exc!r}")
                    log(traceback.format_exc())
                if rep is not None:
                    rep.snapshot()  # each measured cell is evidence
    best = max((c for c in out["cells"].values() if c.get("mfu") is not None),
               key=lambda c: c["mfu"], default=None)
    if best is not None:
        out["best_mfu"] = best["mfu"]
        out["best_cell"] = f"b{best['batch']}/{best['dtype']}/x{best['mesh']}"
    return out


def run_baseline_leg(which: str, timeout: float = 1800.0):
    """One CPU baseline config in an isolated subprocess (tools/
    bench_baselines.py): the child runs tflite/torch on the CPU and never
    needs the chip this process holds; thread counts are pinned and
    recorded."""
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "bench_baselines.py")
    env = dict(os.environ)
    env.setdefault("BENCH_BASELINE_FRAMES", "200")
    out = subprocess.run(
        [sys.executable, script, which],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    for line in reversed(out.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            leg = json.loads(line)
            leg["measured_at"] = time.strftime("%Y-%m-%d %H:%M:%S")
            return leg
    raise RuntimeError(
        f"baseline {which} produced no JSON (rc={out.returncode}): "
        f"{out.stderr.strip()[-300:]}"
    )


def measure_frame_breakdown(image_u8, n=None):
    """Where the per-frame time goes for config #1: wire transfer, device
    compute, jit dispatch, and framework overhead measured separately."""
    if n is None:
        n = int(os.environ.get("BENCH_BREAKDOWN_FRAMES", "100"))
    if n <= 0:
        return {"skipped": "0 frames"}
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.models import mobilenet_v2

    model = mobilenet_v2.build(num_classes=1001, image_size=224)
    flat = np.ascontiguousarray(image_u8).reshape(-1)
    res = {}

    fn = jax.jit(lambda x: model.apply(
        model.params,
        ((x.astype(jnp.float32) - 127.5) / 127.5).reshape(1, 224, 224, 3),
    ))
    fn(flat).block_until_ready()

    # 1) sustained flat wire transfer (enqueue all, drain all)
    frames = [flat.copy() for _ in range(n)]
    t0 = time.perf_counter()
    ds = [jax.device_put(f) for f in frames]
    for d in ds:
        d.block_until_ready()
    res["wire_transfer_ms"] = round((time.perf_counter() - t0) / n * 1e3, 3)

    # 2) device-resident compute chain (dispatch+execute, overlapped)
    t0 = time.perf_counter()
    for d in ds:
        out = fn(d)
    out.block_until_ready()
    res["device_compute_ms"] = round((time.perf_counter() - t0) / n * 1e3, 3)

    # 3) full invoke chain from host arrays (transfer + compute interleaved)
    t0 = time.perf_counter()
    for f in frames:
        out = fn(f)
    out.block_until_ready()
    res["host_invoke_chain_ms"] = round((time.perf_counter() - t0) / n * 1e3, 3)

    # 3b) overlapped transfer+dispatch (the tensor_upload+queue pattern):
    # a producer thread device_puts frame N+1 while this thread dispatches
    # frame N — the achievable pipeline rate is ~max(transfer, dispatch),
    # which this measures directly (vs 3's serial transfer+dispatch sum)
    import queue as _q
    import threading as _t

    hand = _q.Queue(maxsize=4)

    def producer():
        for f in frames:
            hand.put(jax.device_put(f))
        hand.put(None)

    th = _t.Thread(target=producer)
    t0 = time.perf_counter()
    th.start()
    out = None
    while True:
        d = hand.get()
        if d is None:
            break
        out = fn(d)
    if out is not None:
        out.block_until_ready()
    th.join()
    res["overlapped_chain_ms"] = round((time.perf_counter() - t0) / n * 1e3, 3)

    # 4) dispatch-only cost (client-side enqueue)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(ds[0])
    res["dispatch_only_ms"] = round((time.perf_counter() - t0) / n * 1e3, 3)
    out.block_until_ready()

    # 5) p50/p99 per-frame LATENCY (BASELINE.md's second metric): one frame
    # submitted and synced at a time — the latency-floor view, vs the
    # overlapped-throughput view above.  Includes the host→device transfer
    # and the full device round trip.
    lats = []
    for f in frames:
        t0 = time.perf_counter()
        fn(f).block_until_ready()
        lats.append((time.perf_counter() - t0) * 1e3)
    lats.sort()
    res["latency_samples"] = len(lats)
    res["latency_p50_ms"] = round(lats[len(lats) // 2], 3)
    res["latency_p99_ms"] = round(lats[min(len(lats) - 1, int(len(lats) * 0.99))], 3)
    return res


def measure_pallas():
    """Pallas int8_matmul vs plain XLA, compiled by Mosaic on the chip."""
    import jax
    import jax.numpy as jnp

    res = {}
    rng = np.random.default_rng(0)

    def timeit(fn, *args, n=50):
        fn(*args).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        out.block_until_ready()
        return (time.perf_counter() - t0) / n

    # fused_arith is not benched: the default acceleration path is XLA's
    # automatic elementwise fusion (graph/optimize.py + jit); the kernel
    # stays behind ``tensor_transform acceleration=pallas`` (chip_smoke.py
    # compiles it) until an on-chip A/B decides it (ROADMAP D4).
    from nnstreamer_tpu.ops.pallas_kernels import int8_matmul
    from nnstreamer_tpu.ops.quant import quantize_activations, quantize_weight

    a = rng.standard_normal((256, 1280)).astype(np.float32)
    w = rng.standard_normal((1280, 1024)).astype(np.float32)
    b = np.zeros(1024, np.float32)
    qw = quantize_weight(jnp.asarray(w), axis=-1)
    aq, ascale = quantize_activations(jnp.asarray(a))
    i8 = jax.jit(
        lambda q, s: int8_matmul(q, qw.q, s, qw.scale.reshape(1, -1), b)
    )
    bf = jax.jit(
        lambda x: (
            x.astype(jnp.bfloat16) @ jnp.asarray(w).astype(jnp.bfloat16)
        ).astype(jnp.float32)
    )
    t_i8, t_bf = timeit(i8, aq, ascale), timeit(bf, jnp.asarray(a))
    res["int8_matmul_ms"] = round(t_i8 * 1e3, 4)
    res["bf16_matmul_ms"] = round(t_bf * 1e3, 4)
    res["int8_matmul_speedup"] = round(t_bf / t_i8, 3)

    # On-chip tile autotune: the bound is weight HBM traffic, which halves
    # vs bf16; the right tile split depends on the part, so search it on
    # the hardware the bench runs on and report the best alongside the
    # default.
    best = None
    for bm in (None, 128):
        for bn in (128, 256, 512, 1024):
            try:
                f = jax.jit(
                    lambda q, s, bm=bm, bn=bn: int8_matmul(
                        q, qw.q, s, qw.scale.reshape(1, -1), b,
                        block_m=bm, block_n=bn,
                    )
                )
                t = timeit(f, aq, ascale, n=30)
                if best is None or t < best[0]:
                    best = (t, bm, bn)
            except Exception:
                continue  # illegal tile for this part: skip
    if best is not None:
        res["int8_autotune_ms"] = round(best[0] * 1e3, 4)
        res["int8_autotune_block"] = f"m={best[1]},n={best[2]}"
        res["int8_autotune_speedup"] = round(t_bf / best[0], 3)
        # persist the winner: keyed by (kernel, shapes, dtype,
        # platform) under [compile] cache_dir, so int8_matmul's
        # default blocks pick it up in every later process
        try:
            from nnstreamer_tpu.ops import autotune as _autotune

            if _autotune.record(
                _autotune.INT8_KERNEL,
                _autotune.make_key(((256, 1280), (1280, 1024)), "int8"),
                {"block_m": best[1], "block_n": best[2]},
                metric_ms=best[0] * 1e3,
            ):
                res["int8_autotune_persisted"] = True
        except Exception as exc:
            res["int8_autotune_persist_error"] = repr(exc)[:160]
    return res


# ------------------------------------------------------------------- main


def _flat_items(prefix, v, out):
    if isinstance(v, dict):
        for k2, v2 in v.items():
            _flat_items(f"{prefix}.{k2}" if prefix else str(k2), v2, out)
    elif isinstance(v, list):
        out.append((prefix, json.dumps(v)))
    else:
        out.append((prefix, v))


def write_notes(results, device, errors):
    import multiprocessing

    lines = [
        "# BENCH NOTES",
        "",
        f"- date: {time.strftime('%Y-%m-%d %H:%M:%S')}",
        f"- device: **{device['platform']}** `{device['kind']}` "
        f"× {device['count']} (as jax reports it; every row below was "
        "measured in this run on this device unless stamped cpu)",
        f"- host CPUs: {multiprocessing.cpu_count()}",
        "- metric: frames/sec/chip through the tensor_filter invoke path",
        "- CPU baselines run in **isolated subprocesses** (tflite/torch on "
        "the CPU only — they never need the chip this process holds; "
        "threads pinned to the host CPU count, frame counts recorded per "
        "leg; the per-leg `cpu_count`/`threads` fields record the "
        "environment the number came from).",
        "- config4 (per-step repo-slot recurrence, 64-wide cell) is "
        "**dispatch-latency-bound by design**: every step is one tiny "
        "device round trip, which a host CPU does in-process — the honest "
        "expectation is that tflite-CPU WINS this config on "
        "latency-per-step.  The TPU-native recurrence for throughput is "
        "config4b (tensor_aggregator windows → one lax.scan program).",
        "- **MFU target & ceiling**: MobileNet-v2 at 224² is ~0.6 "
        "GFLOP/frame — a *small* model, so streaming MFU is bounded by "
        "dispatch+transfer, not the MXU.  The stated targets on a v5e "
        "chip: batch 8 (latency config) ≥1% MFU, batch 32 ≥3%, batch 128 "
        "(throughput config) ≥10%.  The depthwise convs cap the ceiling: "
        "they are bandwidth-bound (arithmetic intensity <10 flops/byte); "
        "~15-20% is the realistic asymptote for this architecture on v5e. "
        "Interpret the `mfu.sweep` rows against these targets.",
        "",
        "| measurement | value | measured on |",
        "|---|---|---|",
    ]
    flat = []
    for k, v in results.items():
        _flat_items(k, v, flat)

    def stamp(key: str) -> str:
        """Device provenance per row: a CPU baseline number must never be
        mistakable for a chip result."""
        if key.startswith("baselines.") or key == "tflite_cpu_fps":
            return "cpu (isolated subprocess)"
        if key.startswith("vs_baseline_per_config."):
            return f"{device['kind']} / cpu"
        return device["kind"]

    for k, v in flat:
        lines.append(f"| {k} | {v} | {stamp(k)} |")

    # Per-row MFU interpretation against the stated targets
    sweep = (results.get("mfu") or {}).get("sweep") or []
    if sweep:
        lines += ["", "### MFU sweep interpretation", ""]
        for row in sweep:
            mfu, b = row.get("mfu"), row.get("batch")
            if mfu is None:
                lines.append(f"- batch {b}: no cost-analysis flops or no "
                             "peak for this device_kind — step time only.")
                continue
            target = 0.10 if b >= 128 else (0.03 if b >= 32 else 0.01)
            verdict = "MEETS" if mfu >= target else "BELOW"
            lines.append(
                f"- batch {b}: {mfu:.2%} MFU at {row.get('step_ms')} ms/step "
                f"({row.get('fps')} fps equivalent) — {verdict} the "
                f"{target:.0%} target for this batch size; "
                + ("dispatch/transfer-bound regime, batch further to climb "
                   "the curve." if mfu < target else
                   "within the depthwise-conv-limited envelope for "
                   "MobileNet on v5e.")
            )
    if errors:
        lines += ["", "## Errors", ""]
        lines += [f"- `{e}`" for e in errors]
    path = os.environ.get("BENCH_NOTES_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_NOTES.md")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


BUDGET_DEFAULT_S = 480.0


class Reporter:
    """Incremental evidence writer.

    After every leg the current view of the whole run — ratios, headline
    variant — is (a) written atomically to ``BENCH_PARTIAL.json`` and (b)
    printed to stdout as a complete JSON snapshot line marked ``"partial":
    true``.  Killing the process at ANY moment therefore leaves the
    previous snapshot as valid, parseable evidence.  ``finalize()`` is
    idempotent and reachable from the normal end of :func:`main`, the
    SIGTERM/SIGINT handlers, and the hard watchdog thread (which
    ``os._exit``s even when the main thread is stuck in a C call)."""

    def __init__(self, budget_s: float):
        self.t_start = time.perf_counter()
        self.budget_s = budget_s
        self.results = {}
        self.errors = []        # everything worth telling: skips + failures
        self.failed_legs = []   # labels of legs that RAISED (exit code 1)
        self.baselines = {}
        self.device = None      # require_tpu()'s dict, once checked
        self.current_leg = "startup"
        self.last_out = None
        self.done = False
        self._final_emitted = False
        # RLock: a SIGTERM can land while the main thread holds the lock
        # inside snapshot(); the handler runs on the same thread and calls
        # finalize() — a plain Lock would deadlock the very path built to
        # guarantee output (review r5)
        self._lock = threading.RLock()
        self.partial_path = os.environ.get("BENCH_PARTIAL_PATH") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_PARTIAL.json")

    # -- budget ------------------------------------------------------------

    def spent(self) -> float:
        return time.perf_counter() - self.t_start

    def remaining(self) -> float:
        return self.budget_s - self.spent()

    def over_budget(self, label: str) -> bool:
        if self.remaining() < 0:
            self.errors.append(
                f"{label}: skipped (BENCH_BUDGET_S={self.budget_s:g} spent)")
            return True
        return False

    # -- result assembly ---------------------------------------------------

    def build_out(self, partial: bool = False) -> dict:
        """The final-JSON dict, recomputed from whatever has been measured
        so far: per-config ratios and the best-config1-variant headline.
        Safe to call repeatedly."""
        results, baselines = self.results, self.baselines
        results["baselines"] = baselines

        def ratio(tpu_key, base_key, base_field="fps"):
            tpu_v = results.get(tpu_key)
            base = baselines.get(base_key) or {}
            base_v = base.get(base_field) if base.get("ok") else None
            if tpu_v and base_v:
                return round(tpu_v / base_v, 2)
            return None

        vs = {
            "config1": ratio("config1_stream_fps", "config1"),
            "config1_quant": ratio("config1_quant_fps", "config1_quant"),
            "config1_quant_upload": ratio("config1_quant_upload_fps",
                                          "config1_quant"),
            "config1_quant_dynbatch": ratio("config1_quant_dynbatch_fps",
                                            "config1_quant"),
            "config2": ratio("config2_ssd_fps", "config2"),
            "config2_upload": ratio("config2_ssd_upload_fps", "config2"),
            "config2c": ratio("config2c_cascade_fps", "config2c"),
            "config2c_upload": ratio("config2c_cascade_upload_fps", "config2c"),
            "config2c_dynbatch": ratio("config2c_cascade_dynbatch_fps",
                                       "config2c"),
            "config3": ratio("config3_pose_fps", "config3"),
            "config3_upload": ratio("config3_pose_upload_fps", "config3"),
            "config3_dynbatch": ratio("config3_pose_dynbatch_fps", "config3"),
            "config4": ratio("config4_lstm_steps_per_sec", "config4",
                             "steps_per_sec"),
            "config4b": ratio("config4b_seq_windows_per_sec", "config4b",
                              "windows_per_sec"),
            "config5": ratio("config5_mux_batched_fps", "config5"),
            "config5_upload": ratio("config5_mux_upload_fps", "config5"),
        }
        results["vs_baseline_per_config"] = vs
        cpu_fps = (baselines.get("config1") or {}).get("fps") \
            if (baselines.get("config1") or {}).get("ok") else None
        if cpu_fps:
            results["tflite_cpu_fps"] = round(cpu_fps, 2)

        # Headline = the best config1 variant (plain stream / upload-
        # overlap / dynbatch).  All are the SAME streaming pipeline +
        # semantics — upload overlaps the h2d transfer with dispatch,
        # dynbatch coalesces a pile-up adaptively; the reference pipelines
        # the same way with queues.
        variants = {
            "stream": results.get("config1_stream_fps"),
            "upload": results.get("config1_upload_fps"),
            "dynbatch": results.get("config1_dynbatch_fps"),
            "dynbatch+upload": results.get("config1_dynupload_fps"),
        }
        best_variant, best_fps = None, None
        for name, v in variants.items():
            if v is not None and (best_fps is None or v > best_fps):
                best_variant, best_fps = name, v
        vs_baseline = vs["config1"]
        tpu_fps = None
        if best_fps is not None:
            tpu_fps = best_fps
            results["headline_variant"] = best_variant
            if cpu_fps:
                # keep vs['config1'] the matched stream-vs-stream ratio; the
                # best-of-variants headline gets its own labeled key
                vs["config1_best"] = round(best_fps / cpu_fps, 2)
                vs_baseline = vs["config1_best"]

        variant_note = (
            f", best variant: {results['headline_variant']}"
            if results.get("headline_variant") else ""
        )
        out = {
            "metric": "mobilenet_v2_224 image-labeling pipeline throughput "
                      f"(tensor_filter invoke, streaming{variant_note})",
            "value": round(tpu_fps, 2) if tpu_fps else None,
            "unit": "frames/sec/chip",
            "vs_baseline": vs_baseline,
            "device": self.device,
            "extra": results,
        }
        if self.errors:
            out["error"] = "; ".join(self.errors)
        if self.failed_legs:
            out["failed_legs"] = list(self.failed_legs)
        if partial:
            out["partial"] = True
            out["snapshot_after"] = self.current_leg
            out["budget"] = {"spent_s": round(self.spent(), 1),
                            "budget_s": self.budget_s}
        return out

    def snapshot(self) -> None:
        """Persist + print the current state; never raises."""
        try:
            with self._lock:
                if self._final_emitted:
                    return
                out = self.build_out(partial=True)
                self.last_out = out
                tmp = self.partial_path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(out, f)
                os.replace(tmp, self.partial_path)
                print(json.dumps(out), flush=True)
        except Exception as exc:  # noqa: BLE001 — evidence plumbing only
            log(f"# snapshot failed: {exc!r}")

    def finalize(self, async_ctx: bool = False):
        """Emit the final JSON exactly once (notes + cache + stdout).

        ``async_ctx=True`` (signal handler / watchdog thread) reuses the
        last CONSISTENT snapshot instead of recomputing from a results dict
        the main thread may be mutating mid-leg.  The async path acquires
        with a timeout: if the lock is somehow held forever (a thread died
        mid-snapshot), emitting slightly-racy JSON beats hanging the
        process the watchdog exists to end."""
        got = self._lock.acquire(timeout=5.0) if async_ctx \
            else self._lock.acquire()
        try:
            if self._final_emitted:
                return None
            self._final_emitted = True
            if async_ctx:
                out = dict(self.last_out) if self.last_out else {
                    "metric": "mobilenet_v2_224 image-labeling pipeline "
                              "throughput",
                    "value": None, "unit": "frames/sec/chip",
                    "vs_baseline": None,
                    "device": self.device,
                }
                out.pop("partial", None)
                out.pop("snapshot_after", None)
                note = (f"run interrupted during leg {self.current_leg!r} "
                        f"after {self.spent():.0f}s; result is the last "
                        "completed snapshot")
                out["error"] = (f"{out['error']}; {note}"
                                if out.get("error") else note)
            else:
                out = self.build_out(partial=False)
        finally:
            if got:
                self._lock.release()
        if self.device is not None:
            try:
                write_notes(self.results, self.device, self.errors)
            except Exception as exc:
                log(f"# notes write failed: {exc!r}")
        try:
            tmp = self.partial_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(out, f)
            os.replace(tmp, self.partial_path)
        except Exception as exc:
            log(f"# partial-file finalize failed: {exc!r}")
        print(json.dumps(out), flush=True)
        return out


def install_signal_handlers(reporter: Reporter) -> None:
    """SIGTERM/SIGINT → finalize + exit 128+signum: an external
    ``timeout`` kill still yields the last snapshot as the final JSON line,
    but never a zero exit code — an interrupted run is not a run."""
    import signal

    def handler(signum, frame):
        del frame
        log(f"# signal {signum} during {reporter.current_leg!r}; "
            "emitting final snapshot")
        reporter.finalize(async_ctx=True)
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(128 + signum)

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, handler)
        except (ValueError, OSError) as exc:
            log(f"# cannot install handler for signal {sig}: {exc!r}")


def arm_watchdog(reporter: Reporter, hard_s: float) -> threading.Thread:
    """A daemon thread that force-finishes the run at ``hard_s`` seconds
    with exit code 124 (``timeout``'s convention): signal handlers only
    run between Python bytecodes, so a call stuck inside C would otherwise
    hold the process — and the chip — until an outside kill; ``os._exit``
    from this thread works regardless."""

    def run():
        while not reporter.done:
            if reporter.spent() > hard_s:
                log(f"# WATCHDOG: {hard_s:g}s hard limit hit during "
                    f"{reporter.current_leg!r}; emitting final snapshot")
                reporter.finalize(async_ctx=True)
                sys.stdout.flush()
                sys.stderr.flush()
                os._exit(124)
            time.sleep(1.0)

    t = threading.Thread(target=run, daemon=True, name="bench-watchdog")
    t.start()
    return t


def load_reused_baselines(rep: Reporter) -> None:
    """Adopt the isolated-subprocess CPU baselines of a prior run's JSON
    (``BENCH_BASELINES_FROM=<path>``; same host shape, bounded age) so
    chip time is spent on chip legs.  Unset (the default) measures them
    fresh.  Reused rows keep their original ``measured_at`` stamp and say
    where they came from."""
    reuse_path = os.environ.get("BENCH_BASELINES_FROM")
    if not reuse_path:
        return
    baselines, errors = rep.baselines, rep.errors
    try:
        with open(reuse_path) as f:
            prior = json.load(f)
        prior_b = ((prior.get("extra") or {}).get("baselines")
                   or prior.get("baselines") or {})
        host_cpus = os.cpu_count()
        max_age_s = float(os.environ.get(
            "BENCH_BASELINE_MAX_AGE_S", str(7 * 24 * 3600)))
        for which, leg in prior_b.items():
            if not (isinstance(leg, dict) and leg.get("ok")):
                continue
            if leg.get("cpu_count") != host_cpus:
                # a baseline from a different host shape would silently
                # distort every ratio — refuse it and measure fresh
                errors.append(
                    f"baseline {which} from {reuse_path} ignored: "
                    f"measured on a {leg.get('cpu_count')}-CPU host, "
                    f"this host has {host_cpus}")
                continue
            # reuse can chain run→cache→run indefinitely: bound the age so
            # rows measured long ago get re-measured, and keep the ORIGINAL
            # measurement stamp through every hop
            measured_at = leg.get("measured_at")
            if not measured_at:
                errors.append(
                    f"baseline {which} from {reuse_path} ignored: no "
                    "measured_at provenance; re-measuring")
                continue
            try:
                age = time.time() - time.mktime(
                    time.strptime(measured_at, "%Y-%m-%d %H:%M:%S"))
            except ValueError:
                age = max_age_s + 1  # unparseable stamp: re-measure
            if age > max_age_s:
                errors.append(
                    f"baseline {which} from {reuse_path} ignored: "
                    f"measured {measured_at}, older than "
                    f"{max_age_s:g}s; re-measuring")
                continue
            baselines[which] = dict(
                leg,
                reused_from=leg.get("reused_from")
                or os.path.basename(reuse_path))
        log(f"# baselines reused from {reuse_path}: {sorted(baselines)}")
        if not baselines:
            errors.append(
                f"baselines from {reuse_path}: no usable rows; "
                "measuring fresh")
    except Exception as exc:
        errors.append(f"baseline reuse load failed: {exc!r}"[:200])


def main(standalone=False):
    """Run the legs; returns ``(final JSON dict, exit code)``."""
    budget_s = float(os.environ.get("BENCH_BUDGET_S", str(BUDGET_DEFAULT_S)))
    rep = Reporter(budget_s)
    if standalone:
        install_signal_handlers(rep)
        grace = float(os.environ.get("BENCH_WATCHDOG_GRACE_S", "120"))
        arm_watchdog(rep, budget_s + grace)
    rep.device = require_tpu()
    errors, results = rep.errors, rep.results

    from nnstreamer_tpu import native
    from nnstreamer_tpu.backends.exec_cache import ensure_compile_cache
    from nnstreamer_tpu.parallel.mesh import dispatch_mesh_devices

    # $JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
    cache_dir = ensure_compile_cache()

    # raises when [common] native_runtime is on but the library cannot
    # build/load: the host dispatch layer never changes silently
    results["queue_backend"] = native.queue_backend()
    results["compile_cache_dir"] = cache_dir
    log(f"# device: {rep.device}; queue backend: "
        f"{results['queue_backend']}; compile cache: {cache_dir}")
    rep.snapshot()

    mesh_ndev = dispatch_mesh_devices()
    if mesh_ndev > 1:
        # --mesh / NNSTPU_MESH: every jax leg below dispatches batch-axis
        # sharded over this many chips; per-shard batch = batch / chips
        results["mesh_devices"] = mesh_ndev
        log(f"# mesh-sharded dispatch: {mesh_ndev} chips "
            f"(NNSTPU_MESH={os.environ.get('NNSTPU_MESH', '')!r})")

    # Baselines first (reused rows cost nothing) so every snapshot from the
    # first leg on carries real vs_baseline ratios.
    load_reused_baselines(rep)
    rep.snapshot()

    rng = np.random.default_rng(0)
    image_u8 = rng.integers(0, 256, (224, 224, 3)).astype(np.uint8)

    # ---- legs, in VALUE order: config1 variants (the headline) first, then
    # config5 (the north-star architecture), quant, everything else.  Each
    # leg is a closure run by the budget-checking loop at the bottom; a
    # snapshot lands after every one.

    share = {"model": None}

    def get_model():
        if share["model"] is None:
            from nnstreamer_tpu.models import mobilenet_v2

            share["model"] = mobilenet_v2.build(num_classes=1001,
                                                image_size=224)
        return share["model"]

    # -- config #1: streaming image-labeling pipeline (jax backend) --------
    def leg_config1_stream():
        n_tpu = int(os.environ.get("BENCH_FRAMES", "400"))
        if n_tpu <= 0:
            raise _Skipped("skipped (0 frames)")
        fps = run_pipeline_fps("jax", get_model(),
                               [image_u8.copy() for _ in range(n_tpu)])
        results["config1_stream_fps"] = round(fps, 2)
        results["config1_frames"] = n_tpu
        log(f"# config1 jax streaming fps: {fps:.2f}")

    # -- config #1u: same pipeline with tensor_upload + queue — transfer of
    #    frame N+1 (source thread) overlaps dispatch of frame N (worker)
    def leg_config1_upload():
        n_u = int(os.environ.get("BENCH_UPLOAD_FRAMES",
                                 os.environ.get("BENCH_FRAMES", "400")))
        if n_u <= 0:
            raise _Skipped("skipped (0 frames)")
        u_fps = run_pipeline_fps(
            "jax", get_model(), [image_u8.copy() for _ in range(n_u)],
            upload=True,
        )
        results["config1_upload_fps"] = round(u_fps, 2)
        results["config1_upload_frames"] = n_u
        log(f"# config1 upload-overlap fps: {u_fps:.2f}")

    # -- config #1d: adaptive micro-batching (tensor_dynbatch) -------------
    def leg_config1_dynbatch():
        n_d = int(os.environ.get("BENCH_DYNBATCH_FRAMES",
                                 os.environ.get("BENCH_FRAMES", "400")))
        if n_d <= 0:
            raise _Skipped("skipped (0 frames)")
        maxb = dynbatch_max()
        d_fps, d_batches, d_frames = run_dynbatch_fps(
            [image_u8.copy() for _ in range(n_d)], max_batch=maxb
        )
        results["config1_dynbatch_fps"] = round(d_fps, 2)
        results["config1_dynbatch_max"] = maxb
        results["config1_dynbatch_invokes"] = d_batches
        results["config1_dynbatch_frames"] = d_frames
        if mesh_ndev > 1:
            # mesh lane: max_batch is PER SHARD — one invoke spans up to
            # maxb × chips rows across the whole mesh
            results["config1_dynbatch_per_shard"] = maxb
            results["config1_dynbatch_mesh_span"] = maxb * mesh_ndev
        log(f"# config1 dynbatch fps: {d_fps:.2f} "
            f"({d_batches} invokes / {d_frames} frames"
            + (f", {mesh_ndev} chips × {maxb}/shard" if mesh_ndev > 1
               else "") + ")")

    # -- config #1du: dynbatch + upload overlap — coalesced batches cross
    #    the wire in the dynbatch worker while the queue worker dispatches
    #    the previous batch (amortization AND overlap stacked)
    def leg_config1_dynupload():
        n_du = int(os.environ.get("BENCH_DYNBATCH_FRAMES",
                                  os.environ.get("BENCH_FRAMES", "400")))
        if n_du <= 0:
            raise _Skipped("skipped (0 frames)")
        maxb = dynbatch_max()
        du_fps, du_batches, du_frames = run_dynbatch_fps(
            [image_u8.copy() for _ in range(n_du)], upload=True,
            max_batch=maxb,
        )
        results["config1_dynupload_fps"] = round(du_fps, 2)
        results["config1_dynupload_max"] = maxb
        results["config1_dynupload_invokes"] = du_batches
        results["config1_dynupload_frames"] = du_frames
        log(f"# config1 dynbatch+upload fps: {du_fps:.2f} "
            f"({du_batches} invokes / {du_frames} frames)")

    # -- config #1q: uint8-quantized flagship — full-int8 path: every
    #    ungrouped conv runs int8 x int8 → int32 on the MXU with STATIC
    #    activation scales calibrated at build time (round-5: the per-sample
    #    dynamic scales cost extra passes and lost to float on chip; the
    #    reference's uint8 flagship uses fixed scales the same way)
    def leg_config1_quant():
        from nnstreamer_tpu.models import mobilenet_v2

        n_q = int(os.environ.get("BENCH_QUANT_FRAMES", "200"))
        if n_q <= 0:
            raise _Skipped("skipped (0 frames)")
        quant_model = mobilenet_v2.build_quantized(
            num_classes=1001, image_size=224, int8_convs=True,
            static_scales=True)
        q_fps = run_pipeline_fps(
            "jax", quant_model, [image_u8.copy() for _ in range(n_q)]
        )
        results["config1_quant_fps"] = round(q_fps, 2)
        results["config1_quant_frames"] = n_q
        log(f"# config1 quantized fps: {q_fps:.2f}")
        rep.snapshot()
        # upload-overlap variant: int8 gets the same transfer/dispatch
        # overlap as the float headline — the on-chip quant-vs-float
        # comparison must not be handicapped by serial transfers
        qu_fps = run_pipeline_fps(
            "jax", quant_model, [image_u8.copy() for _ in range(n_q)],
            upload=True,
        )
        results["config1_quant_upload_fps"] = round(qu_fps, 2)
        log(f"# config1 quantized upload fps: {qu_fps:.2f}")
        rep.snapshot()
        # dynbatch variant: int8 + amortization stacked — the float
        # headline's best variant is usually dynbatch, so the quant-vs-
        # float comparison needs the same machinery on both sides
        if not rep.over_budget("config1 quant dynbatch variant"):
            maxb = dynbatch_max()
            qd_fps, qd_batches, _ = run_dynbatch_fps(
                [image_u8.copy() for _ in range(n_q)], max_batch=maxb,
                poly_model=poly_wire_model(quant_model, 224),
            )
            results["config1_quant_dynbatch_fps"] = round(qd_fps, 2)
            results["config1_quant_dynbatch_max"] = maxb
            results["config1_quant_dynbatch_invokes"] = qd_batches
            results["config1_quant_dynbatch_frames"] = n_q
            log(f"# config1 quantized dynbatch fps: {qd_fps:.2f} "
                f"({qd_batches} invokes / {n_q} frames, cap {maxb})")

    # -- config #2: SSD-MobileNet bounding-box pipeline --------------------
    # fused on-device decode head (lax.top_k inside the model's program) +
    # the fused-ssd decoder: the benched pipeline includes the FULL
    # detection path (decode + overlay)
    def leg_config2():
        from nnstreamer_tpu.models import ssd_mobilenet

        n_ssd = int(os.environ.get("BENCH_SSD_FRAMES", "100"))
        if n_ssd <= 0:
            raise _Skipped("skipped (0 frames)")
        ssd = ssd_mobilenet.build(num_labels=91, image_size=300,
                                  fused_decode=100)
        img300 = rng.integers(0, 256, (300, 300, 3)).astype(np.uint8)
        ssd_fps = run_pipeline_fps(
            "jax", ssd, [img300.copy() for _ in range(n_ssd)],
            decoder=("bounding_boxes", {
                "option1": "fused-ssd", "option4": "300:300",
                "option5": "300:300",
            }),
        )
        results["config2_ssd_fps"] = round(ssd_fps, 2)
        results["config2_frames"] = n_ssd
        log(f"# config2 ssd fps: {ssd_fps:.2f}")
        # upload-overlap variant (same pipeline + tensor_upload/queue, the
        # discipline that lifted config1): transfer of frame N+1 overlaps
        # dispatch of frame N
        ssd_u_fps = run_pipeline_fps(
            "jax", ssd, [img300.copy() for _ in range(n_ssd)],
            decoder=("bounding_boxes", {
                "option1": "fused-ssd", "option4": "300:300",
                "option5": "300:300",
            }),
            upload=True,
        )
        results["config2_ssd_upload_fps"] = round(ssd_u_fps, 2)
        log(f"# config2 ssd upload fps: {ssd_u_fps:.2f}")

    # -- config #3: PoseNet pose-estimation pipeline -----------------------
    # fused on-device keypoint decode (heatmap argmax in the model's XLA
    # program) + skeleton overlay: the full pose path, both legs symmetric
    def leg_config3():
        from nnstreamer_tpu.models import posenet

        n_pose = int(os.environ.get("BENCH_POSE_FRAMES", "100"))
        if n_pose <= 0:
            raise _Skipped("skipped (0 frames)")
        pose = posenet.build(image_size=224, fused_decode=True)
        grid = posenet.grid_size(224)
        pose_fps = run_pipeline_fps(
            "jax", pose, [image_u8.copy() for _ in range(n_pose)],
            decoder=("pose_estimation", {
                "option1": "224:224", "option2": f"{grid}:{grid}",
            }),
        )
        results["config3_pose_fps"] = round(pose_fps, 2)
        results["config3_frames"] = n_pose
        log(f"# config3 pose fps: {pose_fps:.2f}")
        pose_u_fps = run_pipeline_fps(
            "jax", pose, [image_u8.copy() for _ in range(n_pose)],
            decoder=("pose_estimation", {
                "option1": "224:224", "option2": f"{grid}:{grid}",
            }),
            upload=True,
        )
        results["config3_pose_upload_fps"] = round(pose_u_fps, 2)
        log(f"# config3 pose upload fps: {pose_u_fps:.2f}")
        rep.snapshot()
        # dynbatch variant: piled-up frames coalesce into bucketed
        # batched invokes of the fused pose program (decode_keypoints is
        # batch-polymorphic), overlay decoding downstream per frame
        if not rep.over_budget("config3 dynbatch variant"):
            pose_poly = poly_wire_model(pose, 224)
            maxb = dynbatch_max()
            pd_fps, pd_batches, _ = run_dynbatch_fps(
                [image_u8.copy() for _ in range(n_pose)], max_batch=maxb,
                poly_model=pose_poly,
                decoder=("pose_estimation", {
                    "option1": "224:224", "option2": f"{grid}:{grid}",
                }),
            )
            results["config3_pose_dynbatch_fps"] = round(pd_fps, 2)
            results["config3_dynbatch_invokes"] = pd_batches
            log(f"# config3 pose dynbatch fps: {pd_fps:.2f} "
                f"({pd_batches} invokes / {n_pose} frames)")

    # -- config #2c: fused detect→crop→classify cascade --------------------
    # the reference runs this as detector → host decode → videocrop×K →
    # scaler → second filter; here the whole cascade is ONE program/frame.
    # The upload-overlap variant: the 300x300 frame crosses the wire in the
    # source thread while the queue worker dispatches the previous cascade.
    def leg_config2c():
        from nnstreamer_tpu.models import cascade as cascade_mod

        n_casc = int(os.environ.get("BENCH_CASCADE_FRAMES", "50"))
        if n_casc <= 0:
            raise _Skipped("skipped (0 frames)")
        casc = cascade_mod.build_detect_classify(
            num_labels=91, det_size=300, k=16, crop_size=96,
            num_classes=1001,
        )
        img300c = rng.integers(0, 256, (300, 300, 3)).astype(np.uint8)
        c_fps = run_pipeline_fps(
            "jax", casc, [img300c.copy() for _ in range(n_casc)]
        )
        results["config2c_cascade_fps"] = round(c_fps, 2)
        results["config2c_frames"] = n_casc
        log(f"# config2c cascade (detect+crop+classify x16) fps: {c_fps:.2f}")
        cu_fps = run_pipeline_fps(
            "jax", casc, [img300c.copy() for _ in range(n_casc)],
            upload=True,
        )
        results["config2c_cascade_upload_fps"] = round(cu_fps, 2)
        log(f"# config2c cascade upload fps: {cu_fps:.2f}")
        rep.snapshot()
        # dynbatch variant: the cascade model vmaps over batched frames,
        # so pile-ups amortize the per-frame transfer+dispatch of the
        # flagship-complexity topology too
        if not rep.over_budget("config2c dynbatch variant"):
            casc_poly = poly_wire_model(casc, 300)
            maxb = dynbatch_max()
            cd_fps, cd_batches, _ = run_dynbatch_fps(
                [img300c.copy() for _ in range(n_casc)], max_batch=maxb,
                poly_model=casc_poly,
            )
            results["config2c_cascade_dynbatch_fps"] = round(cd_fps, 2)
            results["config2c_dynbatch_invokes"] = cd_batches
            log(f"# config2c cascade dynbatch fps: {cd_fps:.2f} "
                f"({cd_batches} invokes / {n_casc} frames)")

    # -- segment.ab: whole-segment compilation on vs off -------------------
    # The SAME config2-shape SSD stream (fused decode head + fused-ssd
    # decoder) twice: stock graph vs one device program per
    # run-to-completion region (graph/segments.py — the decoder's
    # quantize+NMS folds into the filter's XLA program).  The device lane
    # rides both runs with a lowered idle-gap threshold so host-dispatch
    # starvation (device_idle{reason=host_dispatch}) is priced per frame
    # — the overhead the segment fold exists to collapse.
    def leg_segment_ab():
        from nnstreamer_tpu.models import ssd_mobilenet
        from nnstreamer_tpu.obs import spans as obs_spans

        n_seg = int(os.environ.get(
            "BENCH_SEGMENT_FRAMES", os.environ.get("BENCH_SSD_FRAMES", "100")))
        if n_seg <= 1:
            raise _Skipped("skipped (<2 frames)")
        ssd = ssd_mobilenet.build(num_labels=91, image_size=300,
                                  fused_decode=100)
        img300s = rng.integers(0, 256, (300, 300, 3)).astype(np.uint8)
        saved = {k: os.environ.get(k) for k in
                 ("NNSTPU_SEGMENT_ENABLED", "NNSTPU_TRACERS",
                  "NNSTPU_OBS_DEVICE_IDLE_GAP_MS")}
        os.environ["NNSTPU_TRACERS"] = "device"
        # default 5 ms hides sub-ms dispatch gaps; price everything ≥50 µs
        os.environ["NNSTPU_OBS_DEVICE_IDLE_GAP_MS"] = "0.05"
        seg = {"frames": n_seg}
        try:
            for variant, enabled in (("unfused", "0"), ("segment", "1")):
                os.environ["NNSTPU_SEGMENT_ENABLED"] = enabled
                obs_spans.reset()  # fresh recorder; the tracer re-activates
                # serialized chain (no decoder queue): the host decode's
                # dead time between device programs is the quantity the
                # segment variant folds away — with the queue it hides in
                # a second thread and both variants read ~0
                fps = run_pipeline_fps(
                    "jax", ssd, [img300s.copy() for _ in range(n_seg)],
                    decoder=("bounding_boxes", {
                        "option1": "fused-ssd", "option4": "300:300",
                        "option5": "300:300",
                    }),
                    pipelined=False,
                )
                idle = [r for r in obs_spans.snapshot()
                        if r[0] == obs_spans.PH_COMPLETE
                        and r[4] == "device_idle"
                        and r[9].get("reason") == "host_dispatch"]
                host_us = sum(r[2] for r in idle) / 1e3 / n_seg
                seg[variant] = {
                    "fps": round(fps, 2),
                    "host_dispatch_us_per_frame": round(host_us, 1),
                    "idle_gaps": len(idle),
                }
                log(f"# segment.ab {variant}: {fps:.2f} fps, host_dispatch "
                    f"{host_us:.1f} us/frame ({len(idle)} gaps)")
                rep.snapshot()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        if seg.get("unfused", {}).get("fps"):
            seg["speedup"] = round(
                seg["segment"]["fps"] / seg["unfused"]["fps"], 3)
        results["segment_ab"] = seg

    # -- partition.ab: all-edge vs all-fleet vs the planner's split --------
    # Among-device A/B (docs/partitioning.md): the SAME cascade chain in
    # three placements over real NNSQ — fully local, fully offloaded to a
    # fleet fragment worker, and wherever plan_partition puts the cut
    # from this run's OWN measured inputs (a live CostModelTracer on the
    # all-edge run + probe_edge_health on the candidate edge).  Every
    # placement must reproduce the all-edge frames bitwise (the ledger
    # stays exact across the wire), and the split run's per-frame
    # transfer lands in the hop:{edge} leg — so the planner's pick is
    # banked measured evidence, not a claim.  One caveat the numbers
    # carry on a single host: the edge probe drives the whole server
    # fragment, so transfer is priced conservatively (wire + one frame
    # of server compute) and the planner leans all-local.
    def leg_partition_ab():
        import tempfile

        from nnstreamer_tpu import parse_launch
        from nnstreamer_tpu.fleet.worker import FleetWorker
        from nnstreamer_tpu.graph.parse import split_launch
        from nnstreamer_tpu.graph.pipeline import Pipeline
        from nnstreamer_tpu.obs import spans as obs_spans
        from nnstreamer_tpu.obs.collector import attribute_trace
        from nnstreamer_tpu.obs.costmodel import CostModelTracer
        from nnstreamer_tpu.obs.spans import SpanTracer
        from nnstreamer_tpu.partition import (
            PartitionDeployment,
            plan_partition,
        )
        from nnstreamer_tpu.partition.deploy import probe_edge_health
        from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

        n_ab = int(os.environ.get("BENCH_PARTITION_FRAMES", "24"))
        if n_ab <= 1:
            raise _Skipped("skipped (<2 frames)")
        tmpd = tempfile.mkdtemp(prefix="bench_partition_")
        model_py = os.path.join(tmpd, "cascade_model.py")
        with open(model_py, "w") as f:
            f.write(
                "from nnstreamer_tpu.models import cascade\n"
                "def get_model():\n"
                "    return cascade.build_detect_classify(\n"
                "        num_labels=91, det_size=300, k=4, crop_size=96,\n"
                "        num_classes=101, width_mult=0.5, seed=0)\n")
        # queues bound each stage into its own thread so the tracer's
        # dispatch legs are per-stage costs, not whole-downstream pushes
        desc = (
            f"videotestsrc num-buffers={n_ab} pattern=smpte "
            "width=300 height=300 ! "
            "tensor_converter name=conv ! queue name=q0 ! "
            "tensor_transform mode=arithmetic "
            "option=typecast:float32,add:-127.5,div:127.5 name=norm ! "
            "queue name=q1 ! "
            f"tensor_filter framework=jax model={model_py} name=cascade ! "
            "tensor_sink name=out collect=true")
        # tiny-frame runs must not pollute a banked COST_MODEL.json
        # (tracer stop() autosaves to the configured path by default)
        cm_env = os.environ.get("NNSTPU_OBS_COSTMODEL_PATH")
        os.environ["NNSTPU_OBS_COSTMODEL_PATH"] = os.path.join(
            tmpd, "COST_MODEL.json")

        def run_placement(launch, tracer=None, spantracer=False):
            # steady-state formula (run_pipeline_fps): frame 0 pays
            # compile/startup, so the clock runs from its arrival to the
            # LAST frame's materialized result (async dispatch means a
            # bare sink arrival is not a completion)
            state = {"first": None}

            def on_frame(_frame):
                if state["first"] is None:
                    state["first"] = time.perf_counter()

            p = parse_launch(launch, Pipeline("partition_ab"))
            p.nodes["out"].connect("new-data", on_frame)
            if tracer is not None:
                p.attach_tracer(tracer)
            if spantracer:
                p.attach_tracer(SpanTracer())
            p.start()
            p.wait(600)
            p.stop()
            out = [[np.asarray(t) for t in fr.tensors]
                   for fr in p.nodes["out"].frames]
            done = time.perf_counter()
            if len(out) != n_ab or state["first"] is None:
                raise RuntimeError(
                    f"placement delivered {len(out)}/{n_ab} frames — "
                    "stalled or wedged split edge")
            return (n_ab - 1) / max(1e-9, done - state["first"]), out

        def assert_exact(got, placement):
            for i, (gold, g) in enumerate(zip(golden, got)):
                if len(gold) != len(g):
                    raise RuntimeError(
                        f"{placement} frame {i}: {len(g)} tensors vs "
                        f"{len(gold)}")
                for gt, t in zip(gold, g):
                    np.testing.assert_array_equal(
                        gt, t, err_msg=f"{placement} frame {i}")

        worker = None
        try:
            # placement 1: all-edge — doubles as the cost-model harvest
            # (the tracer rides the timed run: measuring with the
            # observatory attached is the deployed configuration)
            cmt = CostModelTracer()
            edge_fps, golden = run_placement(desc, tracer=cmt)
            snaps = cmt.stage_snapshots()
            results["partition_ab_frames"] = n_ab
            results["partition_ab_all_edge_fps"] = round(edge_fps, 2)
            log(f"# partition.ab all-edge: {edge_fps:.2f} fps "
                f"({len(snaps)} stage cost entries harvested)")
            rep.snapshot()

            # placement 2: all-fleet — cut=1, every interior stage behind
            # the wire on a fragment worker, hop-attributed
            _, server_desc = split_launch(desc, 1)
            worker = FleetWorker(
                name="bench_partition_ab", host="127.0.0.1", port=0,
                framework="fragment", model=server_desc)
            worker.start()
            deadline = time.monotonic() + 120
            while worker.probe() != "ok":
                if time.monotonic() > deadline:
                    raise RuntimeError("fragment worker never warmed")
                time.sleep(0.02)
            addr = f"127.0.0.1:{worker.query_port}"
            spec = TensorsSpec.of(
                TensorSpec(dtype=np.uint8, shape=(300, 300, 3)))
            # long probe timeout: the first round trip compiles the
            # fragment's cascade for this spec
            health = probe_edge_health(
                "127.0.0.1", worker.query_port, spec, n=3,
                connect_timeout=240.0)
            client_desc, _ = split_launch(desc, 1, client_props={
                "name": "qc_ab", "host": "127.0.0.1",
                "port": str(worker.query_port), "caps": "true",
                "require_caps": "true", "edge": "ab",
                "request_timeout": "240"})
            obs_spans.enable(16384)
            try:
                fleet_fps, fleet_out = run_placement(
                    client_desc, spantracer=True)
                by_trace = {}
                for r in obs_spans.snapshot():
                    if r[0] == obs_spans.PH_COMPLETE and r[6]:
                        by_trace.setdefault(r[6], []).append(r)
                hops = []
                for recs in by_trace.values():
                    legs_at = attribute_trace(recs)
                    if "hop:ab" in legs_at:
                        hops.append(legs_at["hop:ab"] / 1e3)  # ns → µs
            finally:
                obs_spans.disable()
            assert_exact(fleet_out, "all-fleet")
            results["partition_ab_all_fleet_fps"] = round(fleet_fps, 2)
            hop_us = round(sum(hops) / len(hops), 1) if hops else None
            if hop_us is not None:
                results["partition_ab_hop_us"] = hop_us
            log(f"# partition.ab all-fleet: {fleet_fps:.2f} fps, ledger "
                f"exact; hop:ab {hop_us} us/frame over {len(hops)} traces")
            rep.snapshot()

            # placement 3: the planner's pick from the harvested stage
            # legs + the probed edge (one host: placement scale 1.0)
            plan = plan_partition(
                desc, pipeline="partition_ab", addr=addr, edge="ab",
                cost_model={"schema": 1, "stages": snaps},
                wire_health=health)
            for s in plan.scores:
                log(f"#   partition.ab priced cut={s.cut}: "
                    f"{s.total_us:.0f} us/frame (client {s.client_us:.0f}"
                    f" + server {s.server_us:.0f}"
                    f" + transfer {s.transfer_us:.0f})")
            dep = PartitionDeployment(
                plan, client_props={"request_timeout": "240"}).start()
            try:
                planned_fps, planned_out = run_placement(
                    dep.client_launch())
            finally:
                dep.stop()
            assert_exact(planned_out, "planned")
            results["partition_ab_planned_fps"] = round(planned_fps, 2)
            results["partition_ab_planned_cut"] = plan.cut
            results["partition_ab_fingerprint"] = plan.fingerprint
            # verdict: the pick must not measure worse than either
            # measured alternative beyond run-to-run noise
            alts = {c: f for c, f in
                    {None: edge_fps, 1: fleet_fps}.items()
                    if c != plan.cut}
            agrees = all(planned_fps >= 0.9 * f for f in alts.values())
            results["partition_ab_planner_agrees"] = bool(agrees)
            log(f"# partition.ab planned cut={plan.cut} "
                f"(fingerprint {plan.fingerprint}): {planned_fps:.2f} fps"
                f" — {'within noise of or beating' if agrees else 'MEASURABLY BEHIND'}"
                f" the alternatives "
                f"{ {str(c): round(f, 2) for c, f in alts.items()} }")
        finally:
            if worker is not None:
                worker.stop()
            if cm_env is None:
                os.environ.pop("NNSTPU_OBS_COSTMODEL_PATH", None)
            else:
                os.environ["NNSTPU_OBS_COSTMODEL_PATH"] = cm_env

    # -- config #4: LSTM recurrence through repo slots ---------------------
    def leg_config4():
        n_steps = int(os.environ.get("BENCH_LSTM_STEPS", "200"))
        if n_steps <= 0:
            raise _Skipped("skipped (0 steps)")
        lstm_fps = run_lstm_recurrence_fps(n_steps)
        results["config4_lstm_steps_per_sec"] = round(lstm_fps, 2)
        results["config4_steps"] = n_steps
        log(f"# config4 lstm recurrence steps/sec: {lstm_fps:.2f}")

    # -- config #4c: transformer KV-cache decode through repo slots --------
    # device-resident state: the (L,2,T,d) cache never leaves the chip
    def leg_config4c():
        n_kv = int(os.environ.get("BENCH_KV_STEPS",
                                  os.environ.get("BENCH_LSTM_STEPS", "200")))
        if n_kv <= 0:
            raise _Skipped("skipped (0 steps)")
        if n_kv > 120:  # t_max=128 cache bounds the stream (minus warmup)
            log(f"# config4c: clamping {n_kv} steps to 120 (cache t_max=128)")
            n_kv = 120
        kv_fps = run_kvdecode_fps(n_kv)
        results["config4c_kvdecode_steps_per_sec"] = round(kv_fps, 2)
        results["config4c_steps"] = n_kv
        log(f"# config4c kv-cache decode steps/sec: {kv_fps:.2f}")

    # -- config #4d: continuous batching over the decode cell ---------------
    # capacity streams share one compiled step per tick (serving.py);
    # aggregate steps/sec vs config4c's single stream shows the batching
    # multiplier on the same cell
    def leg_config4d():
        n_cb = int(os.environ.get("BENCH_CONTBATCH_STEPS",
                                  os.environ.get("BENCH_LSTM_STEPS", "200")))
        if n_cb <= 0:
            raise _Skipped("skipped (0 steps)")
        n_cb = min(n_cb, 119)  # warmup + steps bounded by t_max=128
        cap = int(os.environ.get("BENCH_CONTBATCH_CAPACITY", "8"))
        cb_fps, cb_ticks = run_contbatch_fps(n_cb, capacity=cap)
        results["config4d_contbatch_steps_per_sec"] = round(cb_fps, 2)
        results["config4d_capacity"] = cap
        results["config4d_steps_per_stream"] = n_cb
        results["config4d_ticks"] = cb_ticks
        single = results.get("config4c_kvdecode_steps_per_sec")
        if single:
            results["config4d_vs_single_stream"] = round(cb_fps / single, 2)
        log(f"# config4d continuous batching: {cb_fps:.2f} steps/s "
            f"aggregate (capacity {cap}, {cb_ticks} ticks)")
        rep.snapshot()
        # prefill half of the split: T context tokens in ONE causal pass
        # vs T dispatch-bound decode ticks on the SAME cell (config4c is
        # the stepwise denominator)
        if not rep.over_budget("config4d prefill"):
            import jax as _jax
            import jax.numpy as _jnp

            from nnstreamer_tpu.models import transformer as _tr

            t_pf = n_cb  # already clamped to < t_max above
            # the SAME cell as config4c/4d by construction: one shared
            # DECODE_CELL definition, params from the shared builder
            cell = _tr.build_decode_cell(**DECODE_CELL)
            params4 = cell.params
            t_max4 = DECODE_CELL["t_max"]
            pf = _jax.jit(lambda xp, n: _tr.prefill(params4, xp, t_max4, n))
            xp = _jnp.asarray(np.random.default_rng(5).standard_normal(
                (t_max4, DECODE_CELL["d_in"])).astype(np.float32))
            nv = _jnp.int32(t_pf)
            _jax.block_until_ready(pf(xp, nv))  # compile outside timing
            reps = []
            for _ in range(3):
                t0 = time.perf_counter()
                _jax.block_until_ready(pf(xp, nv))
                reps.append(time.perf_counter() - t0)
            pf_tps = t_pf / min(reps)
            results["config4d_prefill_tokens_per_sec"] = round(pf_tps, 1)
            results["config4d_prefill_tokens"] = t_pf
            if single:
                results["config4d_prefill_vs_stepwise"] = round(
                    pf_tps / single, 2)
            log(f"# config4d prefill: {pf_tps:.1f} context tokens/s "
                f"(one pass, T={t_pf})")

    # -- config #4b: windowed sequence LSTM (lax.scan) ----------------------
    # The TPU-native recurrence: tensor_aggregator windows → ONE compiled
    # program scans the whole sequence on device.  Config #4 (per-step
    # repo-slot cycles) is round-trip-latency-bound by design — this is the
    # shape a TPU deployment actually uses for throughput.
    def leg_config4b():
        from nnstreamer_tpu.models import lstm as lstm_mod

        n_win = int(os.environ.get("BENCH_SEQ_WINDOWS", "100"))
        if n_win <= 0:
            raise _Skipped("skipped (0 windows)")
        seq_len, width = 128, 512
        seq_model = lstm_mod.build_sequence(
            input_size=width, hidden_size=width, seq_len=seq_len
        )
        windows = [
            rng.standard_normal((seq_len, width)).astype(np.float32)
            for _ in range(n_win)
        ]
        win_fps = run_pipeline_fps("jax", seq_model, windows, normalize=False)
        results["config4b_seq_windows_per_sec"] = round(win_fps, 2)
        results["config4b_windows"] = n_win
        results["config4b_seq_steps_per_sec"] = round(win_fps * seq_len, 1)
        log(f"# config4b sequence-lstm windows/sec: {win_fps:.2f} "
            f"({win_fps * seq_len:.0f} steps/s)")

    # -- config #5: mux → batched classifier, with a stream-scaling sweep --
    # (jax-sharded: the batch dim shards over however many chips exist; on
    # one chip it is an ordinary batched invoke through the sharding path)
    def leg_config5():
        import jax as _jax

        from nnstreamer_tpu.models import mobilenet_v2

        n_dev = max(1, len(_jax.devices()))
        n_streams = int(os.environ.get("BENCH_MUX_STREAMS", "4"))
        per_stream = int(os.environ.get("BENCH_MUX_FRAMES", "50"))
        if per_stream <= 0:
            raise _Skipped("skipped (0 frames)")
        sweep_set = {
            int(v) for v in
            os.environ.get("BENCH_MUX_SWEEP", "1,2,4,8").split(",") if v
        }
        sweep = sorted(sweep_set | {n_streams})
        scaling = {}
        results["config5_scaling"] = scaling
        results["config5_frames_per_stream"] = per_stream
        headline_model = None
        failed = []
        for streams in sweep:
            if streams != n_streams and rep.over_budget(
                    f"config5 sweep {streams}"):
                continue
            try:  # a failed sweep point must not discard measured ones
                batched = mobilenet_v2.build(
                    num_classes=1001, image_size=224, batch=streams
                )
                if streams == n_streams:
                    headline_model = batched  # reused by the upload variant
                fps = run_mux_batched_fps(
                    batched, streams, per_stream, image_u8,
                    framework="jax-sharded",
                    custom=f"devices={min(n_dev, streams)},axis=dp",
                )
                scaling[streams] = round(fps, 2)
                log(f"# config5 mux-batched fps ({streams} streams): {fps:.2f}")
            except Exception as exc:
                errors.append(f"config5 sweep {streams}: {exc!r}"[:300])
                failed.append(streams)
                log(traceback.format_exc())
        results["config5_mux_batched_fps"] = scaling.get(n_streams)
        rep.snapshot()
        # upload-overlap variant at the headline stream count: the batched
        # wire transfer rides the mux worker while the queue worker
        # dispatches the previous round
        if not rep.over_budget("config5 upload variant"):
            if headline_model is None:
                headline_model = mobilenet_v2.build(
                    num_classes=1001, image_size=224, batch=n_streams
                )
            u_fps = run_mux_batched_fps(
                headline_model, n_streams, per_stream, image_u8,
                framework="jax-sharded",
                custom=f"devices={min(n_dev, n_streams)},axis=dp",
                upload=True,
            )
            results["config5_mux_upload_fps"] = round(u_fps, 2)
            log(f"# config5 mux+upload fps ({n_streams} streams): {u_fps:.2f}")
        if failed:
            raise RuntimeError(f"config5 sweep points failed: {failed}")

    # -- per-frame breakdown (where the time goes, config #1) --------------
    def leg_breakdown():
        results["frame_breakdown"] = measure_frame_breakdown(image_u8)
        log(f"# frame breakdown: {results['frame_breakdown']}")

    # -- MFU + Pallas (diagnostics) -----------------------------------------
    def check_mfu(out):
        bad = sorted(k for k in out if k.endswith("_error"))
        if bad:
            raise RuntimeError(f"mfu sweep points failed: {bad}")

    def leg_mfu():
        results["mfu"] = measure_mfu()
        log(f"# mfu: {results['mfu']}")
        check_mfu(results["mfu"])

    def leg_mfu_vit():
        # framework-ceiling sweep: ViT-B/16 is matmul-dominated, so its MFU
        # shows what the framework+XLA path achieves when the model is
        # MXU-friendly (MobileNet's depthwise convs cap the sweep above)
        results["mfu_vit"] = measure_mfu(model_name="vit_b16")
        log(f"# mfu_vit: {results['mfu_vit']}")
        check_mfu(results["mfu_vit"])

    def leg_mfu_ladder():
        results["mfu_ladder"] = measure_mfu_ladder(rep=rep)
        cells = results["mfu_ladder"]["cells"]
        measured = sum(1 for c in cells.values() if "mfu" in c)
        skipped = sum(1 for c in cells.values() if "skipped" in c)
        log(f"# mfu.ladder: {measured} measured / {skipped} skipped of "
            f"{len(cells)} cells")
        failed = sorted(k for k, c in cells.items() if "error" in c)
        if failed:
            raise RuntimeError(f"mfu.ladder cells failed: {failed}")

    def leg_pallas():
        results["pallas"] = measure_pallas()
        log(f"# pallas: {results['pallas']}")

    # -- CPU baselines: the reference stack, isolated subprocesses ---------
    # (reused rows were loaded up front; only the missing ones cost time)
    def leg_baselines():
        if os.environ.get("BENCH_SKIP_BASELINES", "") == "1":
            raise _Skipped("BENCH_SKIP_BASELINES=1")
        failed = []
        for which in ("config1", "config1_quant", "config2", "config2c",
                      "config3", "config4", "config4b", "config5"):
            if which in rep.baselines:
                continue
            if rep.over_budget(f"baseline {which}"):
                continue
            try:
                timeout = max(60.0, rep.remaining() + 60.0)
                leg = run_baseline_leg(which, timeout=timeout)
                rep.baselines[which] = leg
                log(f"# baseline {which}: {leg}")
                if not leg.get("ok"):
                    raise RuntimeError(leg.get("error"))
            except Exception as exc:
                errors.append(f"baseline {which}: {exc!r}"[:300])
                failed.append(which)
            rep.snapshot()  # each baseline improves the ratios
        if failed:
            raise RuntimeError(f"baselines failed: {failed}")

    # ---- the runner: value order, budget gates, snapshot after every leg.
    # min_s is a rough floor — a leg isn't STARTED with less budget than
    # that left (the watchdog covers overshoot mid-leg).
    legs = [
        ("config1 jax leg", leg_config1_stream, 0.0),
        ("config1 upload leg", leg_config1_upload, 20.0),
        ("config1 dynbatch leg", leg_config1_dynbatch, 20.0),
        ("config1 dynupload leg", leg_config1_dynupload, 20.0),
        ("config5 mux leg", leg_config5, 30.0),
        ("config1 quant leg", leg_config1_quant, 20.0),
        ("config2 ssd leg", leg_config2, 30.0),
        ("config2c cascade leg", leg_config2c, 30.0),
        ("segment ab leg", leg_segment_ab, 30.0),
        ("partition ab leg", leg_partition_ab, 45.0),
        ("config3 pose leg", leg_config3, 30.0),
        ("config4 lstm leg", leg_config4, 15.0),
        ("config4b seq leg", leg_config4b, 20.0),
        ("config4c kvdecode leg", leg_config4c, 15.0),
        ("config4d contbatch leg", leg_config4d, 20.0),
        # baselines BEFORE the diagnostics: on a fresh host (no cache to
        # reuse) the judged vs_baseline ratio must outrank breakdown/MFU/
        # pallas when the budget runs short (review r5)
        ("baselines", leg_baselines, 15.0),
        ("breakdown", leg_breakdown, 15.0),
        ("mfu", leg_mfu, 30.0),
        ("mfu_vit", leg_mfu_vit, 30.0),
        ("mfu ladder", leg_mfu_ladder, 30.0),
        ("pallas", leg_pallas, 15.0),
    ]
    legs_filter = {
        v.strip() for v in os.environ.get("BENCH_LEGS", "").split(",")
        if v.strip()
    }
    for label, fn, min_s in legs:
        if legs_filter and label not in legs_filter:
            log(f"# {label}: not in BENCH_LEGS filter; skipped")
            continue
        if rep.over_budget(label):
            continue
        if min_s and rep.remaining() < min_s:
            errors.append(
                f"{label}: skipped ({rep.remaining():.0f}s budget left, "
                f"needs ~{min_s:g}s)")
            continue
        rep.current_leg = label
        try:
            fn()
        except _Skipped as exc:
            errors.append(f"{label}: {exc}")
        except Exception as exc:
            # the remaining legs still run; the exit code remembers
            errors.append(f"{label}: {exc!r}"[:400])
            rep.failed_legs.append(label)
            log(traceback.format_exc())
        rep.snapshot()

    rep.current_leg = "finalize"
    out = rep.finalize()
    rep.done = True
    return out, (1 if rep.failed_legs else 0)


if __name__ == "__main__":
    sys.exit(main(standalone=True)[1])
