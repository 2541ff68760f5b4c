#!/usr/bin/env python
"""Where does config1's per-frame time go on the real chip?

Measures, in order of increasing framework involvement:
  a) batch-1 device step time (device-resident input, sync each call)
  b) jit dispatch rate from Python (async, same input, drain at end)
  c) host->device invoke chain (numpy arg per call, flat wire, drain at end)
  d) backend.invoke() loop (JaxBackend, no graph)
  e) full streaming pipeline (DataSrc -> transform(fused) -> filter -> sink)
  f) (e) under cProfile, top cumulative entries

Run:  python tools/profile_hotloop.py [n_frames]
"""
import cProfile
import io
import os
import pstats
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp


def rate(fn, n, drain=None):
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    if drain is not None:
        drain(out)
    dt = time.perf_counter() - t0
    return n / dt, dt / n * 1e3


def tiny_main(n=1000):
    """Framework-overhead view: a near-zero-compute model makes the loop
    time ≈ pure framework cost (graph hops + backend.invoke + dispatch).
    Compute and transfer are ~0 here, so every millisecond is ours."""
    import numpy as np

    from nnstreamer_tpu.backends.jax_backend import JaxBackend, JaxModel
    from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

    model = JaxModel(
        apply=lambda p, x: x.reshape(-1)[:8].astype(jnp.float32),
        input_spec=TensorsSpec.of(
            TensorSpec(dtype=np.uint8, shape=(224, 224, 3))),
    )
    img = np.random.default_rng(0).integers(0, 256, (224, 224, 3)).astype(np.uint8)
    frames = [img.copy() for _ in range(n)]

    fn = jax.jit(lambda x: x.reshape(-1)[:8].astype(jnp.float32))
    fn(img.reshape(-1)).block_until_ready()
    it = iter(frames)
    fps, ms = rate(lambda: fn(next(it).reshape(-1)), n,
                   drain=lambda o: o.block_until_ready())
    print(f"t0) raw jit dispatch:       {ms:8.4f} ms  ({fps:8.1f}/s)")

    be = JaxBackend()
    be.open(model)
    be.reconfigure(TensorsSpec.from_arrays((img,)))
    be.invoke((img,))
    it = iter(frames)
    fps, ms = rate(lambda: be.invoke((next(it),)), n,
                   drain=lambda o: o[0].block_until_ready())
    print(f"t1) backend.invoke loop:    {ms:8.4f} ms  ({fps:8.1f}/s)")

    import nnstreamer_tpu as nns
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc

    state = {"first": None, "count": 0}

    def cb(frame):
        state["count"] += 1
        if state["first"] is None:
            state["first"] = time.perf_counter()

    best = None
    for _ in range(3):  # warm + take the best of three runs
        state.update(first=None, count=0)
        p = nns.Pipeline()
        src = p.add(DataSrc(data=frames))
        filt = p.add(TensorFilter(framework="jax", model=model))
        sink = p.add(TensorSink(callback=cb))
        p.link_chain(src, filt, sink)
        p.run(timeout=300)
        if state["first"] is None or state["count"] < 2:
            raise RuntimeError(
                f"pipeline delivered {state['count']} frames (need >= 2 "
                "for a rate) — stalled, or run with a larger n")
        dt = (time.perf_counter() - state["first"]) / (state["count"] - 1) * 1e3
        best = dt if best is None else min(best, dt)
    print(f"t2) full pipeline/frame:    {best:8.4f} ms  ({1e3 / best:8.1f}/s)")
    verdict = "PASS" if best <= 0.5 else "FAIL"
    print(f"t3) framework overhead budget (<=0.5 ms/frame): {verdict}")

    pr = cProfile.Profile()
    state.update(first=None, count=0)
    p = nns.Pipeline()
    src = p.add(DataSrc(data=frames))
    filt = p.add(TensorFilter(framework="jax", model=model))
    sink = p.add(TensorSink(callback=cb))
    p.link_chain(src, filt, sink)
    pr.enable()
    p.run(timeout=300)
    pr.disable()
    s = io.StringIO()
    st = pstats.Stats(pr, stream=s)
    st.sort_stats("tottime").print_stats(18)
    print(s.getvalue())


def main():
    if "--tiny" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--tiny"]
        tiny_main(int(args[0]) if args else 1000)
        return
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 200
    from nnstreamer_tpu.models import mobilenet_v2

    model = mobilenet_v2.build(num_classes=1001, image_size=224)
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, (224, 224, 3)).astype(np.uint8)
    flat = np.ascontiguousarray(img).reshape(-1)

    fused = jax.jit(lambda x: model.apply(
        model.params,
        ((x.astype(jnp.float32) - 127.5) / 127.5).reshape(1, 224, 224, 3),
    ))
    d = jax.device_put(flat)
    d.block_until_ready()
    fused(d).block_until_ready()
    fused(flat).block_until_ready()

    # a) sync step time, device-resident
    fps, ms = rate(lambda: fused(d).block_until_ready(), min(n, 100))
    print(f"a) sync device step:        {ms:8.3f} ms  ({fps:7.1f}/s)")

    # b) async dispatch, device-resident
    fps, ms = rate(lambda: fused(d), n, drain=lambda o: o.block_until_ready())
    print(f"b) async dispatch (device): {ms:8.3f} ms  ({fps:7.1f}/s)")

    # c) async chain from host numpy (fresh array each call to defeat caching)
    frames = [flat.copy() for _ in range(n)]
    it = iter(frames)
    fps, ms = rate(lambda: fused(next(it)), n, drain=lambda o: o.block_until_ready())
    print(f"c) async chain (host np):   {ms:8.3f} ms  ({fps:7.1f}/s)")

    # c2) explicit device_put then dispatch, K-deep window
    it = iter(frames)
    fps, ms = rate(lambda: fused(jax.device_put(next(it))), n,
                   drain=lambda o: o.block_until_ready())
    print(f"c2) device_put + dispatch:  {ms:8.3f} ms  ({fps:7.1f}/s)")

    # d) backend.invoke loop (float32 frames — the model's declared spec;
    # the streaming pipeline feeds uint8 only via the fused-transform entry)
    from nnstreamer_tpu.backends.jax_backend import JaxBackend
    from nnstreamer_tpu.spec import TensorsSpec

    imgf = img.astype(np.float32)
    be = JaxBackend()
    be.open(model)
    be.reconfigure(TensorsSpec.from_arrays((imgf,)))
    be.invoke((imgf,))
    frames2 = [imgf.copy() for _ in range(n)]
    it2 = iter(frames2)
    fps, ms = rate(lambda: be.invoke((next(it2),)), n,
                   drain=lambda o: o[0].block_until_ready())
    print(f"d) backend.invoke loop:     {ms:8.3f} ms  ({fps:7.1f}/s)")

    # e) full pipeline
    import bench

    data = [img.copy() for _ in range(n)]
    fps = bench.run_pipeline_fps("jax", model, data)
    print(f"e) full pipeline:           {1e3 / fps:8.3f} ms  ({fps:7.1f}/s)")

    # f) profile the pipeline run
    pr = cProfile.Profile()
    pr.enable()
    fps = bench.run_pipeline_fps("jax", model, data)
    pr.disable()
    print(f"f) pipeline under profile:  {1e3 / fps:8.3f} ms  ({fps:7.1f}/s)")
    s = io.StringIO()
    st = pstats.Stats(pr, stream=s)
    st.sort_stats("cumulative").print_stats(30)
    print(s.getvalue())


if __name__ == "__main__":
    main()
