#!/usr/bin/env python
"""CPU baseline legs for bench.py — the reference stack on the same workloads.

Each invocation measures ONE config in an isolated process (so the TPU
runtime in the parent bench can never contend with the baseline's CPU
threads — the round-2 advisor flagged an unexplained 132→13.7 fps baseline
swing; isolation + pinned threads + recorded env is the fix) and prints
exactly one JSON line.

Usage: python tools/bench_baselines.py
       {config1|config1_quant|config2|config2c|config3|config4|config4b|config5}

Models for configs 2/3/4 are the *exact same jax models* the TPU legs run,
converted with ``tf.lite.TFLiteConverter.experimental_from_jax`` — matched
architecture and weights, running on the reference's tflite-CPU runtime
(``tensor_filter_tensorflow_lite_core.cc`` embeds the same interpreter).
Config 1 uses keras MobileNetV2 (float and post-training-quantized uint8,
the reference's actual flagship flavor).  All pipelines run through this
framework's own graph runtime with ``framework="tensorflow-lite"`` — the
identical topology the TPU legs use, only the backend differs.
"""

import json
import multiprocessing
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

# Pin JAX to CPU before any backend init: a baseline child must never
# reach for the chip its parent (bench.py) holds.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

N_THREADS = int(os.environ.get("BENCH_BASELINE_THREADS",
                               str(multiprocessing.cpu_count())))
N_FRAMES = int(os.environ.get("BENCH_BASELINE_FRAMES", "200"))


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def _tf():
    import tensorflow as tf

    tf.config.threading.set_intra_op_parallelism_threads(N_THREADS)
    tf.config.threading.set_inter_op_parallelism_threads(2)
    return tf


def tflite_from_jax(fn, example_args, quantize: bool = False,
                    rep_data=None) -> bytes:
    """Convert a jax fn to a tflite flatbuffer (same weights, same math)."""
    tf = _tf()
    converter = tf.lite.TFLiteConverter.experimental_from_jax(
        [fn], [[(f"in{i}", a) for i, a in enumerate(example_args)]]
    )
    # some lax convs legalize only through flex (tf select) ops, e.g.
    # explicit pads; the stock python tflite runtime ships the delegate
    converter.target_spec.supported_ops = [
        tf.lite.OpsSet.TFLITE_BUILTINS, tf.lite.OpsSet.SELECT_TF_OPS,
    ]
    if quantize:
        converter.optimizations = [tf.lite.Optimize.DEFAULT]
        if rep_data is not None:
            converter.representative_dataset = rep_data
    return converter.convert()


def tflite_from_keras(model, quantize: bool = False, rep_data=None) -> bytes:
    tf = _tf()
    converter = tf.lite.TFLiteConverter.from_keras_model(model)
    if quantize:
        converter.optimizations = [tf.lite.Optimize.DEFAULT]
        if rep_data is not None:
            converter.representative_dataset = rep_data
            converter.target_spec.supported_ops = [
                tf.lite.OpsSet.TFLITE_BUILTINS_INT8
            ]
            converter.inference_input_type = tf.uint8
            converter.inference_output_type = tf.uint8
    return converter.convert()


def stream_fps(model_bytes, frames, normalize=True, timeout=900,
               decoder=None):
    """datasrc → [normalize, host numpy] → tensor_filter(tensorflow-lite)
    [→ tensor_decoder] → sink fps — bench.run_pipeline_fps with the
    CPU-baseline knobs (one timing harness, no drift)."""
    import bench as bench_mod

    return bench_mod.run_pipeline_fps(
        "tensorflow-lite", model_bytes, frames, normalize=normalize,
        decoder=decoder, custom=f"num_threads={N_THREADS}", accel=False,
        timeout_s=timeout,
    )


def config1(quantize=False):
    tf = _tf()
    rng = np.random.default_rng(0)
    keras_model = tf.keras.applications.MobileNetV2(
        weights=None, input_shape=(224, 224, 3), classes=1000
    )
    img = rng.integers(0, 256, (224, 224, 3)).astype(np.uint8)
    if quantize:
        def rep():
            for _ in range(8):
                yield [rng.standard_normal((1, 224, 224, 3)).astype(np.float32)]

        blob = tflite_from_keras(keras_model, quantize=True, rep_data=rep)
        # uint8-in model: feed raw frames, no normalize (quant params absorb it)
        frames = [img[None].copy() for _ in range(N_FRAMES)]
        fps = stream_fps(blob, frames, normalize=False)
    else:
        blob = tflite_from_keras(keras_model)
        frames = [img[None].copy() for _ in range(N_FRAMES)]
        fps = stream_fps(blob, frames, normalize=True)
    return {"fps": fps, "frames": N_FRAMES, "model": "keras MobileNetV2"}


def config2():
    import jax.numpy as jnp

    from nnstreamer_tpu.models import ssd_mobilenet

    # float32: tflite has no bfloat16 kernels (CPU wants f32 anyway)
    ssd = ssd_mobilenet.build(num_labels=91, image_size=300, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 300, 300, 3)).astype(np.float32)
    fn = ssd.fn()
    blob = tflite_from_jax(fn, [x])
    img = rng.integers(0, 256, (1, 300, 300, 3)).astype(np.uint8)
    n = max(30, N_FRAMES // 4)  # SSD CPU is slow; keep the leg bounded
    import tempfile

    priors_path = os.path.join(tempfile.mkdtemp(), "priors.txt")
    ssd_mobilenet.write_priors_file(priors_path)
    # full detection path on CPU too: host decode (tflite-ssd) + overlay —
    # symmetric with the TPU leg's fused decode + overlay
    fps = stream_fps(blob, [img.copy() for _ in range(n)], normalize=True,
                     decoder=("bounding_boxes", {
                         "option1": "tflite-ssd", "option3": priors_path,
                         "option4": "300:300", "option5": "300:300"}))
    return {"fps": fps, "frames": n, "model": "jax ssd_mobilenet → tflite"}


def config3():
    import jax.numpy as jnp

    from nnstreamer_tpu.models import posenet

    pose = posenet.build(image_size=224, dtype=jnp.float32)
    grid = posenet.grid_size(224)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 224, 224, 3)).astype(np.float32)
    blob = tflite_from_jax(pose.fn(), [x])
    img = rng.integers(0, 256, (1, 224, 224, 3)).astype(np.uint8)
    n = max(30, N_FRAMES // 2)
    # full pose path on CPU too: host heatmap argmax + skeleton overlay —
    # symmetric with the TPU leg's fused decode + overlay
    fps = stream_fps(blob, [img.copy() for _ in range(n)], normalize=True,
                     decoder=("pose_estimation", {
                         "option1": "224:224",
                         "option2": f"{grid}:{grid}"}))
    return {"fps": fps, "frames": n, "model": "jax posenet → tflite"}


def config2c():
    """Detect→crop→classify cascade, the reference way: tflite SSD →
    host box decode (numpy) → host crop+resize (tf.image, the C++
    videocrop/videoscale analog) → second tflite classifier batched over
    the K crops.  Same models/weights as bench.py's fused one-program
    config2c leg (models/cascade.py), every stage a host round trip —
    exactly the multi-element topology under
    ``tests/nnstreamer_decoder_boundingbox/`` in the reference."""
    import jax.numpy as jnp
    tf = _tf()

    from nnstreamer_tpu.models import mobilenet_v2, ssd_mobilenet

    k, crop_size, det_size = 16, 96, 300
    rng = np.random.default_rng(0)
    det = ssd_mobilenet.build(num_labels=91, image_size=det_size,
                              dtype=jnp.float32)
    x_det = rng.standard_normal((1, det_size, det_size, 3)).astype(np.float32)
    det_blob = tflite_from_jax(det.fn(), [x_det])

    cls = mobilenet_v2.build(num_classes=1001, image_size=crop_size,
                             batch=k, dtype=jnp.float32)
    x_cls = rng.standard_normal((k, crop_size, crop_size, 3)).astype(np.float32)
    cls_blob = tflite_from_jax(cls.fn(), [x_cls])

    priors = ssd_mobilenet.generate_priors(det_size).T.astype(np.float32)

    def decode_topk_np(boxes, scores):
        s = 1.0 / (1.0 + np.exp(-scores[:, 1:].astype(np.float32)))
        best = s.max(axis=-1)
        top_i = np.argpartition(-best, k)[:k]
        top_i = top_i[np.argsort(-best[top_i])]
        loc, pri = boxes[top_i], priors[top_i]  # (k,4); pri: yc/xc/h/w
        yc = loc[:, 0] / 10.0 * pri[:, 2] + pri[:, 0]
        xc = loc[:, 1] / 10.0 * pri[:, 3] + pri[:, 1]
        h = np.exp(loc[:, 2] / 5.0) * pri[:, 2]
        w = np.exp(loc[:, 3] / 5.0) * pri[:, 3]
        return np.stack([xc - w / 2, yc - h / 2, w, h], axis=-1)

    def make_interp(blob):
        interp = tf.lite.Interpreter(model_content=blob,
                                     num_threads=N_THREADS)
        interp.allocate_tensors()
        return interp

    det_i, cls_i = make_interp(det_blob), make_interp(cls_blob)
    d_in = det_i.get_input_details()[0]["index"]
    d_out = [o["index"] for o in det_i.get_output_details()]
    c_in = cls_i.get_input_details()[0]["index"]

    img = rng.integers(0, 256, (det_size, det_size, 3)).astype(np.uint8)
    n = max(20, N_FRAMES // 10)

    def one_frame():
        xf = (img.astype(np.float32) - 127.5) / 127.5
        det_i.set_tensor(d_in, xf[None])
        det_i.invoke()
        o0 = det_i.get_tensor(d_out[0])[0]
        o1 = det_i.get_tensor(d_out[1])[0]
        boxes, scores = (o0, o1) if o0.shape[-1] == 4 else (o1, o0)
        xywh = decode_topk_np(boxes, scores)
        # x/y/w/h → normalized y1,x1,y2,x2 for crop_and_resize
        y1, x1 = xywh[:, 1], xywh[:, 0]
        bx = np.stack([y1, x1, y1 + xywh[:, 3], x1 + xywh[:, 2]], axis=-1)
        crops = tf.image.crop_and_resize(
            xf[None], np.clip(bx, 0.0, 1.0), np.zeros(k, np.int32),
            (crop_size, crop_size),
        ).numpy()
        cls_i.set_tensor(c_in, crops)
        cls_i.invoke()

    one_frame()  # warmup
    t0 = time.perf_counter()
    for _ in range(n):
        one_frame()
    fps = n / (time.perf_counter() - t0)
    return {"fps": fps, "frames": n, "k": k,
            "model": "tflite ssd + host decode/crop + tflite classifier"}


def config4():
    """The repo-slot LSTM recurrence with the cell on tflite-CPU — identical
    topology to bench.run_lstm_recurrence_fps, backend swapped."""
    import bench as bench_mod
    from nnstreamer_tpu.models import lstm

    hidden = 64
    model = lstm.build_cell(input_size=hidden, hidden_size=hidden)
    rng = np.random.default_rng(0)
    h = rng.standard_normal((hidden,)).astype(np.float32)
    blob = tflite_from_jax(model.fn(), [h, h.copy(), h.copy()])
    steps = int(os.environ.get("BENCH_LSTM_STEPS", "200"))
    fps = bench_mod.run_lstm_recurrence_fps(
        steps, hidden=hidden, framework="tensorflow-lite", model=blob,
        custom=f"num_threads=1",
    )
    return {"steps_per_sec": fps, "steps": steps, "model": "jax lstm cell → tflite"}


def config4b():
    """Windowed sequence LSTM (same lax.scan model → tflite while-loop)."""
    from nnstreamer_tpu.models import lstm

    seq_len, width = 128, 512
    model = lstm.build_sequence(input_size=width, hidden_size=width,
                                seq_len=seq_len)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((seq_len, width)).astype(np.float32)
    blob = tflite_from_jax(model.fn(), [x])
    n = max(20, N_FRAMES // 10)
    windows = [rng.standard_normal((seq_len, width)).astype(np.float32)
               for _ in range(n)]
    fps = stream_fps(blob, windows, normalize=False)
    return {"windows_per_sec": fps, "steps_per_sec": fps * seq_len,
            "frames": n, "model": "jax lstm sequence → tflite"}


def config5():
    """4-stream mux → batch → tflite(batch=4) → unbatch → demux."""
    import bench as bench_mod
    tf = _tf()
    rng = np.random.default_rng(0)
    keras_model = tf.keras.applications.MobileNetV2(
        weights=None, input_shape=(224, 224, 3), classes=1000
    )
    blob = tflite_from_keras(keras_model)
    n_streams = int(os.environ.get("BENCH_MUX_STREAMS", "4"))
    per_stream = int(os.environ.get("BENCH_MUX_FRAMES", "30"))
    img = rng.integers(0, 256, (224, 224, 3)).astype(np.uint8)
    fps = bench_mod.run_mux_batched_fps(
        blob, n_streams, per_stream, img, framework="tensorflow-lite",
        custom=f"num_threads={N_THREADS}", accel=False,
    )
    return {"fps": fps, "streams": n_streams, "frames_per_stream": per_stream,
            "model": "keras MobileNetV2 (batch invoke)"}


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "config1"
    t0 = time.perf_counter()
    try:
        if which == "config1":
            out = config1()
        elif which == "config1_quant":
            out = config1(quantize=True)
        elif which == "config2":
            out = config2()
        elif which == "config2c":
            out = config2c()
        elif which == "config3":
            out = config3()
        elif which == "config4":
            out = config4()
        elif which == "config4b":
            out = config4b()
        elif which == "config5":
            out = config5()
        else:
            raise ValueError(f"unknown config {which!r}")
        out.update(
            ok=True,
            config=which,
            threads=N_THREADS,
            cpu_count=multiprocessing.cpu_count(),
            wall_s=round(time.perf_counter() - t0, 1),
        )
    except Exception as exc:  # noqa: BLE001 — one leg must never kill the bench
        import traceback

        traceback.print_exc()
        out = {"ok": False, "config": which, "error": repr(exc)[:400]}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
