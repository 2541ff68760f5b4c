"""Traffic kind ``mux_saturated``: N cameras into one batched model, closed
loop, as fast as the chip goes.

The launch graph (the shape ``chip_smoke.py::multi_stream`` proved):

    per group of <= 16 streams:
      group x (camera -> tensor_converter) -> tensor_mux sync_mode=nosync
        -> tensor_batch
    -> tensor_merge along the batch axis (a frame holds at most 16 tensors,
       so 32 or 48 streams cannot go through one mux)
    -> tensor_transform (normalise; fuses into the model)
    -> tensor_filter framework=jax  at batch = streams
    -> tee -> [logits sink, for the comparison]
           -> tensor_split back into the groups; per group:
              tensor_unbatch -> tensor_demux
              -> per stream: tensor_decoder mode=image_labeling -> tensor_sink

The cameras are the harness's own source element: frames made here from
``--seed`` (the program's ``videotestsrc`` would be a generator a later PR
could change), each stream a client that keeps ``inflight`` frames
outstanding and pushes the next when a label comes back.  That is the only
flow control: ``tensor_mux`` queues without bound, so free-running sources
would be an open loop over capacity with latency that grows all run long.

One general routine reads every parameter from the mix's file; a new mix of
this kind is a new file in ``benchmark/traffic/``.
"""

from __future__ import annotations

import threading
import time
from fractions import Fraction
from typing import Any, Dict, List, Optional

import numpy as np

import nnstreamer_tpu as nns
from nnstreamer_tpu.buffer import SECOND, Frame
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.graph.node import SourceNode
from nnstreamer_tpu.media import VideoSpec
from nnstreamer_tpu.obs import hooks

from .. import arithmetic

RATE = Fraction(30)                 # the cameras' nominal rate: pts only
PTS_STEP = int(SECOND / RATE)


def make_frames(seed: int, streams: int, pool: int, shape, grid: int) -> np.ndarray:
    """``(streams, pool, H, W, 3)`` uint8 frames from ``seed``: a coarse
    random colour grid under uniform noise, so that frames differ in what
    survives a mean over tokens (pure noise frames give near-equal logits
    and a routing mistake would hide in rounding)."""
    h, w, c = shape
    rng = np.random.default_rng(seed)
    coarse = rng.integers(0, 256, (streams, pool, grid, grid, c), dtype=np.uint8)
    rows, cols = np.arange(h) * grid // h, np.arange(w) * grid // w
    up = coarse[:, :, rows][:, :, :, cols]
    noise = rng.integers(0, 256, (streams, pool, h, w, c), dtype=np.uint8)
    return ((3 * up.astype(np.uint16) + noise) // 4).astype(np.uint8)


class StopLine:
    """Where every camera stops: all at the same frame count, so the mux's
    last round is whole and every pushed frame can reach its sink."""

    def __init__(self, gates):
        self.lock = threading.Lock()
        self.gates = gates
        self.pushed = [0] * len(gates)
        self.stop_at: Optional[int] = None

    def close(self) -> int:
        with self.lock:
            self.stop_at = max(self.pushed)
        for gate in self.gates:  # a camera waiting for a label sees the line
            gate.release()
        return self.stop_at


class CameraSrc(SourceNode):
    """One closed-loop camera client: pushes frame ``k`` of its pool when
    the gate has a permit (``inflight`` at start, one more per label that
    came back), and stamps the push."""

    def __init__(self, name, index, pool, gate, line, stamps):
        super().__init__(name)
        self.index, self.pool, self.gate = index, pool, gate
        self.line, self.stamps = line, stamps
        h, w, _ = pool[0].shape
        self.video = VideoSpec(format="RGB", width=w, height=h, rate=RATE)

    def output_spec(self):
        return self.video.tensor_spec()

    def frames(self):
        k = 0
        while not self.stopped:
            # a long wait: 48 cameras waking every few ms for nothing take
            # the interpreter lock from the thread that feeds the chip;
            # StopLine.close wakes every camera that is waiting
            if not self.gate.acquire(timeout=1.0):
                continue
            with self.line.lock:
                if self.line.stop_at is not None and k >= self.line.stop_at:
                    return
                self.line.pushed[self.index] = k + 1
            frame = Frame.of(self.pool[k % len(self.pool)], pts=k * PTS_STEP,
                             duration=PTS_STEP, media=self.video)
            self.stamps.append(time.perf_counter_ns())
            yield frame
            k += 1


class Result:
    """What one run of the kind hands back to ``run.py``."""

    def __init__(self):
        self.t0_ns = self.t1_ns = 0
        self.push_ns: List[List[int]] = []
        self.sink_ns: List[List[int]] = []
        self.labels: List[List[tuple]] = []
        self.logits: List[Any] = []          # per round, (streams, classes)
        self.rows: List[np.ndarray] = []     # the same on the host, once read
        self.dispatch_ns: List[int] = []     # per round, traced run only
        self.window: Dict[str, float] = {}
        self.failed = 0
        self.fail_notes: Dict[str, int] = {}
        self.trace_dir: Optional[str] = None
        self.frames: Optional[np.ndarray] = None
        self.pts_step = PTS_STEP
        self.drained = True
        self.degraded: Optional[str] = None  # the backend's, if it fell back


def run(mix: Dict[str, Any], model, normalize: Dict[str, float], frame_shape,
        model_classes: int, seed: int, seconds: float,
        trace_dir: Optional[str] = None, break_output=None) -> Result:
    """Build the graph, warm it, measure ``seconds``, drain, and return the
    stamps and the outputs.  ``break_output`` (tests only) wraps the model's
    apply so that the timed path itself is broken underneath the harness."""
    streams, inflight = int(mix["streams"]), int(mix["inflight"])
    res = Result()
    res.frames = make_frames(seed, streams, int(mix["frame_pool"]),
                             frame_shape, int(mix["frame_grid"]))
    res.push_ns = [[] for _ in range(streams)]
    res.sink_ns = [[] for _ in range(streams)]
    res.labels = [[] for _ in range(streams)]
    gates = [threading.Semaphore(inflight) for _ in range(streams)]
    line = StopLine(gates)
    if break_output is not None:
        import dataclasses

        model = dataclasses.replace(model, apply=break_output(model.apply))

    # a frame holds at most 16 tensors (NNS_TENSOR_SIZE_LIMIT), so the
    # cameras go through muxes of ``group`` streams, whose batches a
    # tensor_merge joins along the batch axis; tensor_split undoes it
    groups = [list(range(i, min(i + int(mix["group"]), streams)))
              for i in range(0, streams, int(mix["group"]))]
    p = nns.Pipeline(name=f"bench_{mix['name']}")
    merge = p.add(nns.make("tensor_merge", mode="linear", option="3",
                           sync_mode="nosync"))
    for g, members in enumerate(groups):
        mux = p.add(nns.make("tensor_mux", sync_mode="nosync"))
        for i, s in enumerate(members):
            cam = p.add(CameraSrc(f"cam{s}", s, res.frames[s], gates[s], line,
                                  res.push_ns[s]))
            conv = p.add(nns.make("tensor_converter"))
            p.link(cam, conv)
            p.link(conv, f"{mux.name}.sink_{i}")
        batch = p.add(nns.make("tensor_batch"))
        p.link(mux, batch)
        p.link(batch, f"{merge.name}.sink_{g}")
    norm = p.add(nns.make(
        "tensor_transform", mode="arithmetic",
        option=f"typecast:float32,add:{normalize['add']},div:{normalize['div']}"))
    filt = p.add(TensorFilter(framework="jax", model=model))
    tee = p.add(nns.make("tee"))
    logits = p.add(TensorSink(name="logits"))
    classes = model_classes
    split = p.add(nns.make("tensor_split", tensorseg=",".join(
        f"{classes}:{len(m)}" for m in groups)))
    p.link_chain(merge, norm, filt, tee, split)
    p.link(tee, logits)
    logits.connect("new-data", lambda f: res.logits.append(f.tensor(0)))

    def label_sink(s):
        stamps, labels, gate = res.sink_ns[s], res.labels[s], gates[s]

        def on_label(frame):
            stamps.append(time.perf_counter_ns())
            labels.append((frame.meta.get("label_index"),
                           frame.meta.get("score"), frame.pts))
            gate.release()

        return on_label

    for g, members in enumerate(groups):
        unbatch = p.add(nns.make("tensor_unbatch"))
        demux = p.add(nns.make("tensor_demux"))
        p.link(f"{split.name}.src_{g}", unbatch)
        p.link(unbatch, demux)
        for i, s in enumerate(members):
            dec = p.add(nns.make("tensor_decoder", mode="image_labeling"))
            sink = p.add(TensorSink(name=f"out{s}"))
            sink.connect("new-data", label_sink(s))
            p.link(f"{demux.name}.src_{i}", dec)
            p.link(dec, sink)

    def on_dispatch(node, frame, outs, t_ns):
        if node is filt:
            res.dispatch_ns.append(t_ns)

    if trace_dir is not None:
        hooks.connect("device_dispatch", on_dispatch)
    try:
        p.start()  # negotiates and compiles (or loads from the cache)
        warm = int(mix["warm_rounds"])
        _wait_rounds(p, res.sink_ns, warm)
        # the window opens at the last label of the warm-up rounds
        res.t0_ns = max(s[warm - 1] for s in res.sink_ns)
        res.t1_ns = res.t0_ns + int(seconds * 1e9)
        if trace_dir is not None:
            _traced_slice(mix, seconds, res, trace_dir)
        _sleep_until(res.t1_ns)
        line.close()
        res.drained = p.wait(timeout=120)
    finally:
        p.stop()
        if trace_dir is not None:
            hooks.disconnect("device_dispatch", on_dispatch)
    res.degraded = filt.backend._degraded
    # the window closes with the round that is in flight when its time is up
    res.window = arithmetic.window_metrics(
        res.push_ns, res.sink_ns, res.t0_ns, res.t1_ns,
        arithmetic.round_close_ns(res.sink_ns, res.t1_ns))
    return res


def _sleep_until(t_ns: int) -> None:
    while True:
        left = (t_ns - time.perf_counter_ns()) / 1e9
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def _wait_rounds(p, sink_ns, rounds: int, timeout: float = 600.0) -> None:
    """Warm-up: every stream has had ``rounds`` labels back, so every shape
    the window uses has run and nothing is left to compile."""
    end = time.monotonic() + timeout
    while min(len(s) for s in sink_ns) < rounds:
        if p._error is not None or p.state != "PLAYING":
            p.wait(timeout=0)  # raises the pipeline's error
            raise RuntimeError(f"pipeline left PLAYING in warm-up: {p.state}")
        if time.monotonic() > end:
            raise TimeoutError(f"warm-up: no {rounds} rounds in {timeout} s")
        time.sleep(0.005)


def _traced_slice(mix, seconds: float, res: Result, trace_dir: str) -> None:
    """Trace a few seconds of the steady window with jax's profiler."""
    import jax

    start = min(float(mix["trace_start_s"]), seconds / 4)
    length = min(float(mix["trace_seconds"]), seconds / 2)
    _sleep_until(res.t0_ns + int(start * 1e9))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        _sleep_until(res.t0_ns + int((start + length) * 1e9))
    finally:
        jax.profiler.stop_trace()
    res.trace_dir = trace_dir


def per_frame_faults(res: Result) -> None:
    """Count, over every frame pushed inside the window, the ones the timed
    path got wrong: never at its sink, out of order there, or with a label
    or score that is not the argmax and maximum of its own logits row (the
    decoder's ``score`` is the float it read, so the match is exact and a
    label routed to the wrong stream shows).  Fills ``failed``/``fail_notes``."""
    notes = {"missing": 0, "order": 0, "label": 0, "score": 0}
    rows = [np.asarray(a, np.float32) for a in res.logits]
    for s, pushes in enumerate(res.push_ns):
        for k, tp in enumerate(pushes):
            if not (res.t0_ns <= tp < res.t1_ns):
                continue
            if k >= len(res.labels[s]) or k >= len(rows):
                notes["missing"] += 1
                continue
            label, score, pts = res.labels[s][k]
            row = rows[k][s].reshape(-1)
            best = int(np.argmax(row))
            if pts != k * res.pts_step:
                notes["order"] += 1
            elif label != best:
                notes["label"] += 1
            elif score != float(row[best]):
                notes["score"] += 1
    res.fail_notes = notes
    res.failed = sum(notes.values())
    res.rows = rows


def sample(res: Result, mix: Dict[str, Any], seed: int):
    """The frames the reference is run over: ``check_frames`` of the frames
    pushed inside the window that came back, drawn from ``seed``: streams in
    a drawn order (every stream is in it where the sample is as large as the
    streams are many), of each a drawn frame of its pool, and the first push
    of that frame inside the window.  So the same seed compares the same
    pixels however many rounds a run got through.  Returns ``(frames_u8,
    program_logits, [(stream, k)])``."""
    rng = np.random.default_rng([seed, 0x5A])
    pool = res.frames.shape[1]
    order = rng.permutation(len(res.push_ns))
    want = int(mix["check_frames"])
    draws = rng.integers(pool, size=4 * want)
    picks = []
    for i in range(4 * want):
        if len(picks) >= want:
            break
        s = int(order[i % len(order)])
        for k, tp in enumerate(res.push_ns[s]):
            if (res.t0_ns <= tp < res.t1_ns and k % pool == draws[i]
                    and k < len(res.labels[s]) and k < len(res.rows)):
                picks.append((s, k))
                break
    if len(picks) < 2:
        return None, None, picks
    frames = np.stack([res.frames[s, k % pool] for s, k in picks])
    program = np.stack([res.rows[k][s].reshape(-1) for s, k in picks])
    return frames, program, picks
