"""The closed loop every saturated traffic kind shares: clients that each
keep a few frames outstanding and push the next when an answer comes back,
the window over their stamps, the traced slice, and what is compared once
the window has closed.

A traffic kind (``mux_saturated`` is one) makes the frames from ``--seed``,
builds its graph around the model with a ``ClientSrc`` per stream and
``Result.on_label``/``on_logits`` at the sinks, and hands the pipeline to
``drive``.  The loop, the window and ``sample`` know nothing of what a frame
holds: ``Result.frames`` is ``(streams, pool, *frame shape)`` of whatever
dtype the kind's sources push.  What comes back is not neutral: the sinks'
contract is the ``image_labeling`` decoder's (``on_label`` reads
``label_index`` and ``score``; ``per_frame_faults`` holds them to the argmax
and maximum of the frame's logits row).  A kind whose graph ends in another
decoder brings a ``per_frame_faults`` of its own, as ``TRAFFIC_KIND`` lets it.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from nnstreamer_tpu.graph.node import SourceNode
from nnstreamer_tpu.obs import hooks

from .. import arithmetic


class StopLine:
    """Where every client stops: all at the same frame count, so the last
    round is whole and every pushed frame can reach its sink."""

    def __init__(self, gates):
        self.lock = threading.Lock()
        self.gates = gates
        self.pushed = [0] * len(gates)
        self.stop_at: Optional[int] = None

    def close(self) -> int:
        with self.lock:
            self.stop_at = max(self.pushed)
        for gate in self.gates:  # a client waiting for a label sees the line
            gate.release()
        return self.stop_at


class Result:
    """What one run of a closed-loop kind hands back to ``run.py``."""

    def __init__(self, streams: int, inflight: int, pts_step: int):
        self.t0_ns = self.t1_ns = 0
        self.push_ns: List[List[int]] = [[] for _ in range(streams)]
        self.sink_ns: List[List[int]] = [[] for _ in range(streams)]
        self.labels: List[List[tuple]] = [[] for _ in range(streams)]
        self.logits: List[Any] = []          # per round, (streams, classes)
        self.rows: List[np.ndarray] = []     # the same on the host, once read
        self.dispatch_ns: List[int] = []     # per round, traced run only
        self.window: Dict[str, float] = {}
        self.failed = 0
        self.fail_notes: Dict[str, int] = {}
        self.trace_dir: Optional[str] = None
        self.frames: Optional[np.ndarray] = None
        self.pts_step = pts_step
        self.drained = True
        self.degraded: Optional[str] = None  # the backend's, if it fell back
        # one permit per frame a client may have outstanding
        self.gates = [threading.Semaphore(inflight) for _ in range(streams)]
        self.line = StopLine(self.gates)

    def on_logits(self, frame) -> None:
        self.logits.append(frame.tensor(0))

    def on_label(self, stream: int):
        """The callback of stream ``stream``'s last sink: stamps the answer,
        keeps what the decoder read and lets the client push its next."""
        stamps, labels = self.sink_ns[stream], self.labels[stream]
        gate = self.gates[stream]

        def on_label(frame):
            stamps.append(time.perf_counter_ns())
            labels.append((frame.meta.get("label_index"),
                           frame.meta.get("score"), frame.pts))
            gate.release()

        return on_label


class ClientSrc(SourceNode):
    """One closed-loop client: pushes its frame ``k`` when the gate has a
    permit (``inflight`` at start, one more per label that came back), and
    stamps the push.  A kind's source gives ``output_spec`` and ``frame``."""

    def __init__(self, name: str, index: int, res: Result):
        super().__init__(name)
        self.index = index
        self.gate, self.line = res.gates[index], res.line
        self.stamps = res.push_ns[index]

    def frame(self, k: int):
        """The ``Frame`` this client pushes as its ``k``-th."""
        raise NotImplementedError

    def frames(self):
        k = 0
        while not self.stopped:
            # a long wait: 48 clients waking every few ms for nothing take
            # the interpreter lock from the thread that feeds the chip;
            # StopLine.close wakes every client that is waiting
            if not self.gate.acquire(timeout=1.0):
                continue
            with self.line.lock:
                if self.line.stop_at is not None and k >= self.line.stop_at:
                    return
                self.line.pushed[self.index] = k + 1
            frame = self.frame(k)
            self.stamps.append(time.perf_counter_ns())
            yield frame
            k += 1


def broken(model, break_output):
    """``model`` with ``break_output`` (tests only) wrapped around its apply,
    so that the timed path itself is broken underneath the harness."""
    if break_output is None:
        return model
    return dataclasses.replace(model, apply=break_output(model.apply))


def drive(p, filt, res: Result, mix: Dict[str, Any], seconds: float,
          trace_dir: Optional[str] = None) -> Result:
    """Start pipeline ``p``, warm it, measure ``seconds``, stop every client
    at the line, drain, and read the window from the stamps.  ``filt`` is
    the element that holds the model: its ``device_dispatch`` hooks stamp
    the rounds of a traced run."""

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
        res.line.close()
        res.drained = p.wait(timeout=120)
    finally:
        p.stop()
        if trace_dir is not None:
            hooks.disconnect("device_dispatch", on_dispatch)
    res.degraded = filt.backend._degraded
    close_window(res)
    return res


def close_window(res: Result) -> None:
    """The window closes with the round that is in flight when its time is
    up; every end-to-end number is read from the stamps over it."""
    res.window = arithmetic.window_metrics(
        res.push_ns, res.sink_ns, res.t0_ns, res.t1_ns,
        arithmetic.round_close_ns(res.sink_ns, res.t1_ns))


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
    """For kinds whose sinks follow ``tensor_decoder mode=image_labeling``.
    Count, over every frame pushed inside the window, the ones the timed
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
    frames however many rounds a run got through.  Returns ``(frames,
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
