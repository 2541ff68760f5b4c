#!/usr/bin/env python
"""Randomized soak campaign: many short randomized pipelines, exactness
checked on every frame.  A failure prints the seed for a one-line repro:

    python tools/soak_campaign.py --seed N

Topology templates (drawn at random per iteration):
  linear        src → [upload+queue | dynbatch | both] → filter → sink
  tee           src → tee → (queued filter) × 2..3 branches
  mux           src×K → mux → batch → filter → unbatch → demux → sink×K
  repo          LSTM-style state cycle through repo slots
  trainer       (x, y) stream into tensor_trainer, loss must stay finite
  renegotiation mid-stream shape changes through random chains
  valve         event-driven valve close/reopen; order + exactness held
  interrupt     pipeline.stop() from another thread mid-stream (30s bound)
  query         TCP offload: QueryServer + 1-3 concurrent client pipelines
  sparse        tensor_sparse_enc→dec round-trip on random shapes/densities

Usage: python tools/soak_campaign.py [--minutes 10] [--seed N]
"""

import argparse
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # soak targets the graph, not the chip

import numpy as np  # noqa: E402


def run_linear(rng):
    import jax.numpy as jnp

    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.backends.jax_backend import JaxModel
    from nnstreamer_tpu.buffer import Frame
    from nnstreamer_tpu.elements.dynbatch import DynBatch, DynUnbatch
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.queue import Queue
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc
    from nnstreamer_tpu.elements.upload import TensorUpload

    n = int(rng.integers(20, 120))
    d = int(rng.integers(2, 16))
    scale = float(rng.uniform(0.5, 3.0))
    use_upload = bool(rng.integers(0, 2))
    use_dyn = bool(rng.integers(0, 2))
    frames = [Frame.of(np.full((d,), float(i), np.float32), pts=i)
              for i in range(n)]
    if use_dyn:
        model = JaxModel(apply=lambda p, x: x * scale,
                         input_spec=None)
    else:
        model = JaxModel(apply=lambda p, x: x * scale)
    got = []
    p = Pipeline()
    chain = [p.add(DataSrc(data=frames))]
    if use_dyn:
        chain.append(p.add(DynBatch(max_batch=int(2 ** rng.integers(1, 4)))))
    if use_upload:
        chain.append(p.add(TensorUpload()))
        chain.append(p.add(Queue(max_size_buffers=8)))
    chain.append(p.add(TensorFilter(framework="jax", model=model)))
    if use_dyn:
        chain.append(p.add(DynUnbatch()))
    sink = p.add(TensorSink())
    sink.connect("new-data", lambda f: got.append(np.asarray(f.tensor(0))))
    chain.append(sink)
    p.link_chain(*chain)
    p.run(timeout=120)
    assert len(got) == n, f"linear: {len(got)}/{n} frames"
    for i, a in enumerate(got):
        np.testing.assert_allclose(a, i * scale, rtol=1e-5,
                                   err_msg=f"frame {i}")


def run_tee(rng):
    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.backends.jax_backend import JaxModel
    from nnstreamer_tpu.buffer import Frame
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.queue import Queue
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.tee import Tee
    from nnstreamer_tpu.elements.testsrc import DataSrc

    n = int(rng.integers(20, 100))
    branches = int(rng.integers(2, 4))
    frames = [Frame.of(np.full((4,), float(i), np.float32), pts=i)
              for i in range(n)]
    got = [[] for _ in range(branches)]
    p = Pipeline()
    src = p.add(DataSrc(data=frames))
    tee = p.add(Tee())
    p.link(src, tee)
    for b in range(branches):
        q = p.add(Queue(max_size_buffers=int(rng.integers(2, 16))))
        f = p.add(TensorFilter(
            framework="jax",
            model=JaxModel(apply=lambda pp, x, b=b: x + float(b)),
        ))
        s = p.add(TensorSink())
        s.connect("new-data",
                  lambda fr, b=b: got[b].append(np.asarray(fr.tensor(0))))
        p.link(tee, q)
        p.link_chain(q, f, s)
    p.run(timeout=120)
    for b in range(branches):
        assert len(got[b]) == n, f"tee branch {b}: {len(got[b])}/{n}"
        for i, a in enumerate(got[b]):
            np.testing.assert_allclose(a, i + b, rtol=1e-5)


def run_mux(rng):
    from nnstreamer_tpu import Pipeline, make
    from nnstreamer_tpu.backends.jax_backend import JaxModel
    from nnstreamer_tpu.elements.batch import TensorBatch, TensorUnbatch
    from nnstreamer_tpu.elements.demux import TensorDemux
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc

    k = int(rng.integers(2, 5))
    per = int(rng.integers(10, 40))
    d = int(rng.integers(2, 8))
    got = {s: [] for s in range(k)}
    p = Pipeline()
    mux = p.add(make("tensor_mux", sync_mode="nosync"))
    for s in range(k):
        src = p.add(DataSrc(
            data=[np.full((d,), 100.0 * s + t, np.float32)
                  for t in range(per)], name=f"s{s}"))
        p.link(src, f"{mux.name}.sink_{s}")
    batch = p.add(TensorBatch())
    filt = p.add(TensorFilter(
        framework="jax", model=JaxModel(apply=lambda pp, x: x * 2.0)))
    unb = p.add(TensorUnbatch())
    demux = p.add(TensorDemux())
    p.link_chain(mux, batch, filt, unb, demux)
    for s in range(k):
        sink = p.add(TensorSink(name=f"o{s}"))
        sink.connect("new-data",
                     lambda fr, s=s: got[s].append(np.asarray(fr.tensor(0))))
        p.link(f"{demux.name}.src_{s}", sink)
    p.run(timeout=120)
    for s in range(k):
        assert len(got[s]) == per, f"mux stream {s}: {len(got[s])}/{per}"
        for t, a in enumerate(got[s]):
            np.testing.assert_allclose(a, 2.0 * (100.0 * s + t), rtol=1e-5)


def run_repo(rng):
    """The reference's tests/nnstreamer_repo_lstm topology: (h, c) cycle
    through two repo slots, x from a source; every step's h must equal the
    cell applied in sequence outside the pipeline."""
    import nnstreamer_tpu as nns
    from nnstreamer_tpu.buffer import Frame
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.repo import (GLOBAL_REPO, TensorRepoSink,
                                              TensorRepoSrc)
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.tee import Tee
    from nnstreamer_tpu.elements.testsrc import DataSrc
    from nnstreamer_tpu.models import lstm
    from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

    steps = int(rng.integers(10, 40))
    hidden = int(rng.integers(8, 64))
    model = lstm.build_cell(input_size=hidden, hidden_size=hidden)
    caps = TensorsSpec(tensors=(TensorSpec(dtype=np.float32, shape=(hidden,)),))
    xs = [np.full((hidden,), 0.01 * i, np.float32) for i in range(steps)]
    p = nns.Pipeline()
    h_src = p.add(TensorRepoSrc(name="h", slot_index=90, caps=caps))
    c_src = p.add(TensorRepoSrc(name="c", slot_index=91, caps=caps))
    x_src = p.add(DataSrc(name="x", data=[Frame.of(x, pts=i)
                                          for i, x in enumerate(xs)]))
    mux = p.add(nns.make("tensor_mux", sync_mode="nosync"))
    filt = p.add(TensorFilter(framework="jax", model=model))
    demux = p.add(nns.make("tensor_demux"))
    tee = p.add(Tee())
    out = p.add(TensorSink(collect=True))
    p.link(h_src, f"{mux.name}.sink_0")
    p.link(c_src, f"{mux.name}.sink_1")
    p.link(x_src, f"{mux.name}.sink_2")
    p.link_chain(mux, filt, demux)
    p.link(f"{demux.name}.src_0", tee)
    p.link(tee, p.add(TensorRepoSink(name="hs", slot_index=90)))
    p.link(tee, out)
    p.link(f"{demux.name}.src_1",
           p.add(TensorRepoSink(name="cs", slot_index=91)))
    try:
        p.run(timeout=120)
    finally:
        GLOBAL_REPO.reset(90)
        GLOBAL_REPO.reset(91)
    assert len(out.frames) == steps, f"repo: {len(out.frames)}/{steps}"
    h = c = np.zeros((hidden,), np.float32)
    for x, frame in zip(xs, out.frames):
        h, c = lstm.cell_step(model.params, h, c, x)
        np.testing.assert_allclose(np.asarray(frame.tensor(0)),
                                   np.asarray(h), rtol=1e-5, atol=1e-6)


def run_trainer(rng):
    import jax.numpy as jnp

    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.backends.jax_backend import JaxModel
    from nnstreamer_tpu.buffer import Frame
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc
    from nnstreamer_tpu.elements.trainer import TensorTrainer
    from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

    n = int(rng.integers(10, 40))
    d = int(rng.integers(2, 8))
    b = int(rng.integers(1, 4)) * 2
    w = rng.standard_normal((d, 2)).astype(np.float32)
    frames = []
    for i in range(n):
        x = rng.standard_normal((b, d)).astype(np.float32)
        frames.append(Frame.of(x, x @ w, pts=i))
    model = JaxModel(
        apply=lambda p, x: x @ p, params=jnp.zeros((d, 2), jnp.float32),
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.float32, shape=(b, d))),
    )
    curve = []
    p = Pipeline()
    src = p.add(DataSrc(data=frames))
    tr = p.add(TensorTrainer(model=model, loss="mse", optimizer="adam,lr=0.05"))
    sink = p.add(TensorSink())
    sink.connect("new-data",
                 lambda f: curve.append(float(np.asarray(f.tensor(0)))))
    p.link_chain(src, tr, sink)
    p.run(timeout=120)
    assert len(curve) == n and all(np.isfinite(v) for v in curve)


def run_renegotiation(rng):
    """Shape changes mid-stream through a random chain: caps events must
    renegotiate every hop (queue workers, dynbatch worker, backend
    recompiles) without loss or reorder."""
    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.backends.jax_backend import JaxModel
    from nnstreamer_tpu.buffer import Frame
    from nnstreamer_tpu.elements.dynbatch import DynBatch, DynUnbatch
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.queue import Queue
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc

    phases = int(rng.integers(2, 5))
    per = int(rng.integers(8, 30))
    use_q = bool(rng.integers(0, 2))
    use_dyn = bool(rng.integers(0, 2))
    frames, expect, seq = [], [], 0
    for _ in range(phases):
        shape = tuple(int(rng.integers(2, 5))
                      for _ in range(int(rng.integers(1, 3))))
        for _ in range(per):
            frames.append(Frame.of(np.full(shape, float(seq), np.float32),
                                   pts=seq))
            expect.append(float(seq) * int(np.prod(shape)))
            seq += 1
    model = JaxModel(apply=lambda p, x: (
        x.reshape(x.shape[0], -1).sum(axis=1) if use_dyn
        else x.reshape(-1).sum()[None]
    ))
    got = []
    p = Pipeline()
    chain = [p.add(DataSrc(data=frames))]
    if use_dyn:
        chain.append(p.add(DynBatch(max_batch=4)))
    if use_q:
        chain.append(p.add(Queue(max_size_buffers=8)))
    chain.append(p.add(TensorFilter(framework="jax", model=model)))
    if use_dyn:
        chain.append(p.add(DynUnbatch()))
    sink = p.add(TensorSink())
    sink.connect("new-data",
                 lambda f: got.append(float(np.asarray(f.tensor(0)).reshape(()))))
    chain.append(sink)
    p.link_chain(*chain)
    p.run(timeout=120)
    assert len(got) == seq, f"reneg: {len(got)}/{seq}"
    np.testing.assert_allclose(got, expect, rtol=1e-5)


def run_valve_selector(rng):
    """Flow control under load: a valve toggled mid-stream drops a known
    span; frames that pass must stay exact and ordered."""
    import threading

    from nnstreamer_tpu import Pipeline, make
    from nnstreamer_tpu.buffer import Frame
    from nnstreamer_tpu.elements.queue import Queue
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc

    n = int(rng.integers(50, 150))
    frames = [Frame.of(np.full((4,), float(i), np.float32), pts=i)
              for i in range(n)]
    got = []
    p = Pipeline()
    src = p.add(DataSrc(data=frames))
    valve = p.add(make("valve"))
    q = p.add(Queue(max_size_buffers=8))
    sink = p.add(TensorSink())
    # event-driven toggling (a wall-clock timer raced the stream on a
    # loaded host): close the valve after the 5th delivered frame,
    # reopen after a few ms — deliveries 1-5 are guaranteed through
    close_at = 5
    reopened = threading.Event()

    def on_frame(f):
        got.append(int(np.asarray(f.tensor(0))[0]))
        if len(got) == close_at and not reopened.is_set():
            valve.drop = True
            threading.Timer(0.01, lambda: (
                setattr(valve, "drop", False), reopened.set()
            )).start()

    sink.connect("new-data", on_frame)
    p.link_chain(src, valve, q, sink)
    p.run(timeout=120)
    # the first close_at deliveries are guaranteed: exactly frames 0..4
    assert got[:close_at] == list(range(close_at)), got[:close_at]
    # whatever arrived must be strictly increasing (order, no dup)
    assert all(b > a for a, b in zip(got, got[1:])), "reorder/dup past valve"
    assert len(got) >= close_at, f"only {len(got)} frames passed the valve"


def run_interrupt(rng):
    """Mid-stream stop: a busy pipeline (queues + filter + dynbatch) is
    stopped from another thread while frames are in flight.  The hunt is
    for shutdown deadlocks — stop() must return promptly."""
    import threading
    import time as _t

    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.backends.jax_backend import JaxModel
    from nnstreamer_tpu.buffer import Frame
    from nnstreamer_tpu.elements.dynbatch import DynBatch, DynUnbatch
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.queue import Queue
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc

    n = 2000  # more than will ever drain before the stop
    frames = [Frame.of(np.full((8,), float(i), np.float32), pts=i)
              for i in range(n)]
    p = Pipeline()
    chain = [p.add(DataSrc(data=frames))]
    if rng.integers(0, 2):
        chain.append(p.add(DynBatch(max_batch=4)))
        chain.append(p.add(Queue(max_size_buffers=4)))
        chain.append(p.add(TensorFilter(
            framework="jax", model=JaxModel(apply=lambda pp, x: x * 2.0))))
        chain.append(p.add(DynUnbatch()))
    else:
        chain.append(p.add(Queue(max_size_buffers=4)))
        chain.append(p.add(TensorFilter(
            framework="jax", model=JaxModel(apply=lambda pp, x: x * 2.0))))
    sink = p.add(TensorSink())
    chain.append(sink)
    p.link_chain(*chain)
    p.start()
    _t.sleep(float(rng.uniform(0.01, 0.15)))
    done = threading.Event()

    def stopper():
        p.stop()
        done.set()

    # daemon: if stop() truly wedges, the blocked thread must not keep the
    # campaign process alive past its final summary
    th = threading.Thread(target=stopper, daemon=True)
    th.start()
    th.join(timeout=30)
    assert done.is_set(), "pipeline.stop() deadlocked (>30s)"


def run_query(rng):
    """TCP offload under churn: an in-process QueryServer, 1-3 client
    pipelines (threads) with per-stream exactness; random shapes exercise
    the per-spec backend cache."""
    import threading

    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.backends.jax_backend import JaxModel
    from nnstreamer_tpu.elements.query import QueryServer, TensorQueryClient
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc
    from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

    n_clients = int(rng.integers(1, 4))
    per = int(rng.integers(5, 25))
    out_spec = TensorsSpec.of(TensorSpec(dtype=np.float32, shape=None))
    model = JaxModel(apply=lambda p, x: x * 2.0)
    # half the runs turn on cross-client batching (requires batch-dim
    # frames, which these (d0, ...) fills satisfy: rank >= 1)
    batch = int(rng.choice([0, 0, 2, 4]))
    with QueryServer(framework="jax", model=model, batch=batch,
                     batch_window_ms=float(rng.uniform(0.5, 10.0))) as srv:
        results = {}

        def client(k, shape):
            frames = [np.full(shape, float(100 * k + i), np.float32)
                      for i in range(per)]
            got = []
            p = Pipeline()
            src = p.add(DataSrc(data=frames))
            cli = p.add(TensorQueryClient(port=srv.port, out_spec=out_spec))
            sink = p.add(TensorSink())
            sink.connect("new-data",
                         lambda f: got.append(np.asarray(f.tensor(0))))
            p.link_chain(src, cli, sink)
            p.run(timeout=120)
            results[k] = got

        shapes = [tuple(int(rng.integers(2, 5))
                        for _ in range(int(rng.integers(1, 3))))
                  for _ in range(n_clients)]
        ts = [threading.Thread(target=client, args=(k, shapes[k]))
              for k in range(n_clients)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
    for k in range(n_clients):
        assert len(results.get(k, [])) == per, f"client {k} incomplete"
        for i, a in enumerate(results[k]):
            np.testing.assert_allclose(a, 2.0 * (100 * k + i), rtol=1e-5)


def run_tensor_if(rng):
    """Value-gating under load: known value stream through tensor_if —
    the surviving set must be exactly the frames matching the predicate."""
    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.tensor_if import TensorIf
    from nnstreamer_tpu.elements.testsrc import DataSrc

    n = int(rng.integers(20, 80))
    thr = float(rng.uniform(0.2, 0.8))
    vals = rng.uniform(0.0, 1.0, n).astype(np.float32)
    got = []
    p = Pipeline()
    src = p.add(DataSrc(data=[np.array([v], np.float32) for v in vals]))
    tif = p.add(TensorIf(compared_value="max", op=">", threshold=thr))
    sink = p.add(TensorSink())
    sink.connect("new-data",
                 lambda f: got.append(float(np.asarray(f.tensor(0))[0])))
    p.link_chain(src, tif, sink)
    p.run(timeout=120)
    want = [float(v) for v in vals if v > thr]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert tif.passed == len(want) and tif.dropped == n - len(want)


def run_crop(rng):
    """tensor_crop static mode under randomized regions: every crop in the
    (K,H,W,C) stack must equal its exact numpy slice (zero-pad beyond the
    region count, coordinates clamped into the frame)."""
    from fractions import Fraction

    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.elements.crop import TensorCrop
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc

    n = int(rng.integers(5, 20))
    H = W = int(rng.integers(16, 48))
    cw, ch = int(rng.integers(4, 12)), int(rng.integers(4, 12))
    K = int(rng.integers(1, 4))
    imgs = [rng.integers(0, 256, (H, W, 3)).astype(np.uint8)
            for _ in range(n)]
    # ≥1 row (the spec layer forbids 0-sized dims); zero-area sentinel rows
    # (w/h ≤ 0, the "no detection" encoding) are mixed in deliberately
    regs = []
    for _ in range(n):
        r = rng.integers(-4, max(W, H) + 4, (int(rng.integers(1, K + 2)), 4))
        r = r.astype(np.int32)
        for i in range(len(r)):
            if rng.uniform() < 0.2:
                r[i, 2 + int(rng.integers(0, 2))] = -int(rng.integers(0, 3))
        regs.append(r)
    got = []
    p = Pipeline()
    raw = p.add(DataSrc(data=imgs, rate=Fraction(30)))
    info = p.add(DataSrc(data=regs, rate=Fraction(30)))
    crop = p.add(TensorCrop(name="c", size=f"{cw}:{ch}", num=K))
    sink = p.add(TensorSink())
    sink.connect("new-data", got.append)
    p.link(raw, "c.raw")
    p.link(info, "c.info")
    p.link(crop, sink)
    p.run(timeout=120)
    assert len(got) == n
    for img, r, f in zip(imgs, regs, got):
        out = np.asarray(f.tensor(0))
        assert out.shape == (K, ch, cw, 3)
        valid = [row for row in r if row[2] > 0 and row[3] > 0][:K]
        assert f.meta["tensor_crop"]["regions"] == len(valid)
        for i, row in enumerate(valid):
            x = int(row[0]); y = int(row[1])
            x = max(0, min(x, W - cw)) if W >= cw else 0
            y = max(0, min(y, H - ch)) if H >= ch else 0
            want = np.zeros((ch, cw, 3), np.uint8)
            src_sl = img[y:y + ch, x:x + cw]
            want[:src_sl.shape[0], :src_sl.shape[1]] = src_sl
            np.testing.assert_array_equal(out[i], want)
        for i in range(len(valid), K):
            assert not out[i].any()


def run_rate(rng):
    """tensor_rate invariants on a randomized in/out rate pair: the output
    pts timeline is exactly slotted, counters balance, and the
    down-sampling case never duplicates (nor the up-sampling case drop)."""
    from nnstreamer_tpu import Pipeline
    from nnstreamer_tpu.buffer import Frame
    from nnstreamer_tpu.elements.rate import TensorRate
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc

    n = int(rng.integers(10, 60))
    fin = int(rng.integers(5, 60))
    fout = int(rng.integers(5, 60))
    dur = 1_000_000_000 // fin
    frames = [Frame.of(np.array([i], np.int32), pts=i * dur, duration=dur)
              for i in range(n)]
    got = []
    p = Pipeline()
    src = p.add(DataSrc(data=frames))
    rate = p.add(TensorRate(framerate=f"{fout}/1"))
    sink = p.add(TensorSink())
    sink.connect("new-data", got.append)
    p.link_chain(src, rate, sink)
    p.run(timeout=120)
    period = 1_000_000_000 // fout
    slots = [f.pts // period for f in got]
    assert slots == sorted(set(slots)), "output slots must be strictly increasing"
    assert all(f.pts % period == 0 for f in got)
    assert rate.in_frames == n
    assert rate.out_frames == len(got) == rate.in_frames - rate.drop + rate.dup
    if fout <= fin:
        assert rate.dup == 0
    if fout >= fin:
        assert rate.drop == 0
    # source values must appear in order (duplication repeats, never reorders)
    vals = [int(np.asarray(f.tensor(0))[0]) for f in got]
    assert vals == sorted(vals)


def run_sparse(rng):
    """tensor_sparse_enc→dec round-trip exactness on randomized shapes,
    dtypes, and densities (including all-zero and fully-dense frames),
    with a queue between the codec halves half the time."""
    from nnstreamer_tpu import Pipeline, make
    from nnstreamer_tpu.buffer import Frame
    from nnstreamer_tpu.elements.queue import Queue
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.testsrc import DataSrc

    n = int(rng.integers(5, 40))
    rank = int(rng.integers(1, 4))
    shape = tuple(int(rng.integers(2, 12)) for _ in range(rank))
    dtype = rng.choice([np.float32, np.int32, np.uint8])
    frames = []
    for i in range(n):
        x = np.zeros(shape, dtype)
        # 1-in-5 frames hit an exact extreme so the empty-sentinel and
        # fully-dense encoder paths really run (a uniform draw almost
        # never produces either)
        r = int(rng.integers(0, 5))
        density = 0.0 if r == 0 else 1.0 if r == 1 else float(rng.uniform(0, 1))
        k = int(round(x.size * density))
        if k:
            pos = rng.choice(x.size, size=k, replace=False)
            vals = rng.integers(1, 100, k)
            x.reshape(-1)[pos] = vals.astype(dtype)
        frames.append(Frame.of(x, pts=i))
    got = []
    p = Pipeline()
    chain = [p.add(DataSrc(data=[f.with_tensors((f.tensor(0).copy(),))
                                 for f in frames]))]
    chain.append(p.add(make("tensor_sparse_enc")))
    if rng.integers(0, 2):
        chain.append(p.add(Queue(max_size_buffers=4)))
    chain.append(p.add(make("tensor_sparse_dec")))
    sink = p.add(TensorSink())
    sink.connect("new-data", got.append)
    chain.append(sink)
    p.link_chain(*chain)
    p.run(timeout=120)
    assert len(got) == n
    for f, out in zip(frames, got):
        np.testing.assert_array_equal(np.asarray(out.tensor(0)),
                                      np.asarray(f.tensor(0)))
        assert out.pts == f.pts


def run_continuous_batching(rng):
    """serving.ContinuousBatcher under randomized membership churn:
    random capacity, random stream lengths, staggered joins/leaves/
    starvation, occasional slot reuse — every stream's outputs must
    match the single-sequence decode loop exactly."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models import transformer
    from nnstreamer_tpu.serving import ContinuousBatcher

    kw = dict(t_max=12, d_in=4, n_out=3, d_model=16, n_heads=2, n_layers=1)
    capacity = int(rng.integers(1, 5))
    n_streams = int(rng.integers(1, capacity + 3))  # more streams than slots
    lengths = [int(rng.integers(1, 9)) for _ in range(n_streams)]
    streams = [
        [rng.standard_normal(kw["d_in"]).astype(np.float32)
         for _ in range(n)]
        for n in lengths
    ]
    got = [[] for _ in streams]
    with ContinuousBatcher(capacity=capacity, seed=int(rng.integers(4)),
                           **kw) as eng:
        pending = list(range(n_streams))
        live = {}  # stream idx -> (session, iterator position)
        while pending or live:
            if pending and len(live) < capacity and rng.random() < 0.7:
                k = pending.pop(0)
                live[k] = (eng.open_session(timeout=30), 0)
            if not live:
                continue
            # random live stream advances one step; others starve
            k = list(live)[int(rng.integers(0, len(live)))]
            sess, i = live[k]
            sess.feed(streams[k][i])
            got[k].append(sess.get(timeout=60))
            if i + 1 >= lengths[k]:
                sess.close()
                del live[k]
            else:
                live[k] = (sess, i + 1)
        params = eng.params
    for k, xs in enumerate(streams):
        cache = transformer.init_decode_cache(
            kw["n_layers"], kw["d_model"], kw["t_max"])
        pos = jnp.zeros((1,), np.int32)
        for i, x in enumerate(xs):
            y, cache, pos = transformer.decode_step(
                params, jnp.asarray(x), cache, pos)
            np.testing.assert_allclose(
                got[k][i], np.asarray(y), rtol=1e-4, atol=1e-4)


TEMPLATES = [run_linear, run_tee, run_mux, run_repo, run_trainer,
             run_renegotiation, run_valve_selector, run_interrupt,
             run_query, run_tensor_if, run_crop, run_rate, run_sparse,
             run_continuous_batching]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--minutes", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=None)
    args = ap.parse_args()

    if args.seed is not None:  # single-iteration repro
        rng = np.random.default_rng(args.seed)
        fn = TEMPLATES[int(rng.integers(0, len(TEMPLATES)))]
        print(f"repro seed={args.seed}: {fn.__name__}")
        fn(rng)
        print("OK")
        return 0

    try:  # stamp the platform: a TPU soak log must be provably TPU
        import jax

        print(f"jax platform: {jax.devices()[0].platform}", flush=True)
    except Exception as exc:  # noqa: BLE001 — the soak itself still counts
        print(f"jax platform: unavailable ({exc!r})", flush=True)

    t_end = time.time() + args.minutes * 60
    i = fails = 0
    base = int(time.time())
    while time.time() < t_end:
        seed = base + i
        rng = np.random.default_rng(seed)
        fn = TEMPLATES[int(rng.integers(0, len(TEMPLATES)))]
        try:
            fn(rng)
            print(f"[{i}] {fn.__name__} seed={seed} OK", flush=True)
        except Exception:
            fails += 1
            print(f"[{i}] {fn.__name__} seed={seed} FAILED", flush=True)
            traceback.print_exc()
        i += 1
    print(f"campaign done: {i} iterations, {fails} failures")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
