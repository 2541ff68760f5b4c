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
this kind is a new file in ``benchmark/traffic/``.  The loop, the window and
what is compared after it are ``closed_loop``'s.
"""

from __future__ import annotations

from fractions import Fraction
from typing import Any, Dict, Optional

import numpy as np

import nnstreamer_tpu as nns
from nnstreamer_tpu.buffer import SECOND, Frame
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.media import VideoSpec

from . import closed_loop
from .closed_loop import per_frame_faults, sample  # noqa: F401  (run.py's)

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


class CameraSrc(closed_loop.ClientSrc):
    """One closed-loop camera client over its pool of frames."""

    def __init__(self, name, index, res):
        super().__init__(name, index, res)
        self.pool = res.frames[index]
        h, w, _ = self.pool[0].shape
        self.video = VideoSpec(format="RGB", width=w, height=h, rate=RATE)

    def output_spec(self):
        return self.video.tensor_spec()

    def frame(self, k):
        return Frame.of(self.pool[k % len(self.pool)], pts=k * PTS_STEP,
                        duration=PTS_STEP, media=self.video)


def run(mix: Dict[str, Any], model, cfg: Dict[str, Any], kind, sizes,
        seed: int, seconds: float, trace_dir: Optional[str] = None,
        break_output=None) -> closed_loop.Result:
    """Build the graph, warm it, measure ``seconds``, drain, and return the
    stamps and the outputs.  The cameras' frames are ``kind.frame_shape``
    images, normalised as the configuration says in front of the model,
    whose rows are ``num_classes`` wide."""
    streams = int(mix["streams"])
    normalize = cfg["normalize"]
    res = closed_loop.Result(streams, int(mix["inflight"]), PTS_STEP)
    res.frames = make_frames(seed, streams, int(mix["frame_pool"]),
                             kind.frame_shape(sizes), int(mix["frame_grid"]))
    model = closed_loop.broken(model, break_output)

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
            cam = p.add(CameraSrc(f"cam{s}", s, res))
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
    classes = int(sizes["num_classes"])
    split = p.add(nns.make("tensor_split", tensorseg=",".join(
        f"{classes}:{len(m)}" for m in groups)))
    p.link_chain(merge, norm, filt, tee, split)
    p.link(tee, logits)
    logits.connect("new-data", res.on_logits)

    for g, members in enumerate(groups):
        unbatch = p.add(nns.make("tensor_unbatch"))
        demux = p.add(nns.make("tensor_demux"))
        p.link(f"{split.name}.src_{g}", unbatch)
        p.link(unbatch, demux)
        for i, s in enumerate(members):
            dec = p.add(nns.make("tensor_decoder", mode="image_labeling"))
            sink = p.add(TensorSink(name=f"out{s}"))
            sink.connect("new-data", res.on_label(s))
            p.link(f"{demux.name}.src_{i}", dec)
            p.link(dec, sink)
    return closed_loop.drive(p, filt, res, mix, seconds, trace_dir)


def example_input(mix: Dict[str, Any], cfg: Dict[str, Any], kind, sizes,
                  batch: int):
    """What the model's executable takes at batch ``batch`` in this traffic:
    ``(shape, dtype, front)``, ``front`` being what the pipeline fuses in
    front of the model (the normalise).  For ``compile_rehearsal.py``."""
    import jax.numpy as jnp

    add, div = cfg["normalize"]["add"], cfg["normalize"]["div"]
    return ((batch,) + tuple(kind.frame_shape(sizes)), jnp.uint8,
            lambda x: (x.astype(jnp.float32) + add) / div)
