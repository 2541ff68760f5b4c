"""Traffic kind ``token_windows``: N closed-loop clients that each push a
window of token ids into one batched model and read the next token back.

    streams x token client -> tensor_mux sync_mode=nosync -> tensor_batch
    -> tensor_filter framework=jax  at batch = streams
    -> tee -> [logits sink, for the comparison]
           -> tensor_unbatch -> tensor_demux
              -> per stream: tensor_decoder mode=image_labeling (the next
                 token's id and its logit) -> tensor_sink

A frame is one ``[seq]`` int32 window, its ids uniform over the whole
vocabulary from ``--seed``; a tensor stream's caps fix the shape, so every
window is ``seq`` long.  A frame holds at most 16 tensors, so at most 16
streams go through the one mux.  The loop, the window and what is compared
after it are ``closed_loop``'s.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

import nnstreamer_tpu as nns
from nnstreamer_tpu.buffer import SECOND, Frame
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

from . import closed_loop
from .closed_loop import per_frame_faults, sample  # noqa: F401  (run.py's)

PTS_STEP = SECOND // 100


def make_frames(seed: int, streams: int, pool: int, seq: int,
                vocab: int) -> np.ndarray:
    """``(streams, pool, seq)`` int32 token ids from ``seed``."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (streams, pool, seq), dtype=np.int32)


class TokenSrc(closed_loop.ClientSrc):
    """One closed-loop client over its pool of windows."""

    def __init__(self, name, index, res):
        super().__init__(name, index, res)
        self.pool = res.frames[index]

    def output_spec(self):
        return TensorsSpec.of(TensorSpec(dtype=np.int32,
                                         shape=self.pool[0].shape))

    def frame(self, k):
        return Frame.of(self.pool[k % len(self.pool)], pts=k * PTS_STEP,
                        duration=PTS_STEP)


def run(mix: Dict[str, Any], model, cfg: Dict[str, Any], kind, sizes,
        seed: int, seconds: float, trace_dir: Optional[str] = None,
        break_output=None) -> closed_loop.Result:
    del cfg
    streams = int(mix["streams"])
    (seq,) = kind.frame_shape(sizes)
    res = closed_loop.Result(streams, int(mix["inflight"]), PTS_STEP)
    res.frames = make_frames(seed, streams, int(mix["frame_pool"]), seq,
                             int(sizes["vocab_size"]))
    p = nns.Pipeline(name=f"bench_{mix['name']}")
    mux = p.add(nns.make("tensor_mux", sync_mode="nosync"))
    for s in range(streams):
        p.link(p.add(TokenSrc(f"client{s}", s, res)), f"{mux.name}.sink_{s}")
    batch = p.add(nns.make("tensor_batch"))
    filt = p.add(TensorFilter(framework="jax",
                              model=closed_loop.broken(model, break_output)))
    tee = p.add(nns.make("tee"))
    logits = p.add(TensorSink(name="logits"))
    unbatch = p.add(nns.make("tensor_unbatch"))
    demux = p.add(nns.make("tensor_demux"))
    p.link_chain(mux, batch, filt, tee, unbatch, demux)
    p.link(tee, logits)
    logits.connect("new-data", res.on_logits)
    for s in range(streams):
        dec = p.add(nns.make("tensor_decoder", mode="image_labeling"))
        sink = p.add(TensorSink(name=f"out{s}"))
        sink.connect("new-data", res.on_label(s))
        p.link(f"{demux.name}.src_{s}", dec)
        p.link(dec, sink)
    return closed_loop.drive(p, filt, res, mix, seconds, trace_dir)


def example_input(mix: Dict[str, Any], cfg: Dict[str, Any], kind, sizes,
                  batch: int):
    del mix, cfg
    return (batch,) + tuple(kind.frame_shape(sizes)), np.int32, lambda x: x
