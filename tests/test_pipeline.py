"""Graph-runtime tests: construction, negotiation, scheduling, events —
the analog of the reference's whole-pipeline ``unittest_sink.cpp`` cases."""

import numpy as np
import pytest

from nnstreamer_tpu import NegotiationError, Pipeline, make, parse_launch
from nnstreamer_tpu.elements.app import AppSink, AppSrc
from nnstreamer_tpu.elements.queue import Queue
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.tee import Tee
from nnstreamer_tpu.elements.testsrc import DataSrc, VideoTestSrc
from nnstreamer_tpu.buffer import Frame
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec


def test_auto_names_never_collide():
    """Anonymous elements get monotonic names (gst's elementN).  The old
    id(self)%10000 scheme collided once CPython reused addresses — found
    by the soak campaign as 'duplicate node name' in multi-element
    pipelines (tools/soak_campaign.py seeds 1785431042/1184/1304/2007)."""
    from nnstreamer_tpu.graph.node import Node

    names = [Node().name for _ in range(20000)]
    assert len(set(names)) == len(names)
    # and they register into a pipeline without duplicate-name errors
    p = Pipeline()
    for _ in range(64):
        p.add(Queue())
        p.add(TensorSink())


def test_datasrc_to_sink():
    data = [np.full((4,), i, np.float32) for i in range(5)]
    p = Pipeline()
    src = p.add(DataSrc(data=data))
    sink = p.add(TensorSink(collect=True))
    p.link(src, sink)
    p.run(timeout=10)
    assert sink.num_frames == 5
    assert [int(f.tensor(0)[0]) for f in sink.frames] == [0, 1, 2, 3, 4]


def test_sources_start_last_added_first():
    """Of the sources feeding one collect element the last to start
    completes the first round and carries every later one: started in this
    order that is the source on the element's first pad, whatever the run."""
    p = Pipeline()
    mux = p.add(make("tensor_mux", sync_mode="nosync"))
    for i in range(3):
        p.link(p.add(DataSrc(name=f"s{i}", data=[np.zeros(2, np.float32)])),
               f"{mux.name}.sink_{i}")
    p.link(mux, p.add(TensorSink(name="out")))
    p.start()
    try:
        assert [t.name for t in p.threads if t.name.startswith("src:")] == [
            "src:s2", "src:s1", "src:s0"]
        p.wait(timeout=10)
    finally:
        p.stop()


def test_negotiated_specs_propagate():
    p = Pipeline()
    src = p.add(VideoTestSrc(num_buffers=2, width=64, height=48))
    sink = p.add(TensorSink(collect=True))
    p.link(src, sink)
    p.run(timeout=10)
    spec = sink.sink_pads["sink"].spec
    assert spec.tensors[0].shape == (48, 64, 3)
    assert sink.frames[0].tensor(0).shape == (48, 64, 3)


def test_queue_decouples_and_preserves_order():
    data = [np.array([i], np.int32) for i in range(50)]
    p = Pipeline()
    src = p.add(DataSrc(data=data))
    q = p.add(Queue(max_size_buffers=4))
    sink = p.add(TensorSink(collect=True))
    p.link_chain(src, q, sink)
    p.run(timeout=10)
    assert [int(f.tensor(0)[0]) for f in sink.frames] == list(range(50))


def test_tee_fanout():
    data = [np.array([i], np.int32) for i in range(10)]
    p = Pipeline()
    src = p.add(DataSrc(data=data))
    tee = p.add(Tee())
    s1 = p.add(TensorSink(name="s1", collect=True))
    s2 = p.add(TensorSink(name="s2", collect=True))
    p.link(src, tee)
    p.link(tee, s1)
    p.link(tee, s2)
    p.run(timeout=10)
    assert s1.num_frames == 10 and s2.num_frames == 10


def test_negotiation_failure_raises():
    class PickySink(TensorSink):
        def sink_spec(self, pad_name):
            return TensorsSpec.of(TensorSpec(dtype=np.uint8, shape=(7,)))

    p = Pipeline()
    src = p.add(DataSrc(data=[np.zeros((3,), np.float32)]))
    sink = p.add(PickySink())
    p.link(src, sink)
    with pytest.raises(NegotiationError):
        p.start()
    p.stop()


def test_error_in_node_propagates():
    class Boom(TensorSink):
        def process(self, pad, frame):
            raise RuntimeError("boom")

    p = Pipeline()
    src = p.add(DataSrc(data=[np.zeros(3, np.float32)]))
    sink = p.add(Boom())
    p.link(src, sink)
    p.start()
    with pytest.raises(Exception, match="boom"):
        p.wait(5)
    p.stop()


def test_appsrc_appsink():
    p = Pipeline()
    src = p.add(AppSrc(caps="other/tensor, dimension=(string)4:1:1:1, "
                            "type=(string)float32, framerate=(fraction)0/1"))
    sink = p.add(AppSink())
    p.link(src, sink)
    p.start()
    for i in range(3):
        src.push_frame(Frame.of(np.full((4,), i, np.float32)))
    src.end_of_stream()
    got = []
    while True:
        f = sink.pull(timeout=5)
        if f is None:
            break
        got.append(int(f.tensor(0)[0]))
    p.wait(5)
    p.stop()
    assert got == [0, 1, 2]


def test_parse_launch_linear():
    p = parse_launch(
        "videotestsrc num-buffers=3 width=32 height=32 ! "
        "tensor_converter ! tensor_sink name=out collect=true"
    )
    p.run(timeout=10)
    out = p["out"]
    assert out.num_frames == 3
    assert out.frames[0].tensor(0).shape == (32, 32, 3)


def test_parse_launch_named_branches():
    p = parse_launch(
        "videotestsrc num-buffers=2 width=16 height=16 ! tee name=t "
        "t. ! queue ! tensor_sink name=a collect=true "
        "t. ! queue ! tensor_sink name=b collect=true"
    )
    p.run(timeout=10)
    assert p["a"].num_frames == 2
    assert p["b"].num_frames == 2


def test_to_dot():
    p = parse_launch("videotestsrc num-buffers=1 ! tensor_sink name=out")
    p.start()
    dot = p.to_dot()
    p.wait(5)
    p.stop()
    assert "digraph" in dot and "out" in dot
