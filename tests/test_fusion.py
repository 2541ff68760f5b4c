"""Transform-fusion tests: transform chains fold into the jax filter's XLA
program (the north-star fusion requirement)."""

import numpy as np
import pytest

import jax.numpy as jnp

from nnstreamer_tpu import Pipeline
from nnstreamer_tpu.backends.jax_backend import JaxModel
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.elements.testsrc import DataSrc
from nnstreamer_tpu.elements.transform import TensorTransform
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec


def _model(shape=(4,)):
    return JaxModel(
        apply=lambda p, x: x * 10.0,
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.float32, shape=shape)),
    )


def test_pre_transform_fuses_and_matches_golden(rng):
    x = rng.integers(0, 255, (4,), dtype=np.uint8)
    p = Pipeline()
    src = p.add(DataSrc(data=[x]))
    tr = p.add(TensorTransform(
        mode="arithmetic", option="typecast:float32,add:-127.5,div:127.5"
    ))
    filt = p.add(TensorFilter(framework="jax", model=_model()))
    sink = p.add(TensorSink(collect=True))
    p.link_chain(src, tr, filt, sink)
    p.run(timeout=60)
    # transform node was absorbed into the filter
    assert tr.name not in p.nodes
    assert len(filt._fused_pre) == 1
    golden = (x.astype(np.float32) - 127.5) / 127.5 * 10.0
    np.testing.assert_allclose(
        np.asarray(sink.frames[0].tensor(0)), golden, rtol=1e-5
    )
    # the filter's sink pad negotiated the RAW uint8 spec: only raw bytes
    # cross host→device
    assert filt.sink_pads["sink"].spec.tensors[0].dtype == np.uint8


def test_pre_and_post_chains_fuse(rng):
    x = rng.integers(0, 255, (4,), dtype=np.uint8)
    p = Pipeline()
    src = p.add(DataSrc(data=[x]))
    t1 = p.add(TensorTransform(mode="typecast", option="float32", name="t1"))
    t2 = p.add(TensorTransform(mode="arithmetic", option="div:255.0", name="t2"))
    filt = p.add(TensorFilter(framework="jax", model=_model()))
    t3 = p.add(TensorTransform(mode="clamp", option="0.0:5.0", name="t3"))
    sink = p.add(TensorSink(collect=True))
    p.link_chain(src, t1, t2, filt, t3, sink)
    p.run(timeout=60)
    assert len(filt._fused_pre) == 2 and len(filt._fused_post) == 1
    assert all(n not in p.nodes for n in ("t1", "t2", "t3"))
    golden = np.clip(x.astype(np.float32) / 255.0 * 10.0, 0.0, 5.0)
    np.testing.assert_allclose(
        np.asarray(sink.frames[0].tensor(0)), golden, rtol=1e-5
    )


def test_fusion_disabled_keeps_nodes(rng):
    x = rng.integers(0, 255, (4,), dtype=np.uint8)
    p = Pipeline()
    p.auto_fuse = False
    src = p.add(DataSrc(data=[x]))
    tr = p.add(TensorTransform(mode="typecast", option="float32"))
    filt = p.add(TensorFilter(framework="jax", model=_model()))
    sink = p.add(TensorSink(collect=True))
    p.link_chain(src, tr, filt, sink)
    p.run(timeout=60)
    assert tr.name in p.nodes
    assert not filt._fused_pre
    np.testing.assert_allclose(
        np.asarray(sink.frames[0].tensor(0)),
        x.astype(np.float32) * 10.0,
        rtol=1e-5,
    )


def test_host_transform_not_fused(rng):
    """acceleration=False transforms stay as host nodes."""
    x = rng.integers(0, 255, (4,), dtype=np.uint8)
    p = Pipeline()
    src = p.add(DataSrc(data=[x]))
    tr = p.add(TensorTransform(mode="typecast", option="float32", acceleration=False))
    filt = p.add(TensorFilter(framework="jax", model=_model()))
    sink = p.add(TensorSink(collect=True))
    p.link_chain(src, tr, filt, sink)
    p.run(timeout=60)
    assert tr.name in p.nodes
    assert not filt._fused_pre


def test_incompatible_fused_chain_fails(rng):
    from nnstreamer_tpu import NegotiationError

    x = rng.integers(0, 255, (4,), dtype=np.uint8)
    p = Pipeline()
    src = p.add(DataSrc(data=[x]))
    tr = p.add(TensorTransform(mode="typecast", option="int32"))  # model wants f32
    filt = p.add(TensorFilter(framework="jax", model=_model()))
    sink = p.add(TensorSink(collect=True))
    p.link_chain(src, tr, filt, sink)
    with pytest.raises(NegotiationError):
        p.start()
    p.stop()


def test_failed_start_restores_unfused_graph(rng):
    """A NegotiationError during start() must leave the user's graph intact
    (transforms restored, fusion uninstalled) so auto_fuse=False retry works."""
    from nnstreamer_tpu import NegotiationError

    x = rng.integers(0, 255, (4,), dtype=np.uint8)
    p = Pipeline()
    src = p.add(DataSrc(data=[x]))
    tr = p.add(TensorTransform(mode="typecast", option="int32", name="bad_tr"))
    filt = p.add(TensorFilter(framework="jax", model=_model()))
    sink = p.add(TensorSink(collect=True))
    p.link_chain(src, tr, filt, sink)
    with pytest.raises(NegotiationError):
        p.start()
    assert "bad_tr" in p.nodes           # transform restored
    assert not filt._fused_pre           # fusion uninstalled
    assert filt.sink_pads["sink"].peer.node is tr  # links restored
    p.stop()


def test_namedtuple_output_with_post_transform(rng):
    import collections

    Out = collections.namedtuple("Out", ["a", "b"])
    model = JaxModel(
        apply=lambda p, x: Out(x * 2.0, x + 1.0),
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.float32, shape=(4,))),
    )
    x = rng.integers(0, 255, (4,), dtype=np.uint8)
    p = Pipeline()
    src = p.add(DataSrc(data=[x]))
    t1 = p.add(TensorTransform(mode="typecast", option="float32"))
    filt = p.add(TensorFilter(framework="jax", model=model))
    t2 = p.add(TensorTransform(mode="clamp", option="0.0:100.0"))
    sink = p.add(TensorSink(collect=True))
    p.link_chain(src, t1, filt, t2, sink)
    p.run(timeout=60)
    f = sink.frames[0]
    np.testing.assert_allclose(
        np.asarray(f.tensor(0)), np.clip(x * 2.0, 0, 100), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(f.tensor(1)), np.clip(x + 1.0, 0, 100), rtol=1e-5
    )


def test_fused_input_property_still_enforced(rng):
    """input= describes the MODEL input; fusion must not skip the check
    (regression: _install_fusion used to ignore _prop_in)."""
    from nnstreamer_tpu import NegotiationError, PipelineError

    x = rng.integers(0, 255, (4,), dtype=np.uint8)
    p = Pipeline()
    src = p.add(DataSrc(data=[x]))
    tr = p.add(TensorTransform(mode="typecast", option="float32"))
    filt = p.add(TensorFilter(
        framework="jax", model=_model(), input="8", inputtype="float32"
    ))
    sink = p.add(TensorSink(collect=True))
    p.link_chain(src, tr, filt, sink)
    with pytest.raises((NegotiationError, PipelineError)):
        p.start()
    # failed start restored the spliced-out transform
    assert tr.name in p.nodes
