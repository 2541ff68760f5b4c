"""Weights are arguments of every program the jax backend builds: no
program holds them as constants, programs do not depend on their values,
and what a model computes is what it computed as a closure."""

import re

import jax
import jax.numpy as jnp
import numpy as np
from nnstreamer_tpu.backends import exec_cache
from nnstreamer_tpu.backends.jax_backend import (JaxBackend, JaxModel,
                                                 split_params)
from nnstreamer_tpu.models import vit
from nnstreamer_tpu.obs.metrics import REGISTRY
from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

VIT = dict(num_classes=10, image_size=28, patch=7, d_model=64, n_heads=2,
           n_layers=2, batch=3)


def opened(model):
    be = JaxBackend()
    be.open(model)
    be.reconfigure(model.input_spec)
    return be


def flat_text(be):
    spec = be.input_spec()
    structs = tuple(jax.ShapeDtypeStruct(w, t.dtype)
                    for w, t in zip(be._wire_shapes, spec.tensors))
    return be._flat_compiled.lower(*structs).as_text()


def largest_constant(text):
    """Elements of the largest literal tensor in a StableHLO module."""
    sizes = [int(np.prod([int(n) for n in dims.split("x") if n.isdigit()] or [1]))
             for dims in re.findall(r"stablehlo\.constant dense<[^>]*> : "
                                    r"tensor<([0-9x]*)x?[a-z]", text)]
    return max(sizes, default=0)


def test_no_program_holds_a_weight_sized_constant():
    model = vit.build(**VIT)
    be = opened(model)
    smallest = min(int(np.prod(w.shape)) for w in be._weights if w.ndim == 2)
    for text in (flat_text(be),
                 be._compiled.lower(*[jax.ShapeDtypeStruct(tuple(t.shape), t.dtype)
                                      for t in be.input_spec().tensors]).as_text()):
        assert "stablehlo.constant" in text  # there are literals (eps, scales)
        assert largest_constant(text) < smallest
    # while the closure a caller outside the backend may still ask for does
    x = jnp.zeros((3, 28, 28, 3), jnp.float32)
    assert largest_constant(jax.jit(model.fn()).lower(x).as_text()) >= smallest


def test_static_leaves_stay_static_and_arrays_become_arguments():
    params = {"w": np.ones((4, 3), np.float32), "n_heads": 2,
              "scale": 0.5, "nested": [jnp.zeros((3,)), "tag", None]}
    arrays, merge = split_params(params)
    assert sorted(a.shape for a in arrays) == [(3,), (4, 3)]
    back = merge([a + 1 for a in arrays])
    assert back["n_heads"] == 2 and back["scale"] == 0.5
    assert back["nested"][1] == "tag" and back["nested"][2] is None
    assert float(np.asarray(back["w"]).sum()) == 24.0


def test_new_weights_of_equal_shapes_are_the_same_program():
    a = opened(vit.build(seed=1, **VIT))
    b = opened(vit.build(seed=2, **VIT))
    assert any(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a._weights, b._weights))
    ta, tb = flat_text(a), flat_text(b)
    assert ta == tb
    structs = tuple(jax.ShapeDtypeStruct(w, t.dtype) for w, t in
                    zip(a._wire_shapes, a.input_spec().tensors))
    assert (exec_cache.fingerprint_lowered(a._flat_compiled.lower(*structs))
            == exec_cache.fingerprint_lowered(b._flat_compiled.lower(*structs)))


def test_new_weights_of_equal_shapes_do_not_compile_again(tmp_path):
    """The second model's programs come out of jax's compilation cache:
    their key no longer depends on what the weights hold."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    hits = []

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax.monitoring.register_event_listener(on_event)
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs,
           jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        x = np.zeros((3, 28, 28, 3), np.float32)
        be = JaxBackend()
        be.open(vit.build(seed=3, **VIT))
        be._mesh_config = lambda: (None, "dp")
        first = np.asarray(be.invoke((x + 1,))[0])
        before = len(hits)
        be2 = JaxBackend()
        be2.open(vit.build(seed=4, **VIT))
        be2._mesh_config = lambda: (None, "dp")
        second = np.asarray(be2.invoke((x + 1,))[0])
        assert len(hits) > before
        assert not np.array_equal(first, second)
    finally:
        jax.config.update("jax_compilation_cache_dir", old[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", old[2])
        cc.reset_cache()


def test_the_vits_logits_are_bit_equal_to_the_closures():
    model = vit.build(**VIT)
    x = np.random.default_rng(0).standard_normal((3, 28, 28, 3)).astype(np.float32)
    be = opened(model)
    got = np.asarray(be.invoke((x,))[0])
    want = np.asarray(jax.jit(model.fn())(x))
    assert got.dtype == want.dtype and np.array_equal(got, want)


def test_a_fused_transform_and_the_row_path_run_over_arguments():
    """The wrapper (transform fusion) is applied inside the program, around
    the model over its arguments; the per-row executable takes them too."""
    model = JaxModel(apply=lambda p, x: x @ p["w"] + p["b"],
                     params={"w": np.arange(12, dtype=np.float32).reshape(4, 3),
                             "b": np.ones((3,), np.float32)},
                     input_spec=TensorsSpec.of(TensorSpec(dtype=np.float32,
                                                          shape=(2, 4))))
    be = JaxBackend()
    be.open(model)
    be.set_wrapper(lambda fn: lambda x: fn(x * 2.0) + 1.0)
    be.reconfigure_fused(model.input_spec)
    x = np.ones((2, 4), np.float32)
    want = (x * 2) @ model.params["w"] + model.params["b"] + 1
    np.testing.assert_allclose(np.asarray(be.invoke((x,))[0]), want)
    assert tuple(be.output_spec().tensors[0].shape) == (2, 3)


def test_opening_a_model_uploads_its_weights_once_and_says_so():
    hist = REGISTRY.get("nnstpu_weights_upload_seconds")
    count = lambda: sum(c.count for _, c in hist.children()) if hist else 0
    before = count()
    model = vit.build(**VIT)
    be = opened(model)
    hist = REGISTRY.get("nnstpu_weights_upload_seconds")
    assert count() == before + 1
    be.warm_compile(TensorsSpec.of(TensorSpec(dtype=np.float32,
                                              shape=(5, 28, 28, 3))))
    assert count() == before + 1  # a second geometry reuses the placement
    gauge = REGISTRY.get("nnstpu_weights_device_bytes")
    held = dict(gauge.children())[(model.name,)]
    assert held.value == sum(int(w.nbytes) for w in be._weights)
    assert all(isinstance(w, jax.Array) for ws in be._placed.values() for w in ws)
    be.close()
    assert be._placed == {} and be._weights == []
