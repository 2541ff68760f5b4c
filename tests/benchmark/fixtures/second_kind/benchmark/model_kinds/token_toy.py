"""Model kind ``token_toy`` (tests only): a model that is no ViT.  One frame
is ``[seq]`` int32 token ids; embedding -> mean over the sequence -> linear
-> a ``num_classes``-wide logits row.  It exists so that the harness's tests
add a second kind of model by files alone; it is no cell of the benchmark.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

BYTES_PER_VALUE = 4  # float32, the dtype the toy's configuration states


def sizes(cfg: Dict[str, Any], rehearsal: bool = False) -> Dict[str, int]:
    s = dict(cfg["build"])
    if rehearsal:
        s.update(cfg["rehearsal"])
    return s


def frame_shape(s: Dict[str, int]) -> Tuple[int]:
    return (s["seq"],)


def frame_flops(s: Dict[str, int]) -> Dict[str, float]:
    """The mean's ``seq x width`` adds and the linear layer's multiply-adds."""
    mix = s["seq"] * s["width"]
    linear = 2 * s["width"] * s["num_classes"]
    return {"mix": float(mix), "linear": float(linear),
            "total": float(mix + linear)}


def mix_work(s: Dict[str, int]) -> Dict[str, float]:
    """The mean over the sequence for one frame: ``seq x width`` adds, the
    gathered rows read once and one row written."""
    return {"flops": float(s["seq"] * s["width"]),
            "bytes": float((s["seq"] + 1) * s["width"] * BYTES_PER_VALUE)}


def marks(s: Dict[str, int]) -> Dict[str, Dict[str, list]]:
    """``mix``: the ops that read the gathered ``[..., seq, width]`` rows, or
    a kernel named ``toy_mix`` should one ever take their place."""
    return {"mix": {"names": ["toy_mix"], "dims": [[s["seq"], s["width"]]]}}


def param_count(s: Dict[str, int]) -> int:
    return (s["vocab"] * s["width"] + s["width"] * s["num_classes"]
            + s["num_classes"])


def init_weights(s: Dict[str, int], seed: int):
    """Host float32 arrays from ``seed``, in a pytree of the toy's own."""
    import numpy as np

    rng = np.random.default_rng(seed)

    def normal(shape, std):
        return (rng.standard_normal(shape, dtype=np.float32)
                * np.float32(std))

    return {"tokens": {"table": normal((s["vocab"], s["width"]), 1.0)},
            "out": (normal((s["width"], s["num_classes"]), s["width"] ** -0.5),
                    normal((s["num_classes"],), 0.1))}


def build_program(s: Dict[str, int], weights, batch: int, control: bool = False):
    """The system under test: a ``JaxModel`` over ``[batch, seq]`` int32
    frames.  ``control=True`` computes in bfloat16, the step below the
    float32 the configuration states."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nnstreamer_tpu.backends.jax_backend import JaxModel
    from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

    dtype = jnp.bfloat16 if control else jnp.float32

    def fwd(p, ids):
        p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), p)
        kernel, offset = p["out"]
        mixed = p["tokens"]["table"][ids].mean(axis=-2)
        return (mixed @ kernel + offset).astype(jnp.float32)

    return JaxModel(
        apply=fwd, params=weights, name="token_toy",
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.int32,
                                             shape=(batch, s["seq"]))))
