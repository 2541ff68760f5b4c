"""Model kind ``vit``: the repo's ViT classifier at a configuration's sizes.

What the harness needs of a model, in one place: the weights (made here
from a seed, on the host, in the served type), the
program under test built from them through ``nnstreamer_tpu.models.vit.build``
unchanged, its lower-precision control (the program's own W8A8 path), and
the work functions — operations and bytes from the configuration's shapes,
never from ``cost_analysis()``.  The work counted is the algorithm's at the
configuration's dtype, whatever implements it: attention's ``T x T`` scores
are not bytes the algorithm needs.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

BYTES_PER_VALUE = 2  # bf16, the dtype both configurations state


def sizes(cfg: Dict[str, Any], rehearsal: bool = False) -> Dict[str, int]:
    """The ``vit.build`` arguments of ``cfg``; ``rehearsal`` swaps in the
    tiny widths kept beside them for the CPU control-flow run."""
    s = dict(cfg["build"])
    if rehearsal:
        s.update(cfg["rehearsal"])
    return s


def tokens(s: Dict[str, int]) -> int:
    return (s["image_size"] // s["patch"]) ** 2


def frame_shape(s: Dict[str, int]) -> Tuple[int, int, int]:
    return (s["image_size"], s["image_size"], 3)


def frame_flops(s: Dict[str, int]) -> Dict[str, float]:
    """FLOPs one frame needs (a multiply-add is 2).

    dense: per token and layer qkv 6d^2 + proj 2d^2 + ffn 16d^2 (d_ff = 4d),
    plus the patch embedding and the linear head, per token;
    attention: QK^T and PV, 4 T^2 d per layer.
    """
    t, d, layers = tokens(s), s["d_model"], s["n_layers"]
    d_in = s["patch"] * s["patch"] * 3
    dense = t * (layers * 24 * d * d + 2 * d_in * d + 2 * d * s["num_classes"])
    attention = layers * 4 * t * t * d
    return {"dense": float(dense), "attention": float(attention),
            "total": float(dense + attention)}


def attention_work(s: Dict[str, int]) -> Dict[str, float]:
    """Attention's work for one frame over all layers: 4 T^2 d FLOPs a layer,
    and q, k, v read and o written once at the configuration's dtype."""
    t, d, layers = tokens(s), s["d_model"], s["n_layers"]
    return {"flops": float(layers * 4 * t * t * d),
            "bytes": float(layers * 4 * t * d * BYTES_PER_VALUE)}


def marks(s: Dict[str, int]) -> Dict[str, Dict[str, list]]:
    """What marks an op of the trace as a part of this program, by label
    (``trace_reduce.carries``).  ``attention``: the fused kernel by its name,
    and on the plain path the ops that read or write a score array, whose
    trailing dims are ``T x T`` (``T`` is unique per configuration)."""
    t = tokens(s)
    return {"attention": {"names": ["nns_fused_attention"], "dims": [[t, t]]}}


def param_count(s: Dict[str, int]) -> int:
    t, d, layers = tokens(s), s["d_model"], s["n_layers"]
    d_in = s["patch"] * s["patch"] * 3
    per_layer = 12 * d * d + 13 * d  # qkv, proj, ff1, ff2 with biases, 2 LN
    return (layers * per_layer + d_in * d + d + t * d + 2 * d
            + d * s["num_classes"] + s["num_classes"])


def init_weights(s: Dict[str, int], seed: int):
    """The params pytree ``vit.build`` takes: bf16 numpy arrays on the host,
    as a deployment's ``np.load`` of a checkpoint leaves them, so that the
    device holds nothing of the harness's while the window runs and
    ``memory_peak_bytes`` is the program's alone.  One generator per block,
    spawned from ``seed``, the blocks drawn in a few threads (set-up pays it
    in every run).  Biases and LayerNorm gains are random too, so that a path
    that dropped one would show against the reference."""
    from concurrent.futures import ThreadPoolExecutor

    import ml_dtypes
    import numpy as np

    d, layers, classes = s["d_model"], s["n_layers"], s["num_classes"]
    d_in = s["patch"] * s["patch"] * 3
    t = tokens(s)

    def normal(rng, shape, std, mean=0.0):
        a = rng.standard_normal(shape, dtype=np.float32)
        a *= np.float32(std)
        if mean:
            a += np.float32(mean)
        return a.astype(ml_dtypes.bfloat16)

    def dense(rng, cin, cout):
        return {"w": normal(rng, (cin, cout), cin ** -0.5),
                "b": normal(rng, (cout,), 0.02)}

    def ln(rng):
        return {"scale": normal(rng, (d,), 0.1, 1.0),
                "bias": normal(rng, (d,), 0.05)}

    def block(seq):
        rng = np.random.default_rng(seq)
        return {"ln1": ln(rng), "qkv": dense(rng, d, 3 * d),
                "proj": dense(rng, d, d), "ln2": ln(rng),
                "ff1": dense(rng, d, 4 * d), "ff2": dense(rng, 4 * d, d)}

    outer, *per_block = np.random.SeedSequence(seed).spawn(layers + 1)
    with ThreadPoolExecutor(8) as pool:
        blocks = list(pool.map(block, per_block))
    rng = np.random.default_rng(outer)
    return {
        "embed": dense(rng, d_in, d),
        "blocks": blocks,
        "ln_f": ln(rng),
        "head": dense(rng, d, classes),
        "pos_embed": normal(rng, (t, d), 0.02),
        "n_heads": s["n_heads"],
    }


def build_program(s: Dict[str, int], weights, batch: int, control: bool = False):
    """The system under test: ``vit.build`` with this configuration's sizes
    around ``weights``.  ``control=True`` switches on the program's own
    lower-precision path (``build_quantized``, W8A8), the step below bf16
    that would tempt a later PR; it exists to be refused by the comparison."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models import vit

    build = vit.build_quantized if control else vit.build
    return build(
        num_classes=s["num_classes"], image_size=s["image_size"],
        patch=s["patch"], d_model=s["d_model"], n_heads=s["n_heads"],
        n_layers=s["n_layers"], attn=s["attn"], batch=batch,
        dtype=jnp.bfloat16, params=weights,
    )
