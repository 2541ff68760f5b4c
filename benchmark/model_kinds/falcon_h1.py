"""Model kind ``falcon_h1``: the repo's hybrid token model, a Mamba-2 mixer
beside grouped attention in every layer (``nnstreamer_tpu/models/
falcon_h1.py``), at a configuration's sizes.

A configuration's file holds the published ``config.json`` keys at its top
level as they are run (``num_hidden_layers`` cut, every width whole),
``build`` (``seq``, the window a frame holds, and ``layers``, the published
indices built) and ``rehearsal`` (what a CPU run overrides).  The weights are
made here from a seed, on the host, in the served type.  The work functions
count the algorithm's work from the shapes: the causal half of the scores,
the scan's three products a chunk (the Gram of C against B once a group),
the dense MLP.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

# at the top, so that a program without the model fails as the kind is
# imported and not after the weights are made
from nnstreamer_tpu.models import falcon_h1 as program
from nnstreamer_tpu.ops import ssm_scan

BYTES_PER_VALUE = 2  # bf16, the dtype the configuration states
HARNESS_KEYS = ("source", "published", "kind", "reference", "dtype",
                "weights_seed", "reduced", "assumed", "limits",
                "rehearsal_limits", "build", "rehearsal", "name",
                "deployment")


def sizes(cfg: Dict[str, Any], rehearsal: bool = False) -> Dict[str, Any]:
    """The model's ``config.json`` as run, with ``seq`` and ``layers``."""
    s = {k: v for k, v in cfg.items() if k not in HARNESS_KEYS}
    s.update(cfg["build"])
    if rehearsal:
        s.update(cfg["rehearsal"])
    return s


def frame_shape(s: Dict[str, Any]) -> Tuple[int]:
    return (s["seq"],)


def frame_flops(s: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs one frame needs (a multiply-add is 2): per layer the dense
    SwiGLU, the mixer's in- and out-projections, the attention's q, k, v
    and o, the score and value products over the causal half, the scan
    (:func:`ssm_scan_work`), the causal conv; the head at the last
    position."""
    t, d, w = s["seq"], s["hidden_size"], program.widths(s)
    n = len(s["layers"])
    hq = s["num_attention_heads"] * s["head_dim"]
    kv = s["num_key_value_heads"] * s["head_dim"]
    parts = {
        "dense_mlp": n * 6.0 * t * d * s["intermediate_size"],
        "mixer_projections": n * 2.0 * t * d * (w["in"] + w["d_ssm"]),
        "attention_projections": n * 2.0 * t * d * (2 * hq + 2 * kv),
        "attention": n * 4.0 * (t * (t + 1) // 2) * hq,
        "ssm_scan": ssm_scan_work(s)["flops"],
        "conv": n * 2.0 * t * s["mamba_d_conv"] * w["conv"],
        "head": 2.0 * d * s["vocab_size"],
    }
    return dict(parts, total=float(sum(parts.values())))


def ssm_scan_work(s: Dict[str, Any]) -> Dict[str, float]:
    """The scan of one frame, a token and layer at a time: the Gram of C
    against B once a group and chunk (``2 Q N`` a token and group), the
    masked Gram times X and the state's C product and update once a head
    (``2 Q P + 4 N P`` a token and head); x, B and C read and y written
    once in the served type, Δ read once in float32 (the conv and the gate
    run outside the kernel)."""
    t, n = s["seq"], len(s["layers"])
    q, states = s["mamba_chunk_size"], s["mamba_d_state"]
    heads, groups, p = (s["mamba_n_heads"], s["mamba_n_groups"],
                        s["mamba_d_head"])
    per_token = (2 * q * states * groups
                 + heads * (2 * q * p + 4 * states * p))
    values = 2 * heads * p + 2 * groups * states
    return {"flops": float(n * t * per_token),
            "bytes": float(n * t * (values * BYTES_PER_VALUE + 4 * heads))}


def marks(s: Dict[str, Any]) -> Dict[str, Dict[str, list]]:
    """``ssm_scan``: the kernel by its name, and on the plain path the ops
    that hold a chunk's per-head ``Q x Q`` decays or a chunk's per-head
    ``P x N`` states."""
    q = s["mamba_chunk_size"]
    heads = s["mamba_n_heads"]
    return {"ssm_scan": {"names": [ssm_scan.KERNEL_NAME],
                         "dims": [[heads, q, q],
                                  [heads, s["mamba_d_head"],
                                   s["mamba_d_state"]]]}}


def param_count(s: Dict[str, Any]) -> int:
    d, w = s["hidden_size"], program.widths(s)
    hq = s["num_attention_heads"] * s["head_dim"]
    kv = s["num_key_value_heads"] * s["head_dim"]
    per_layer = (2 * d                                       # two norms
                 + 2 * d * hq + 2 * d * kv                   # q, k, v, o
                 + d * w["in"] + w["d_ssm"] * d              # mixer in, out
                 + (s["mamba_d_conv"] + 1) * w["conv"]       # conv, bias
                 + 3 * s["mamba_n_heads"] + w["d_ssm"]       # A, D, Δ; norm
                 + 3 * d * s["intermediate_size"])           # SwiGLU
    return 2 * s["vocab_size"] * d + d + len(s["layers"]) * per_layer


def init_weights(s: Dict[str, Any], seed: int):
    """``models/falcon_h1``'s pytree: bf16 numpy arrays on the host, as a
    checkpoint's load leaves them.  The large arrays are drawn in slabs of
    at most 32 M values, each from a generator of its own spawned from
    ``seed``, a few slabs at a time in threads: matrices ``N(0, 1 /
    fan-in)``, the embedding ``N(0, 1)``, the norms' gains ``N(1, 0.1)``.
    The mixer's small arrays as Mamba-2 initialises them, drawn: the conv
    ``U(±1 / sqrt(taps))`` (weights and bias, torch's default), ``A_log =
    log U(1, 16)``, ``dt_bias`` the inverse softplus of a log-uniform Δ in
    [1e-3, 1e-1], ``D ~ U(0.5, 1.5)``."""
    from concurrent.futures import ThreadPoolExecutor

    import ml_dtypes
    import numpy as np

    d, dh, w = s["hidden_size"], s["head_dim"], program.widths(s)
    hq = s["num_attention_heads"] * dh
    kv = s["num_key_value_heads"] * dh
    heads, taps = s["mamba_n_heads"], s["mamba_d_conv"]
    gate_m, down_m = s["mlp_multipliers"]
    small = np.random.default_rng([seed, 0x55])
    bf16 = ml_dtypes.bfloat16
    jobs = []  # (array, rows, std, mean)

    def normal(shape, std, mean=0.0):
        a = np.empty(shape, bf16)
        flat = a.reshape(shape[0], -1) if len(shape) > 1 else a.reshape(1, -1)
        step = max(1, (32 << 20) // flat.shape[1])
        jobs.extend((flat, r, min(r + step, flat.shape[0]), std, mean)
                    for r in range(0, flat.shape[0], step))
        return a

    def matrix(*shape, after=1.0):
        return normal(shape, shape[-2] ** -0.5 / np.asarray(after, np.float32))

    def gain(n=d):
        return normal((n,), 0.1, 1.0)

    def uniform(lo, hi, *shape):
        return small.uniform(lo, hi, shape).astype(np.float32)

    layers = []
    for _ in s["layers"]:
        dt0 = np.exp(uniform(np.log(1e-3), np.log(1e-1), heads))
        bound = taps ** -0.5
        mamba = {"w_in": matrix(d, w["in"], after=s["ssm_in_multiplier"]
                                * program.mup_vector(s)),
                 "conv_w": uniform(-bound, bound, taps, w["conv"]).astype(bf16),
                 "conv_b": uniform(-bound, bound, w["conv"]).astype(bf16),
                 "dt_bias": (dt0 + np.log(-np.expm1(-dt0))).astype(bf16),
                 "A_log": np.log(uniform(1, 16, heads)).astype(bf16),
                 "D": uniform(0.5, 1.5, heads).astype(bf16),
                 "norm": gain(w["d_ssm"]),
                 "w_out": matrix(w["d_ssm"], d,
                                 after=s["ssm_out_multiplier"])}
        layers.append({
            "input_norm": gain(),
            "attn": {"wq": matrix(d, hq, after=s["attention_in_multiplier"]),
                     "wk": matrix(d, kv, after=s["attention_in_multiplier"]
                                  * s["key_multiplier"]),
                     "wv": matrix(d, kv, after=s["attention_in_multiplier"]),
                     "wo": matrix(hq, d,
                                  after=s["attention_out_multiplier"])},
            "mamba": mamba, "ff_norm": gain(),
            "mlp": {"w_in": matrix(d, 2 * s["intermediate_size"], after=np.repeat(
                        [gate_m, 1.0], s["intermediate_size"])),
                    "w_out": matrix(s["intermediate_size"], d, after=down_m)}})
    weights = {"embed": normal((s["vocab_size"], d),
                               1.0 / s["embedding_multiplier"]),
               "layers": layers, "norm": gain(),
               "head": matrix(d, s["vocab_size"],
                              after=s["lm_head_multiplier"])}

    def draw(job, seq):
        flat, r0, r1, std, mean = job
        a = np.random.default_rng(seq).standard_normal(
            (r1 - r0, flat.shape[1]), dtype=np.float32)
        a *= np.float32(std)
        if mean:
            a += np.float32(mean)
        flat[r0:r1] = a

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(draw, jobs, np.random.SeedSequence(seed).spawn(len(jobs))))
    return weights


def build_program(s: Dict[str, Any], weights, batch: int,
                  control: bool = False):
    """The system under test: ``falcon_h1.build`` over ``weights`` at this
    configuration's sizes.  ``control=True`` is the same program with the
    scan's carried state and decays in bfloat16 (``low``); it exists to be
    refused by the comparison."""
    import jax.numpy as jnp

    return program.build(config=s, seq=s["seq"], batch=batch,
                         dtype=jnp.bfloat16, params=weights, low=control)
