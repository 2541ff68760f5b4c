"""Model kind ``laguna``: the repo's decoder-only token model
(``nnstreamer_tpu/models/laguna.py``) at a configuration's sizes.

A configuration's file holds the published ``config.json`` keys at its top
level as they are run (``num_hidden_layers`` cut, every width and the
per-layer lists whole), ``build`` (``seq``, the window a frame holds, and
``token_chunk``, the tokens the expert layer takes at a time) and
``rehearsal`` (what a CPU run overrides).  The weights are made here from a
seed, on the host, in the served type; the work functions count the
algorithm's work from the shapes: 8 + 1 experts a token, the causal half of
a full layer's scores, the band of a sliding layer's.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

# at the top, so that a program without the model fails as the kind is
# imported and not after the weights are made
from nnstreamer_tpu.models import laguna as program

BYTES_PER_VALUE = 2  # bf16, the dtype the configuration states
# init_weights' decisive routers: how far along an expert's direction a
# token's embedding lies if the expert was drawn for it, and what a router
# reads of that direction
TOKEN_MARK = 16.0
ROUTER_GAIN = 0.5
HARNESS_KEYS = ("source", "published", "kind", "reference", "dtype",
                "weights_seed", "reduced", "assumed", "limits",
                "rehearsal_limits", "build", "rehearsal", "name")


def sizes(cfg: Dict[str, Any], rehearsal: bool = False) -> Dict[str, Any]:
    """The model's ``config.json`` as run, with ``seq`` and ``token_chunk``."""
    s = {k: v for k, v in cfg.items() if k not in HARNESS_KEYS}
    s.update(cfg["build"])
    if rehearsal:
        s.update(cfg["rehearsal"])
    return s


def frame_shape(s: Dict[str, Any]) -> Tuple[int]:
    return (s["seq"],)


def _layers(s):
    return range(s["num_hidden_layers"])


def _seen(s: Dict[str, Any], i: int) -> int:
    """(query, key) pairs layer ``i``'s mask lets through in one frame."""
    t = s["seq"]
    if s["layer_types"][i] == "sliding_attention":
        w = min(s["sliding_window"], t)
        return w * (w + 1) // 2 + (t - w) * w
    return t * (t + 1) // 2


def frame_flops(s: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs one frame needs (a multiply-add is 2): the q, k, v and output
    projections; layer 0's dense SwiGLU; per sparse layer the router, the
    ``num_experts_per_tok`` routed experts and the shared one a token; the
    score and value products over the pairs the mask lets through; the head
    at the last position."""
    t, d, dh = s["seq"], s["hidden_size"], s["head_dim"]
    kv = s["num_key_value_heads"] * dh
    proj = dense = experts = full = window = 0
    for i in _layers(s):
        hq = s["num_attention_heads_per_layer"][i] * dh
        proj += 2 * t * d * (2 * hq + 2 * kv)
        if s["mlp_layer_types"][i] == "dense":
            dense += 6 * t * d * s["intermediate_size"]
        else:
            experts += t * (2 * d * s["num_experts"]
                            + 6 * d * (s["num_experts_per_tok"]
                                       * s["moe_intermediate_size"]
                                       + s["shared_expert_intermediate_size"]))
        pairs = 4 * _seen(s, i) * hq
        if s["layer_types"][i] == "sliding_attention":
            window += pairs
        else:
            full += pairs
    head = 2 * d * s["vocab_size"]
    parts = {"projections": proj, "dense_mlp": dense, "experts": experts,
             "full_attention": full, "window_attention": window, "head": head}
    return dict({k: float(v) for k, v in parts.items()},
                total=float(sum(parts.values())))


def moe_work(s: Dict[str, Any]) -> Dict[str, float]:
    """The sparse layers of one frame: router, routed and shared experts'
    FLOPs; the tokens read and written once a layer.  (The experts' weights
    are read once a step whatever the batch, so they are no frame's bytes;
    at 65 536 tokens a step they are a tenth of the FLOPs' time.)"""
    sparse = sum(1 for i in _layers(s) if s["mlp_layer_types"][i] == "sparse")
    return {"flops": frame_flops(s)["experts"],
            "bytes": float(sparse * 2 * s["seq"] * s["hidden_size"]
                           * BYTES_PER_VALUE)}


def attention_work(s: Dict[str, Any]) -> Dict[str, float]:
    """Both kinds of attention layer of one frame: 4 x head width FLOPs a
    (query, key) pair the mask lets through; q, k, v read and o written
    once."""
    flops = frame_flops(s)
    dh = s["head_dim"]
    values = sum(2 * s["num_attention_heads_per_layer"][i]
                 + 2 * s["num_key_value_heads"] for i in _layers(s))
    return {"flops": flops["full_attention"] + flops["window_attention"],
            "bytes": float(s["seq"] * values * dh * BYTES_PER_VALUE)}


mixed_attention_work = attention_work


def marks(s: Dict[str, Any]) -> Dict[str, Dict[str, list]]:
    """``attention``: the blocked kernel by its name, and on the plain
    path the ops that hold a ``T x T`` array.  ``moe``: the ops that read or
    write the expert layer's own arrays, a chunk of ``c`` tokens at a time:
    its ``c x k`` (token, expert) pairs' rows at the model's, the experts'
    and the gated width, the shared expert's hidden rows, and the router's
    ``c x experts`` scores and ``c x k`` choice."""
    t = s["seq"]
    c = s["token_chunk"]
    pairs = c * s["num_experts_per_tok"]
    f = s["moe_intermediate_size"]
    fs = s["shared_expert_intermediate_size"]
    dims = [[pairs, s["hidden_size"]], [pairs, 2 * f], [pairs, f], [pairs],
            [c, 2 * fs], [c, fs], [c, s["num_experts"]],
            [c, s["num_experts_per_tok"]],
            [c, s["num_experts_per_tok"], s["hidden_size"]]]
    attention = {"names": ["nns_blocked_attention"], "dims": [[t, t]]}
    # ``mixed_attention``: the same ops under the name of ISSUE 34's metric
    # (the harness ties ``<label>_roofline`` to its label); the ``benchmark``
    # PR that appends the cell to ``attention_roofline`` drops it
    return {"moe": {"names": ["ragged-dot"], "dims": dims},
            "attention": attention, "mixed_attention": attention}


def param_count(s: Dict[str, Any]) -> int:
    d, dh = s["hidden_size"], s["head_dim"]
    kv = s["num_key_value_heads"] * dh
    n = 2 * s["vocab_size"] * d + d
    for i in _layers(s):
        hq = s["num_attention_heads_per_layer"][i] * dh
        n += 2 * d + 2 * d * hq + 2 * d * kv
        if s["mlp_layer_types"][i] == "dense":
            n += 3 * d * s["intermediate_size"]
        else:
            n += (d * s["num_experts"]
                  + 3 * d * (s["num_experts"] * s["moe_intermediate_size"]
                             + s["shared_expert_intermediate_size"]))
    return n


def init_weights(s: Dict[str, Any], seed: int):
    """``models/laguna``'s pytree: bf16 numpy arrays on the host, as a
    checkpoint's load leaves them.  Every array is drawn in slabs of at most
    32 M values, each from a generator of its own spawned from ``seed``, a
    few slabs at a time in threads (set-up pays it in every run): matrices
    ``N(0, 1 / fan-in)``, the embedding ``N(0, 1)``, the norms' gains ``N(1,
    0.1)``, so that a path that dropped a gain would show.

    The routers are decisive, as a trained router is and a random one is
    not: expert ``e`` has a direction ``r_e`` of ``+-1 / sqrt(hidden)``, the
    same in every layer; its score reads ``ROUTER_GAIN`` of the stream along
    it (``N(0, 1e-6)`` beside), and token ``v``'s embedding lies
    ``TOKEN_MARK`` along the directions of the ``num_experts_per_tok``
    experts drawn for it from the seed.  The choice differs from token to
    token and loads the experts evenly, between the last chosen score and
    the first left out lie about four deviations of everything the layers
    add, and no coordinate of the stream stands out: a mark is ``16 *
    sqrt(8 / hidden)`` = 1.0 a coordinate beside the embedding's ``N(0,
    1)``, so a lower precision's activation scales see an ordinary stream
    (marks on 8 single coordinates made the W8A8 control's first projection
    err 2.5 times more than on a clean stream, PERF.md).  With ``N(0, 1 /
    fan-in)`` routers some 1 in 10 (token, layer) pairs have those two
    scores within bfloat16's rounding of the stream; each such pair at a
    frame's last position moves ``logit_err`` by 0.1-0.4, more than the
    step from bfloat16 to the W8A8 control does, and no limit could tell
    them apart (PERF.md, Findings)."""
    from concurrent.futures import ThreadPoolExecutor

    import ml_dtypes
    import numpy as np

    d, dh = s["hidden_size"], s["head_dim"]
    kv = s["num_key_value_heads"] * dh
    e, k = s["num_experts"], s["num_experts_per_tok"]
    jobs = []  # (array, rows, std, mean, what is added to the rows)
    directions = np.random.default_rng([seed, 0xD]).choice(
        np.array([-1, 1], np.float32), (e, d)) / np.float32(d ** 0.5)
    drawn = np.random.default_rng([seed, 0xE]).random(
        (s["vocab_size"], e), dtype=np.float32)
    chosen = np.argpartition(drawn, k, axis=1)[:, :k]  # a token's experts

    def token_marks(r0, r1):  # a matrix product: it lets the other threads run
        along = np.zeros((r1 - r0, e), np.float32)
        np.put_along_axis(along, chosen[r0:r1], np.float32(TOKEN_MARK), 1)
        return along @ directions

    def normal(shape, std, mean=0.0, plus=None):
        a = np.empty(shape, ml_dtypes.bfloat16)
        flat = a.reshape(shape[0], -1) if len(shape) > 1 else a.reshape(1, -1)
        step = max(1, (32 << 20) // flat.shape[1])
        jobs.extend((flat, r, min(r + step, flat.shape[0]), std, mean, plus)
                    for r in range(0, flat.shape[0], step))
        return a

    def matrix(*shape):
        return normal(shape, shape[-2] ** -0.5)

    def glu(width, lead=()):
        return {"w_in": matrix(*lead, d, 2 * width),
                "w_out": matrix(*lead, width, d)}

    layers = []
    for i in _layers(s):
        hq = s["num_attention_heads_per_layer"][i] * dh
        p = {"attn_norm": normal((d,), 0.1, 1.0), "wq": matrix(d, hq),
             "wk": matrix(d, kv), "wv": matrix(d, kv), "wo": matrix(hq, d),
             "mlp_norm": normal((d,), 0.1, 1.0)}
        if s["mlp_layer_types"][i] == "dense":
            p["mlp"] = glu(s["intermediate_size"])
        else:
            p["moe"] = dict(glu(s["moe_intermediate_size"],
                                (s["num_experts"],)),
                            router=normal(
                                (d, e), 1e-3, plus=lambda r0, r1:
                                ROUTER_GAIN * directions.T[r0:r1]),
                            shared=glu(s["shared_expert_intermediate_size"]))
        layers.append(p)
    weights = {"embed": normal((s["vocab_size"], d), 1.0, plus=token_marks),
               "layers": layers,
               "norm": normal((d,), 0.1, 1.0),
               "head": matrix(d, s["vocab_size"])}

    def draw(job, seq):
        flat, r0, r1, std, mean, plus = job
        a = np.random.default_rng(seq).standard_normal(
            (r1 - r0, flat.shape[1]), dtype=np.float32)
        a *= np.float32(std)
        if mean:
            a += np.float32(mean)
        if plus is not None:
            a += plus(r0, r1)
        flat[r0:r1] = a

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(draw, jobs, np.random.SeedSequence(seed).spawn(len(jobs))))
    return weights


def build_program(s: Dict[str, Any], weights, batch: int,
                  control: bool = False):
    """The system under test: ``laguna.build`` over ``weights`` at this
    configuration's sizes.  ``control=True`` is the program's own step below
    bfloat16 (``build_quantized``: W8A8 on the projections, the dense and
    shared MLPs and the head); it exists to be refused by the comparison."""
    import jax.numpy as jnp

    build = program.build_quantized if control else program.build
    return build(config=s, seq=s["seq"], batch=batch, dtype=jnp.bfloat16,
                 params=weights, token_chunk=s["token_chunk"])
