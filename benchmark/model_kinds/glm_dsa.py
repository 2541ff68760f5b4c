"""Model kind ``glm_dsa``: the repo's latent-attention token model with a
learned key selection (``nnstreamer_tpu/models/glm_dsa.py``) at a
configuration's sizes.

A configuration's file holds the published ``config.json`` keys at its top
level as they are run: every width and every per-layer list whole, the depth
cut to the layers ``build.layers`` names (published indices into the
per-layer lists), ``n_routed_experts`` the experts *held here* and
``vocab_size`` the rows held here.  ``build`` also gives ``router_experts``
(the published expert count, which the router keeps) and ``first_expert``
(where this chip's share starts), ``seq`` (the window a frame holds) and
``token_chunk`` (the tokens the expert layer takes at a time); ``rehearsal``
is what a CPU run overrides.  ``sizes`` hands the program and the reference
one dict in the program's keys: ``n_routed_experts`` the router's width,
``experts_held`` = ``[first, count]``, ``layers``.

The weights are made here from a seed, on the host, in the served type and
in a checkpoint's layout.  The work functions count the algorithm's work
from the shapes: the score and value products over the *selected* keys, the
indexer's causal scores (its projections in ``frame_flops`` alone), 8 of
256 experts a token of which the share held here, and the shared expert.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

# at the top, so that a program without the model fails as the kind is
# imported and not after the weights are made
from nnstreamer_tpu.models import glm_dsa as program
from nnstreamer_tpu.ops import sparse_attention
from nnstreamer_tpu.parallel import moe

BYTES_PER_VALUE = 2  # bf16, the dtype the configuration states
# init_weights' decisive routers (as the ``laguna`` kind's): how far along
# an expert's direction a token's embedding lies if the expert was drawn for
# it, what a router reads of that direction, and the deviation of the
# selection bias beside a chosen score's ~1 and an unchosen one's ~0.5
TOKEN_MARK = 16.0
ROUTER_GAIN = 0.5
BIAS_STD = 0.02
HARNESS_KEYS = ("source", "published", "kind", "reference", "dtype",
                "weights_seed", "reduced", "assumed", "limits",
                "rehearsal_limits", "build", "rehearsal", "name",
                "deployment")


def sizes(cfg: Dict[str, Any], rehearsal: bool = False) -> Dict[str, Any]:
    """The model's ``config.json`` as the program reads it, with ``seq`` and
    ``token_chunk``."""
    s = {k: v for k, v in cfg.items() if k not in HARNESS_KEYS}
    s.update(cfg["build"])
    if rehearsal:
        s.update(cfg["rehearsal"])
    s["experts_held"] = [s.pop("first_expert"), s["n_routed_experts"]]
    s["n_routed_experts"] = s.pop("router_experts")
    return s


def frame_shape(s: Dict[str, Any]) -> Tuple[int]:
    return (s["seq"],)


def _full(s, i) -> bool:
    return s["indexer_types"][i] == "full"


def _sparse(s, i) -> bool:
    return s["mlp_layer_types"][i] == "sparse"


def _selected_pairs(s: Dict[str, Any]) -> int:
    """(query, key) pairs one layer's softmax runs over in one frame: query
    ``t`` keeps ``min(t + 1, index_topk)`` keys."""
    t, k = s["seq"], min(s["index_topk"], s["seq"])
    return k * (k + 1) // 2 + (t - k) * k


def _attention_params(s) -> int:
    d, h = s["hidden_size"], s["num_attention_heads"]
    rq, rkv, dr = s["q_lora_rank"], s["kv_lora_rank"], s["qk_rope_head_dim"]
    return (d * rq + rq * h * (s["qk_nope_head_dim"] + dr) + d * (rkv + dr)
            + rkv * h * (s["qk_nope_head_dim"] + s["v_head_dim"])
            + h * s["v_head_dim"] * d)


def _indexer_params(s) -> int:
    return (s["q_lora_rank"] * s["index_n_heads"] * s["index_head_dim"]
            + s["hidden_size"] * (s["index_head_dim"] + s["index_n_heads"]))


def frame_flops(s: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs one frame needs (a multiply-add is 2): the latent attention's
    five projections; per ``full`` layer the indexer's projections and its
    scores over the causal pairs; the score and value products over the
    selected keys alone; the dense SwiGLU; per sparse layer the router, the
    shared expert and the share of a token's ``num_experts_per_tok`` routed
    experts that an even routing sends to the experts held here; the head
    at the last position."""
    t, d = s["seq"], s["hidden_size"]
    heads = s["num_attention_heads"]
    held = s["experts_held"][1] / s["n_routed_experts"]
    glu = 6 * d * s["moe_intermediate_size"]
    proj = index = attend = dense = experts = 0.0
    for i in s["layers"]:
        proj += 2 * t * _attention_params(s)
        if _full(s, i):
            index += (2 * t * _indexer_params(s) + t * (t + 1)
                      * s["index_n_heads"] * s["index_head_dim"])
        attend += 2 * _selected_pairs(s) * heads * (
            s["qk_nope_head_dim"] + s["qk_rope_head_dim"] + s["v_head_dim"])
        if _sparse(s, i):
            experts += t * (2 * d * s["n_routed_experts"]
                            + glu * (s["num_experts_per_tok"] * held
                                     + s["n_shared_experts"]))
        else:
            dense += 6 * t * d * s["intermediate_size"]
    parts = {"projections": proj, "indexer": index,
             "sparse_attention": attend, "dense_mlp": dense,
             "experts": experts, "head": 2.0 * d * s["vocab_size"]}
    return dict(parts, total=float(sum(parts.values())))


def sparse_attention_work(s: Dict[str, Any]) -> Dict[str, float]:
    """The selected attention of one frame: the score and value products
    over the selected keys alone, whatever computes them (a pass over every
    causal key under a mask reads low); q, the latents and o once a
    layer."""
    values = s["num_attention_heads"] * (
        s["qk_nope_head_dim"] + s["qk_rope_head_dim"] + s["v_head_dim"]) \
        + s["kv_lora_rank"] + s["qk_rope_head_dim"]
    return {"flops": frame_flops(s)["sparse_attention"],
            "bytes": float(len(s["layers"]) * s["seq"] * values
                           * BYTES_PER_VALUE)}


def indexer_work(s: Dict[str, Any]) -> Dict[str, float]:
    """The ``full`` layers' scoring and selection of one frame, which is
    what the ``indexer`` mark finds (the kernel; on the plain path the ops
    over a block's scores): the causal scores' products alone, the
    selection's comparisons counted as none; the indexer's q and k and the
    float32 head weights read, the ``T x T`` one-byte selection written.
    The indexer's three projections are ops of their own among the layer's
    other projections: ``frame_flops`` counts them, this does not."""
    t = s["seq"]
    heads, width = s["index_n_heads"], s["index_head_dim"]
    full = sum(1 for i in s["layers"] if _full(s, i))
    return {"flops": float(full * t * (t + 1) * heads * width),
            "bytes": float(full * (t * ((heads + 1) * width * BYTES_PER_VALUE
                                        + heads * 4) + t * t))}


def held_experts_work(s: Dict[str, Any]) -> Dict[str, float]:
    """The sparse layers of one frame: router, the held experts' pairs and
    the shared expert; the tokens read and written once a layer (the
    weights are no frame's bytes, as in the ``laguna`` kind's ``moe``)."""
    sparse = sum(1 for i in s["layers"] if _sparse(s, i))
    return {"flops": frame_flops(s)["experts"],
            "bytes": float(sparse * 2 * s["seq"] * s["hidden_size"]
                           * BYTES_PER_VALUE)}


def marks(s: Dict[str, Any]) -> Dict[str, Dict[str, list]]:
    """``sparse_attention``: the kernel by its name, and on the plain path
    the ops that hold all heads' scores of a block of query rows.
    ``indexer``: the selection kernel by its name, and on the plain path
    the ops that hold a block's per-head scores (not the summed ``rows x
    T`` ones: at the published sizes those are the dims of the value
    up-projection's weight).  ``held_experts``: ``ragged-dot`` by name and
    the ops over the expert layer's own arrays, a chunk of ``c`` tokens at a
    time, as the ``laguna`` kind marks ``moe``.  No mark names an array that
    a loop carries whole (the token chunks, the pairs' order, a chunk's
    ``c x k`` choice), or the loop would count beside its body."""
    t, c = s["seq"], s["token_chunk"]
    k, d = s["num_experts_per_tok"], s["hidden_size"]
    f = s["moe_intermediate_size"]
    rows = t // sparse_attention.row_blocks(t, sparse_attention.SELECT_ROWS)
    dims = [[c * k, d], [c, 2 * f * s["n_shared_experts"]],
            [c, f * s["n_shared_experts"]], [c, s["n_routed_experts"]],
            [c, k, d]]
    passed = moe.share_rows(c * k, s["experts_held"][1],
                            s["n_routed_experts"])
    if passed != c:
        dims += [[passed, d], [passed, 2 * f], [passed, f]]
    return {"sparse_attention": {
                "names": [sparse_attention.KERNEL_NAME],
                "dims": [[s["num_attention_heads"], rows, t]]},
            "indexer": {"names": [sparse_attention.INDEX_KERNEL_NAME],
                        "dims": [[s["index_n_heads"], t]]},
            "held_experts": {"names": ["ragged-dot"], "dims": dims}}


def param_count(s: Dict[str, Any]) -> int:
    d = s["hidden_size"]
    glu = 3 * d * s["moe_intermediate_size"]
    n = 2 * s["vocab_size"] * d + d
    for i in s["layers"]:
        n += (_attention_params(s) + 2 * d + s["q_lora_rank"]
              + s["kv_lora_rank"])
        if _full(s, i):
            n += _indexer_params(s) + 2 * s["index_head_dim"]
        if _sparse(s, i):
            n += ((d + 1) * s["n_routed_experts"]
                  + glu * (s["experts_held"][1] + s["n_shared_experts"]))
        else:
            n += 3 * d * s["intermediate_size"]
    return n


def init_weights(s: Dict[str, Any], seed: int):
    """``models/glm_dsa``'s pytree in a checkpoint's layout: bf16 numpy
    arrays on the host.  Every array is drawn in slabs of at most 32 M
    values, each from a generator of its own spawned from ``seed``, a few
    slabs at a time in threads: matrices ``N(0, 1 / fan-in)``, the embedding
    ``N(0, 1)``, the norms' gains ``N(1, 0.1)`` (the indexer's LayerNorm
    bias ``N(0, 0.1)``), so that a path that dropped one would show.

    The routers are decisive, as the ``laguna`` kind's and for its reason
    (a near-tie at the 8th score moves a frame's ``logit_err`` by more than
    the step to the control does): expert ``e`` of the router's
    ``n_routed_experts`` has a direction of ``+-1 / sqrt(hidden)``, the same
    in every layer, its score reads ``ROUTER_GAIN`` of the stream along it,
    and a token's embedding lies ``TOKEN_MARK`` along the directions of the
    ``num_experts_per_tok`` experts drawn for it, held here or not.  The
    selection bias is ``N(0, BIAS_STD)``: non-zero, and small beside the
    distance between a chosen score and the next."""
    from concurrent.futures import ThreadPoolExecutor

    import ml_dtypes
    import numpy as np

    d, heads = s["hidden_size"], s["num_attention_heads"]
    rq, rkv = s["q_lora_rank"], s["kv_lora_rank"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    e, k = s["n_routed_experts"], s["num_experts_per_tok"]
    held = s["experts_held"][1]
    jobs = []  # (array, rows, std, mean, what is added to the rows)
    directions = np.random.default_rng([seed, 0xD]).choice(
        np.array([-1, 1], np.float32), (e, d)) / np.float32(d ** 0.5)
    drawn = np.random.default_rng([seed, 0xE]).random(
        (s["vocab_size"], e), dtype=np.float32)
    chosen = np.argpartition(drawn, k, axis=1)[:, :k]  # a token's experts

    def token_marks(r0, r1):
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

    def gain(n=d):
        return normal((n,), 0.1, 1.0)

    def glu(width, lead=()):
        return {"w_in": matrix(*lead, d, 2 * width),
                "w_out": matrix(*lead, width, d)}

    layers = []
    for i in s["layers"]:
        p = {"attn_norm": gain(), "w_dq": matrix(d, rq), "q_norm": gain(rq),
             "w_uq": matrix(rq, heads * (dn + dr)),
             "w_dkv": matrix(d, rkv + dr), "kv_norm": gain(rkv),
             "w_uk": matrix(rkv, heads * dn), "w_uv": matrix(rkv, heads * dv),
             "wo": matrix(heads * dv, d), "mlp_norm": gain()}
        if _full(s, i):
            width = s["index_head_dim"]
            p["indexer"] = {
                "wq": matrix(rq, s["index_n_heads"] * width),
                "wk": matrix(d, width),
                "k_norm": {"scale": gain(width),
                           "bias": normal((width,), 0.1)},
                "w_heads": matrix(d, s["index_n_heads"])}
        if _sparse(s, i):
            f = s["moe_intermediate_size"]
            p["moe"] = dict(glu(f, (held,)),
                            router=normal(
                                (d, e), 1e-3, plus=lambda r0, r1:
                                ROUTER_GAIN * directions.T[r0:r1]),
                            bias=normal((e,), BIAS_STD),
                            shared=glu(f * s["n_shared_experts"]))
        else:
            p["mlp"] = glu(s["intermediate_size"])
        layers.append(p)
    weights = {"embed": normal((s["vocab_size"], d), 1.0, plus=token_marks),
               "layers": layers, "norm": gain(),
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
    """The system under test: ``glm_dsa.build`` over ``weights`` at this
    configuration's sizes.  ``control=True`` is the program's own step below
    bfloat16 (``build_quantized``: W8A8 on the latent attention's
    projections, the dense and shared MLPs and the head); it exists to be
    refused by the comparison."""
    import jax.numpy as jnp

    build = program.build_quantized if control else program.build
    return build(config=s, seq=s["seq"], batch=batch, dtype=jnp.bfloat16,
                 params=weights, token_chunk=s["token_chunk"])
