"""Model kind ``axk1``: the repo's latent-attention token model without a
key selection, with YaRN and a group-limited router
(``nnstreamer_tpu/models/axk1.py``) at a configuration's sizes.

A configuration's file holds the published ``config.json`` keys at its top
level as they are run: every width whole, ``num_hidden_layers`` the layers
built (``build.layers``, published indices), ``n_routed_experts`` the
experts *held here* and ``vocab_size`` the rows held here.  ``build`` also
gives ``router_experts`` (the published expert count, which the router
keeps) and ``first_expert`` (where this chip's share starts), ``seq`` (the
window a frame holds) and ``token_chunk`` (the tokens the expert layer takes
at a time); ``rehearsal`` is what a CPU run overrides.  ``sizes`` hands the
program and the reference one dict in the program's keys:
``n_routed_experts`` the router's width, ``experts_held`` = ``[first,
count]``, ``layers``.

The weights are made here from a seed, on the host, in the served type and
in a checkpoint's layout.  The work functions count the algorithm's work
from the shapes: the score and value products over *every* causal key, 8 of
192 experts a token of which the share held here, the router and the shared
expert.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

# at the top, so that a program without the model fails as the kind is
# imported and not after the weights are made
from nnstreamer_tpu.models import axk1 as program
from nnstreamer_tpu.ops import sparse_attention
from nnstreamer_tpu.parallel import moe

BYTES_PER_VALUE = 2  # bf16, the dtype the configuration states
# init_weights' decisive routers: how far along an expert's direction a
# token's embedding lies if the expert was drawn for it, how far along the
# one expert of a fifth group that lures a choice without the group limit,
# and what a router reads of a direction
TOKEN_MARK = 16.0
LURE_MARK = 24.0
ROUTER_GAIN = 0.5
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


def _sparse(s, i) -> bool:
    return (i >= s["first_k_dense_replace"]
            and i % s.get("moe_layer_freq", 1) == 0)


def _attention_params(s) -> int:
    """The five projections (the two latent norms' gains are counted with
    the layer's in ``param_count``)."""
    d, h = s["hidden_size"], s["num_attention_heads"]
    rq, rkv, dr = s["q_lora_rank"], s["kv_lora_rank"], s["qk_rope_head_dim"]
    return (d * rq + rq * h * (s["qk_nope_head_dim"] + dr) + d * (rkv + dr)
            + rkv * h * (s["qk_nope_head_dim"] + s["v_head_dim"])
            + h * s["v_head_dim"] * d)


def frame_flops(s: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs one frame needs (a multiply-add is 2): the latent attention's
    five projections; the score and value products over every causal key;
    the dense SwiGLU; per sparse layer the router, the shared expert and the
    share of a token's ``num_experts_per_tok`` routed experts that an even
    routing sends to the experts held here; the head at the last
    position."""
    t, d = s["seq"], s["hidden_size"]
    heads = s["num_attention_heads"]
    held = s["experts_held"][1] / s["n_routed_experts"]
    glu = 6 * d * s["moe_intermediate_size"]
    proj = attend = dense = experts = 0.0
    for i in s["layers"]:
        proj += 2 * t * _attention_params(s)
        attend += t * (t + 1) * heads * (
            s["qk_nope_head_dim"] + s["qk_rope_head_dim"] + s["v_head_dim"])
        if _sparse(s, i):
            experts += t * (2 * d * s["n_routed_experts"]
                            + glu * (s["num_experts_per_tok"] * held
                                     + s["n_shared_experts"]))
        else:
            dense += 6 * t * d * s["intermediate_size"]
    parts = {"projections": proj, "latent_attention": attend,
             "dense_mlp": dense, "experts": experts,
             "head": 2.0 * d * s["vocab_size"]}
    return dict(parts, total=float(sum(parts.values())))


def attention_work(s: Dict[str, Any]) -> Dict[str, float]:
    """The attention of one frame: the score and value products over every
    causal key (the rotation of q, the softmax and the masked half of the
    blocks on the diagonal counted as none); q, the keys' and values'
    projections and o once a layer."""
    values = s["num_attention_heads"] * (
        2 * s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
        + 2 * s["v_head_dim"]) + s["qk_rope_head_dim"]
    return {"flops": frame_flops(s)["latent_attention"],
            "bytes": float(len(s["layers"]) * s["seq"] * values
                           * BYTES_PER_VALUE)}


def held_experts_work(s: Dict[str, Any]) -> Dict[str, float]:
    """The sparse layers of one frame: router, the held experts' pairs and
    the shared expert (the group-limited choice's comparisons counted as
    none); the tokens read and written once a layer (the weights are no
    frame's bytes, as in the ``laguna`` kind's ``moe``)."""
    sparse = sum(1 for i in s["layers"] if _sparse(s, i))
    return {"flops": frame_flops(s)["experts"],
            "bytes": float(sparse * 2 * s["seq"] * s["hidden_size"]
                           * BYTES_PER_VALUE)}


# the same work under the names of ISSUE 40's metrics: the harness ties
# ``<label>_roofline`` to a mark and a work function of its label, and the
# accepted readers read ``attention`` and ``held_experts``
latent_attention_work = attention_work
group_limited_experts_work = held_experts_work


def marks(s: Dict[str, Any]) -> Dict[str, Dict[str, list]]:
    """``attention``: the kernel by its name, and on the plain path the ops
    that hold all heads' scores of a block of query rows.  ``held_experts``,
    as the ``glm_dsa`` kind marks it: ``ragged-dot`` by name and the ops
    over the expert layer's own arrays, a chunk of ``c`` tokens at a time,
    and here the router's scores by group too.  Each also under its metric's
    label, as the ``laguna`` kind marks ``mixed_attention``.  No mark names
    an array that a loop carries whole (the token chunks, the pairs' order,
    a chunk's ``c x k`` choice), or the loop would count beside its body."""
    t, c = s["seq"], s["token_chunk"]
    k, d = s["num_experts_per_tok"], s["hidden_size"]
    f, e = s["moe_intermediate_size"], s["n_routed_experts"]
    rows = t // sparse_attention.row_blocks(t, sparse_attention.SELECT_ROWS)
    dims = [[c * k, d], [c, 2 * f * s["n_shared_experts"]],
            [c, f * s["n_shared_experts"]], [c, e], [c, k, d],
            [c, s["n_group"], e // s["n_group"]]]
    passed = moe.share_rows(c * k, s["experts_held"][1], e)
    if passed != c:
        dims += [[passed, d], [passed, 2 * f], [passed, f]]
    attention = {"names": [sparse_attention.LATENT_KERNEL_NAME],
                 "dims": [[s["num_attention_heads"], rows, t]]}
    experts = {"names": ["ragged-dot"], "dims": dims}
    return {"attention": attention, "latent_attention": attention,
            "held_experts": experts, "group_limited_experts": experts}


def param_count(s: Dict[str, Any]) -> int:
    d = s["hidden_size"]
    glu = 3 * d * s["moe_intermediate_size"]
    n = 2 * s["vocab_size"] * d + d
    for i in s["layers"]:
        n += (_attention_params(s) + 2 * d + s["q_lora_rank"]
              + s["kv_lora_rank"])
        if _sparse(s, i):
            n += (d * s["n_routed_experts"]
                  + glu * (s["experts_held"][1] + s["n_shared_experts"]))
        else:
            n += 3 * d * s["intermediate_size"]
    return n


def drawn_routing(s: Dict[str, Any], seed: int):
    """What ``init_weights`` draws for each row of the vocabulary held:
    ``(chosen, lure)``.  ``chosen`` ``[vocab, k]``: the token's
    ``num_experts_per_tok`` experts, ``k / topk_group`` in each of
    ``topk_group`` groups; ``lure`` ``[vocab]``: one expert of a group that
    is closed to the token.  Where a drawn one of the experts *held here*
    stands in a closed group, the lure is that expert, so that this chip
    computes what a choice without the limit would send it (a row for a
    token that sends nothing to that expert, at the token's own position,
    which is where ``correct`` can read it); else it is one of a further
    drawn group."""
    import numpy as np

    e, k = s["n_routed_experts"], s["num_experts_per_tok"]
    groups, kept = s["n_group"], s["topk_group"]
    size, each, rows = e // groups, k // kept, s["vocab_size"]
    if each * kept != k or each < 2 or kept >= groups:
        raise ValueError(f"{k} experts in {kept} of {groups} groups")
    rng = np.random.default_rng([seed, 0xE])
    # kept + 1 groups a token, in random order: the last is closed to it
    drawn = np.argpartition(rng.random((rows, groups), dtype=np.float32),
                            kept, axis=1)[:, :kept + 1]
    within = np.argpartition(
        rng.random((rows, kept, size), dtype=np.float32), each,
        axis=2)[:, :, :each]
    chosen = (drawn[:, :kept, None] * size + within).reshape(rows, k)
    lure = drawn[:, kept] * size + rng.integers(0, size, rows)
    first, count = s["experts_held"]
    here = first + rng.integers(0, count, rows)
    closed = (drawn[:, :kept] != (here // size)[:, None]).all(axis=1)
    return chosen, np.where(closed, here, lure)


def init_weights(s: Dict[str, Any], seed: int):
    """``models/axk1``'s pytree in a checkpoint's layout: bf16 numpy arrays
    on the host.  Every array is drawn in slabs of at most 32 M values, each
    from a generator of its own spawned from ``seed``, a few slabs at a time
    in threads: matrices ``N(0, 1 / fan-in)``, the embedding ``N(0, 1)``,
    the norms' gains ``N(1, 0.1)``, so that a path that dropped one would
    show.

    The routers are decisive at both levels, for the reason the ``laguna``
    kind gives (a near-tie at a last position moves a frame's ``logit_err``
    by more than the step to the control does).  Expert ``e`` of the
    router's ``n_routed_experts`` has a direction, orthonormal to the
    others' and the same in every layer, and its score reads
    ``ROUTER_GAIN`` of the stream along it.  A token's embedding lies
    ``TOKEN_MARK`` along the directions of the experts drawn for it
    (:func:`drawn_routing`): two in each of ``topk_group`` groups, so those
    groups' scores (two scores near 1) stand clear of every other group's
    (at most one), and the eight inside them clear of their neighbours.
    For every token the group limit *binds*: the embedding also lies
    ``LURE_MARK`` along one expert of a fifth group, the token's highest
    score of all, which a choice among all experts would take and the
    group-limited one may not (its group holds one high score, not two)."""
    from concurrent.futures import ThreadPoolExecutor

    import ml_dtypes
    import numpy as np

    d, heads = s["hidden_size"], s["num_attention_heads"]
    rq, rkv = s["q_lora_rank"], s["kv_lora_rank"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    e, held = s["n_routed_experts"], s["experts_held"][1]
    jobs = []  # (array, rows, std, mean, what is added to the rows)
    directions = np.linalg.qr(np.random.default_rng([seed, 0xD]).standard_normal(
        (d, e)).astype(np.float32))[0].T.copy()          # [e, d], orthonormal
    chosen, lure = drawn_routing(s, seed)

    def token_marks(r0, r1):
        along = np.zeros((r1 - r0, e), np.float32)
        np.put_along_axis(along, chosen[r0:r1], np.float32(TOKEN_MARK), 1)
        np.put_along_axis(along, lure[r0:r1, None], np.float32(LURE_MARK), 1)
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
        if _sparse(s, i):
            f = s["moe_intermediate_size"]
            p["moe"] = dict(glu(f, (held,)),
                            router=normal(
                                (d, e), 1e-3, plus=lambda r0, r1:
                                ROUTER_GAIN * directions.T[r0:r1]),
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
    """The system under test: ``axk1.build`` over ``weights`` at this
    configuration's sizes.  ``control=True`` is the program's own step below
    bfloat16 (``build_quantized``: W8A8 on the latent attention's
    projections, the dense and shared MLPs and the head); it exists to be
    refused by the comparison."""
    import jax.numpy as jnp

    build = program.build_quantized if control else program.build
    return build(config=s, seq=s["seq"], batch=batch, dtype=jnp.bfloat16,
                 params=weights, token_chunk=s["token_chunk"])
