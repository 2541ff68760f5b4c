"""Decoder-only token model of the ``glm_moe_dsa`` family on the streaming
path: multi-head latent attention under a learned selection of keys, and
sparse experts of which a chip may hold its share.

One frame is a window of ``T`` token ids; the program is a whole forward
pass over it and returns the logits of the last position, as
``models/laguna.py`` does.  The model is read from the published
``config.json`` keys; layer ``i``, pre-norm RMSNorm, no biases:

- *latent attention*: ``c_q = RMSNorm(h W_dq)`` (``q_lora_rank``), ``q = c_q
  W_uq`` in ``num_attention_heads`` heads of ``qk_nope_head_dim |
  qk_rope_head_dim``; ``[c_kv | k_r] = h W_dkv`` (``kv_lora_rank |
  qk_rope_head_dim``), ``c_kv = RMSNorm(c_kv)``, ``k_n = c_kv W_uk``, ``v =
  c_kv W_uv`` (``v_head_dim`` a head); rotary (``rope_parameters``, default
  type, interleaved pairs) on q's rope dims and on ``k_r``, which every head
  shares; the softmax of ``(q_n . k_n + q_r . k_r) / sqrt(qk_head_dim)``
  runs over the keys selected for the query alone
  (``ops/sparse_attention.latent_sparse_attention``, which takes the
  projections unrotated beside the rotary tables);
- *the selection* (``indexer_types[i]``): a ``full`` layer's indexer, ``q_I =
  c_q W_Iq`` in ``index_n_heads`` heads of ``index_head_dim``, ``k_I =
  LayerNorm(h W_Ik)`` (one head), rotary on the first ``qk_rope_head_dim``
  of each, ``w = h W_Iw``, scores every causal pair and keeps each query's
  ``index_topk`` highest keys (``ops/sparse_attention.select_keys``); a
  ``shared`` layer takes the selection of the nearest ``full`` layer before
  it, so the layer loop carries ``(x, selection)``;
- ``mlp_layer_types[i]``: ``dense`` a SwiGLU of ``intermediate_size``,
  ``sparse`` the ``num_experts_per_tok`` highest of ``n_routed_experts``
  sigmoid scores plus a per-expert bias that steers the choice alone, the
  chosen scores renormalised and times ``routed_scaling_factor``, SwiGLU
  experts of ``moe_intermediate_size`` beside ``n_shared_experts`` shared
  ones (``parallel/moe.moe_top_k``).

Two keys say what is built here and are no published ones: ``layers``, the
published indices of the layers built (left out: the first
``num_hidden_layers``), and ``experts_held`` = ``[first, count]``, this
chip's share of every sparse layer's experts (left out: all).  The router
keeps ``n_routed_experts`` outputs either way.

The layer body is the family's, and what a sibling lacks it leaves out
(``models/axk1.py`` builds on it): without ``indexer_types`` no layer
selects and the softmax runs over every causal key; ``softmax_scale``
replaces ``1 / sqrt(qk_head_dim)``; ``n_group`` groups of which
``topk_group`` stand for the router's choice (one group: all of them);
a ``topk_method`` other than ``noaux_tc`` has no selection bias.

The arrays are held with the rotary pairs split (pair ``i`` = dims ``(i, i
+ rot/2)``): :func:`split_rotary_pairs` reorders a checkpoint's columns once
on the host, the same way for a query and its key, so every score is the
published one and no activation is ever re-tiled for the interleaved layout.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..backends.jax_backend import JaxModel
from ..ops.fused_attention import rotate
from ..ops.sparse_attention import (latent_sparse_attention, select_keys,
                                    shared_selection)
from ..parallel.moe import matmul, moe_top_k, swiglu
from ..spec import TensorSpec, TensorsSpec
from .laguna import (load_config, quantize_weights, rms_norm,
                     rotary_tables)


def layer_ids(cfg: Dict[str, Any]) -> List[int]:
    """The published indices of the layers built."""
    return list(cfg.get("layers") or range(cfg["num_hidden_layers"]))


def experts_held(cfg: Dict[str, Any]) -> Sequence[int]:
    """``(first, count)`` of the routed experts this chip holds."""
    return tuple(cfg.get("experts_held") or (0, cfg["n_routed_experts"]))


def _pairs_split(w, heads: int, at: int, rot: int):
    """The columns of ``w`` ``[rows, heads * width]``, each head's ``rot``
    dims from ``at`` on reordered from interleaved pairs ``(2i, 2i + 1)`` to
    split ones ``(i, i + rot/2)``."""
    width = w.shape[-1] // heads
    order = np.arange(width)
    order[at:at + rot] = at + np.concatenate([np.arange(0, rot, 2),
                                              np.arange(1, rot, 2)])
    cols = (np.arange(heads)[:, None] * width + order[None, :]).reshape(-1)
    return w[..., cols]


def split_rotary_pairs(cfg: Dict[str, Any], params):
    """A checkpoint's pytree (rotary dims in interleaved pairs,
    ``rope_interleave`` and ``indexer_rope_interleave``) in the layout
    :func:`apply` takes: the same permutation of a query's and its key's
    rotary columns, which leaves every score as it was."""
    heads, rot = cfg["num_attention_heads"], cfg["qk_rope_head_dim"]
    layers = []
    for p in params["layers"]:
        p = dict(p, w_uq=_pairs_split(p["w_uq"], heads,
                                      cfg["qk_nope_head_dim"], rot),
                 w_dkv=_pairs_split(p["w_dkv"], 1, cfg["kv_lora_rank"], rot))
        if "indexer" in p:
            # the key's LayerNorm is per dim: its gains move with the dims
            p["indexer"] = dict(
                p["indexer"],
                wq=_pairs_split(p["indexer"]["wq"], cfg["index_n_heads"], 0,
                                rot),
                wk=_pairs_split(p["indexer"]["wk"], 1, 0, rot),
                k_norm={k: _pairs_split(v, 1, 0, rot)
                        for k, v in p["indexer"]["k_norm"].items()})
        layers.append(p)
    return dict(params, layers=layers)


def layer_norm(x, scale, bias, eps: float):
    h = x.astype(jnp.float32)
    h = h - h.mean(axis=-1, keepdims=True)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def selects(cfg: Dict[str, Any], i: int) -> Optional[str]:
    """``full``, ``shared`` or, in a model without a selection, nothing."""
    kinds = cfg.get("indexer_types")
    return kinds[i] if kinds else None


def select(cfg: Dict[str, Any], p, h, c_q, tables):
    """A ``full`` layer's indexer: the ``[B, T, T]`` selection."""
    heads, width = cfg["index_n_heads"], cfg["index_head_dim"]
    eps = cfg["rms_norm_eps"]
    q_i = rotate(matmul(c_q, p["wq"]), *tables, heads)
    k_i = rotate(layer_norm(matmul(h, p["wk"]), p["k_norm"]["scale"],
                            p["k_norm"]["bias"], eps), *tables, 1)
    w = jnp.dot(h, p["w_heads"].astype(h.dtype),
                preferred_element_type=jnp.float32)
    return select_keys(q_i, k_i, w * (heads * width) ** -0.5,
                       cfg["index_topk"])


def layer(cfg: Dict[str, Any], i: int, p, x, selection, tables,
          token_chunk=None):
    """Published layer ``i`` over ``x`` ``[B, T, d]``; returns ``(x, the
    selection it attended under)``."""
    eps = cfg["rms_norm_eps"]
    heads = cfg["num_attention_heads"]
    rank = cfg["kv_lora_rank"]
    h = rms_norm(x, p["attn_norm"], eps)
    c_q = rms_norm(matmul(h, p["w_dq"]), p["q_norm"], eps)
    down = matmul(h, p["w_dkv"])
    c_kv = rms_norm(down[..., :rank], p["kv_norm"], eps)
    if selects(cfg, i) is None:
        selection = None  # every causal key: no mask is made or read
    elif selects(cfg, i) == "full":
        selection = select(cfg, p["indexer"], h, c_q, tables)
    elif selection is None:
        raise ValueError(f"layer {i} shares a selection and none of the "
                         "layers built before it makes one")
    else:
        selection = shared_selection(selection)
    # q and the rotary key part as the products write them: the attention's
    # lowering rotates them, q on the kernel's own blocks where it tiles
    o = latent_sparse_attention(matmul(c_q, p["w_uq"]),
                                matmul(c_kv, p["w_uk"]), down[..., rank:],
                                matmul(c_kv, p["w_uv"]), selection, heads,
                                rotary=tables, scale=cfg.get("softmax_scale"))
    x = x + matmul(o, p["wo"])
    h = rms_norm(x, p["mlp_norm"], eps)
    if cfg["mlp_layer_types"][i] == "dense":
        return x + swiglu(h, p["mlp"]["w_in"], p["mlp"]["w_out"]), selection
    return x + moe_top_k(p["moe"], h, cfg["num_experts_per_tok"],
                         cfg["routed_scaling_factor"], token_chunk,
                         experts_held(cfg)[0], cfg.get("n_group"),
                         cfg.get("topk_group")), selection


def apply(cfg: Dict[str, Any], params, ids, dtype=jnp.bfloat16,
          token_chunk: Optional[int] = None):
    """``ids`` ``[B, T]`` int32 -> float32 logits ``[B, vocab]`` of the last
    position (one window ``[T]`` -> ``[vocab]``).  ``params``: the pytree
    with the rotary pairs split (:func:`split_rotary_pairs`)."""
    if ids.ndim == 1:
        return apply(cfg, params, ids[None], dtype, token_chunk)[0]
    tables = rotary_tables(cfg["rope_parameters"], cfg["qk_rope_head_dim"],
                           ids.shape[-1])
    x = jnp.asarray(params["embed"])[ids].astype(dtype)
    selection = None
    for i, p in zip(layer_ids(cfg), params["layers"]):
        x, selection = layer(cfg, i, p, x, selection, tables, token_chunk)
    last = rms_norm(x[:, -1], params["norm"], cfg["rms_norm_eps"])
    return matmul(last, params["head"]).astype(jnp.float32)


def init_params(cfg: Dict[str, Any], seed: int = 0, dtype=jnp.bfloat16):
    """Seeded random weights in a checkpoint's pytree and layout (small
    sizes: the arrays are made on the default device)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    e, (_, held) = cfg["n_routed_experts"], experts_held(cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

    def w(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dtype)

    def gain(n=d, mean=1.0):
        return (mean + 0.1 * jax.random.normal(next(keys), (n,))).astype(dtype)

    def glu(width, lead=()):
        return {"w_in": w(*lead, d, 2 * width), "w_out": w(*lead, width, d)}

    layers = []
    for i in layer_ids(cfg):
        p = {"attn_norm": gain(), "w_dq": w(d, rq), "q_norm": gain(rq),
             "w_uq": w(rq, heads * (dn + dr)), "w_dkv": w(d, rkv + dr),
             "kv_norm": gain(rkv), "w_uk": w(rkv, heads * dn),
             "w_uv": w(rkv, heads * dv), "wo": w(heads * dv, d),
             "mlp_norm": gain()}
        if selects(cfg, i) == "full":
            width = cfg["index_head_dim"]
            p["indexer"] = {
                "wq": w(rq, cfg["index_n_heads"] * width), "wk": w(d, width),
                "k_norm": {"scale": gain(width), "bias": gain(width, 0.0)},
                "w_heads": w(d, cfg["index_n_heads"])}
        if cfg["mlp_layer_types"][i] == "dense":
            p["mlp"] = glu(cfg["intermediate_size"])
        else:
            f = cfg["moe_intermediate_size"]
            p["moe"] = dict(glu(f, (held,)), router=w(d, e))
            if cfg.get("topk_method", "noaux_tc") == "noaux_tc":
                p["moe"]["bias"] = gain(e, 0.0)
            p["moe"]["shared"] = glu(f * cfg["n_shared_experts"])
        layers.append(p)
    embed = jax.random.normal(next(keys), (cfg["vocab_size"], d), jnp.float32)
    return {"embed": embed.astype(dtype), "layers": layers, "norm": gain(),
            "head": w(d, cfg["vocab_size"])}


def build(config, seq: int, batch: Optional[int] = None,
          dtype=jnp.bfloat16, seed: int = 0, params=None,
          token_chunk: Optional[int] = None) -> JaxModel:
    """Stream-ready model: one frame = ``[seq]`` int32 token ids, the answer
    its next token's float32 logits ``[vocab]``.  ``config``: the published
    ``config.json`` (dict or path) with ``layers`` and ``experts_held`` if
    this is a cut; ``params``: a checkpoint's weights (``init_params``'
    pytree and layout), seeded random ones if left out."""
    cfg = load_config(config)
    dtype = jnp.dtype(dtype)  # a launch string gives its name
    if params is None:
        params = init_params(cfg, seed, dtype)
    shape = (seq,) if batch is None else (batch, seq)
    return JaxModel(
        apply=lambda p, ids: apply(cfg, p, ids, dtype, token_chunk),
        params=split_rotary_pairs(cfg, params),
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.int32, shape=shape)),
        name=f"glm_dsa_{cfg['hidden_size']}x{len(layer_ids(cfg))}",
    )


def build_quantized(**kwargs) -> JaxModel:
    """The step below bfloat16: the latent attention's projections, the
    dense MLP, the shared experts and the head W8A8 (``ops/quant``); the
    indexer, the router and the routed experts stay as they are.  Takes
    :func:`build`'s kwargs."""
    model = build(**kwargs)
    model.params = quantize_weights(model.params,
                                    ("router", "embed", "indexer"))
    return model
