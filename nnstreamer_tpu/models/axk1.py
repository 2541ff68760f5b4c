"""Decoder-only token model of the ``axk1`` family (``deepseek_v3``-shaped)
on the streaming path: multi-head latent attention over every causal key,
YaRN rotary embeddings, and sparse experts chosen group by group, of which a
chip may hold its share.

One frame is a window of ``T`` token ids; the program is a whole forward
pass over it and returns the logits of the last position.  The layer body is
``models/glm_dsa.layer``, the same latent attention with no selection in
front of it; this module reads the family's published ``config.json`` keys
into the keys that body takes (:func:`latent_config`):

- *latent attention*: ``q_lora_rank``, ``kv_lora_rank``,
  ``num_attention_heads`` heads of ``qk_nope_head_dim | qk_rope_head_dim``
  and ``v_head_dim``, as ``models/glm_dsa.py`` describes; no
  ``indexer_types``, so every layer attends densely
  (``ops/sparse_attention.latent_sparse_attention`` without a mask);
- *rotary*: ``rope_theta`` beside a ``rope_scaling`` dict of type ``yarn``
  (``factor``, ``original_max_position_embeddings``, ``beta_fast``,
  ``beta_slow``, ``mscale``, ``mscale_all_dim``): the per-pair blend of
  interpolated and extrapolated frequencies that ``laguna.rotary_tables``
  computes, cos and sin times ``m(mscale) / m(mscale_all_dim)`` and the
  softmax scale ``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5 *
  m(mscale_all_dim) ** 2``, with ``m(s) = 0.1 s ln(factor) + 1``: the scale
  multiplies the unrotated part of a score too, so it is the attention's;
- *MLPs*: layer ``i`` is dense (a SwiGLU of ``intermediate_size``) where ``i
  < first_k_dense_replace`` or ``i % moe_layer_freq != 0``, else sparse: the
  router's sigmoid scores stand in ``n_group`` groups, a group's score is
  the sum of its two highest, the ``topk_group`` highest groups are kept and
  the ``num_experts_per_tok`` highest experts among theirs chosen
  (``parallel/moe.route_top_k``), renormalised and times
  ``routed_scaling_factor``, beside ``n_shared_experts`` shared ones;
  ``topk_method: "none"`` is read as no selection bias.

``layers`` (the published indices built) and ``experts_held`` = ``[first,
count]`` (this chip's share of every sparse layer's experts) cut the model
as they cut ``glm_dsa``'s; a checkpoint's rotary dims lie in interleaved
pairs and are reordered once on the host (``glm_dsa.split_rotary_pairs``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax.numpy as jnp

from ..backends.jax_backend import JaxModel
from . import glm_dsa
from .laguna import load_config, quantize_weights


def yarn_scale(scaling: Dict[str, Any], key: str) -> float:
    """``m(s) = 0.1 s ln(factor) + 1`` of ``scaling[key]`` (1 where the
    factor does not stretch or the key is left out or 0)."""
    s, factor = scaling.get(key) or 0, scaling.get("factor", 1)
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 and s else 1.0


def latent_config(config) -> Dict[str, Any]:
    """The published ``config.json`` (dict or path) with the keys
    ``glm_dsa.layer`` reads worked out beside it: ``mlp_layer_types`` by
    published index, ``rope_parameters`` in ``rotary_tables``' form and
    ``softmax_scale``."""
    cfg = load_config(config)
    depth = max([cfg["num_hidden_layers"], *(i + 1 for i in
                                             cfg.get("layers") or ())])
    dense, freq = cfg["first_k_dense_replace"], cfg.get("moe_layer_freq", 1)
    cfg["mlp_layer_types"] = ["dense" if i < dense or i % freq else "sparse"
                              for i in range(depth)]
    scaling = cfg.get("rope_scaling") or {}
    rope = {"rope_theta": cfg["rope_theta"], "rope_type": "default"}
    if scaling.get("type", scaling.get("rope_type")) == "yarn":
        rope = dict(scaling, rope_theta=cfg["rope_theta"], rope_type="yarn",
                    attention_factor=yarn_scale(scaling, "mscale")
                    / yarn_scale(scaling, "mscale_all_dim"))
    cfg["rope_parameters"] = rope
    cfg["softmax_scale"] = (
        (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
        * yarn_scale(scaling, "mscale_all_dim") ** 2)
    return cfg


def build(config, seq: int, batch: Optional[int] = None,
          dtype=jnp.bfloat16, seed: int = 0, params=None,
          token_chunk: Optional[int] = None) -> JaxModel:
    """Stream-ready model: one frame = ``[seq]`` int32 token ids, the answer
    its next token's float32 logits ``[vocab]`` over the rows held.
    ``config``: the published ``config.json`` (dict or path) with ``layers``
    and ``experts_held`` if this is a cut; ``params``: a checkpoint's
    weights (``glm_dsa.init_params``' pytree and layout, no indexer and no
    selection bias), seeded random ones if left out."""
    cfg = latent_config(config)
    model = glm_dsa.build(cfg, seq, batch, dtype, seed, params, token_chunk)
    model.name = f"axk1_{cfg['hidden_size']}x{len(glm_dsa.layer_ids(cfg))}"
    return model


def build_quantized(**kwargs) -> JaxModel:
    """The step below bfloat16: the latent attention's projections, the
    dense MLP, the shared experts and the head W8A8 (``ops/quant``); the
    router and the routed experts stay as they are.  Takes :func:`build`'s
    kwargs."""
    model = build(**kwargs)
    model.params = quantize_weights(model.params)
    return model
