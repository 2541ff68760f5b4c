"""Decoder-only hybrid token model of the ``nemotron_h`` family on the
streaming path: a sequence of single-mixer layers, each a Mamba-2 mixer,
grouped attention or a latent mixture of experts, as
``hybrid_override_pattern`` spells them.

The program is ``models/decoder.py``'s forward pass around these layers,
read from the published ``config.json`` keys; every layer is ``x ← x +
mixer(RMSNorm(x))`` at ``layer_norm_epsilon``, its mixer chosen by one
character of the pattern:

- ``M``, *Mamba-2*: ``models/decoder.mixer`` over this config's widths
  (:func:`mixer_config`; the family has no multipliers): ``[z | xBC | dt] =
  h W_in``, ``xBC = SiLU(causal depthwise conv1d(xBC))`` (``conv_kernel``
  taps, a bias if ``use_conv_bias``), x in ``mamba_num_heads`` heads of
  ``mamba_head_dim``, B and C in ``n_groups`` groups of ``ssm_state_size``,
  ``Δ = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, the chunked scan
  ``ops/ssm_scan.ssd_scan`` in chunks of ``chunk_size`` with the ``D`` skip
  (heads narrower than a lane tile take the kernel's ``shared_tile``
  body), the gated RMSNorm ``norm(y ⊙ SiLU(z))`` over each of ``n_groups``
  groups of channels, then ``W_out``;
- ``*``, *attention*: causal softmax over ``num_attention_heads`` query
  heads and ``num_key_value_heads`` key/value heads of ``head_dim`` at
  ``head_dim ** -0.5``, no rotary and no biases
  (``ops/fused_attention.attention`` without tables);
- ``E``, *the latent mixture of experts* (``parallel/moe.moe_top_k`` with
  ``latent_in`` / ``latent_out`` and ``act="relu2"``): the router reads x,
  sigmoid scores of ``n_routed_experts``, the ``num_experts_per_tok``
  highest of ``score + e_score_correction_bias``, their unbiased scores
  renormalised and times ``routed_scaling_factor``; the routed experts
  read ``z = x W_lat_in`` (``moe_latent_size``), each ``W2 relu(W1 z)²`` of
  ``moe_intermediate_size``, their weighted sum taken in the latent width
  and brought back by ``W_lat_out``; the shared expert ``W_sd relu(W_su
  x)²`` of ``moe_shared_expert_intermediate_size`` added unweighted;
- then ``norm_f`` (``norm_eps``) and the untied head over the last position.

Two keys say what is built here and are no published ones, as in
``models/glm_dsa.py``: ``layers``, the published indices of the layers built
(left out: the first ``num_hidden_layers``), and ``experts_held`` =
``[first, count]``, this chip's share of every expert layer's routed experts
(left out: all); the router keeps ``n_routed_experts`` outputs either way.
``hybrid_override_pattern`` holds a character for every published layer
(``layers`` picks from it) or for the layers built alone, in order.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax.numpy as jnp

from ..backends.jax_backend import JaxModel
from ..ops.fused_attention import attention
from ..ops.lowering import gauge
from ..parallel.moe import matmul, moe_top_k
from . import decoder
# widths: read off this module by the benchmark's kind
from .decoder import (  # noqa: F401
    experts_held, layer_ids, load_config, mixer, mixer_params,
    quantize_weights, rms_norm, widths)

KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    """Each built layer's character of ``hybrid_override_pattern``."""
    pattern, ids = cfg["hybrid_override_pattern"], layer_ids(cfg)
    kinds = list(pattern) if len(pattern) == len(ids) else [pattern[i]
                                                           for i in ids]
    unknown = sorted(set(kinds) - set(KINDS))
    if unknown:
        raise ValueError(f"layer kinds {unknown} of {pattern!r}: this model "
                         f"builds {sorted(KINDS)}")
    return kinds


def mixer_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """This family's Mamba-2 keys in the ones ``decoder.mixer`` reads."""
    return {"hidden_size": cfg["hidden_size"],
            "mamba_d_ssm": cfg["mamba_num_heads"] * cfg["mamba_head_dim"],
            "mamba_n_heads": cfg["mamba_num_heads"],
            "mamba_d_head": cfg["mamba_head_dim"],
            "mamba_n_groups": cfg["n_groups"],
            "mamba_d_state": cfg["ssm_state_size"],
            "mamba_d_conv": cfg["conv_kernel"],
            "mamba_conv_bias": cfg["use_conv_bias"],
            "mamba_chunk_size": cfg["chunk_size"],
            "rms_norm_eps": cfg["layer_norm_epsilon"]}


def attention_layer(cfg: Dict[str, Any], p, h):
    o = attention(matmul(h, p["wq"]), cfg["num_attention_heads"], True,
                  k=matmul(h, p["wk"]), v=matmul(h, p["wv"]),
                  n_kv_heads=cfg["num_key_value_heads"])
    return matmul(o, p["wo"])


def experts_layer(cfg: Dict[str, Any], p, h, token_chunk=None):
    if not cfg.get("norm_topk_prob", True):
        raise NotImplementedError("a router whose chosen scores are not "
                                  "renormalised")
    return moe_top_k(p, h, cfg["num_experts_per_tok"],
                     cfg["routed_scaling_factor"], token_chunk,
                     experts_held(cfg)[0], cfg.get("n_group"),
                     cfg.get("topk_group"), act="relu2")


def layer(cfg: Dict[str, Any], kind: str, p, x, low: bool = False,
          token_chunk=None):
    """One layer of ``kind`` (a pattern's character) over ``x`` ``[B, T,
    d]``."""
    h = rms_norm(x, p["norm"], cfg["layer_norm_epsilon"])
    if kind == "M":
        return x + mixer(mixer_config(cfg), p["mixer"], h, low)
    if kind == "*":
        return x + attention_layer(cfg, p["attn"], h)
    return x + experts_layer(cfg, p["moe"], h, token_chunk)


def apply(cfg: Dict[str, Any], params, ids, dtype=jnp.bfloat16,
          low: bool = False, token_chunk: Optional[int] = None):
    """``ids`` ``[B, T]`` int32 → float32 logits ``[B, vocab]`` of the last
    position (one window ``[T]`` → ``[vocab]``)."""
    def layers(x):
        for kind, p in zip(layer_kinds(cfg), params["layers"]):
            x = layer(cfg, kind, p, x, low, token_chunk)
        return x

    return decoder.apply(params, ids, dtype, layers, cfg["norm_eps"])


def init_params(cfg: Dict[str, Any], seed: int = 0, dtype=jnp.bfloat16):
    """Seeded random weights in the model's pytree (``decoder.Draws``; the
    mixers' ``decoder.mixer_params``), the selection bias ``N(0, 0.1)``."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, (_, held) = cfg["n_routed_experts"], experts_held(cfg)
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    draw = decoder.Draws(seed, dtype)
    matrix = draw.matrix
    layers = []
    for kind in layer_kinds(cfg):
        if kind == "M":
            p = {"mixer": mixer_params(draw, mixer_config(cfg))}
        elif kind == "*":
            p = {"attn": {"wq": matrix(d, heads * dh),
                          "wk": matrix(d, kv * dh), "wv": matrix(d, kv * dh),
                          "wo": matrix(heads * dh, d)}}
        else:
            p = {"moe": {"router": matrix(d, e), "bias": draw.gain(e, 0.0),
                         "latent_in": matrix(d, lat),
                         "latent_out": matrix(lat, d),
                         "w_in": matrix(held, lat, f),
                         "w_out": matrix(held, f, lat),
                         "shared": {"w_in": matrix(d, fs),
                                    "w_out": matrix(fs, d)}}}
        layers.append(dict(p, norm=draw.gain(d)))
    return draw.model(cfg, layers)


def build(config, seq: int, batch: Optional[int] = None,
          dtype=jnp.bfloat16, seed: int = 0, params=None, low: bool = False,
          token_chunk: Optional[int] = None) -> JaxModel:
    """Stream-ready model: one frame = ``[seq]`` int32 token ids, the answer
    its next token's float32 logits ``[vocab]``.  ``config``: the published
    ``config.json`` (dict or path) with ``layers`` and ``experts_held`` if
    this is a cut; ``params``: its weights (``init_params``' pytree), seeded
    random ones if left out.  ``low``: the scans' carried state and decays in
    bfloat16 (the benchmark's control); ``token_chunk``: the tokens an expert
    layer takes at a time."""
    cfg = load_config(config)
    kinds = layer_kinds(cfg)
    for char, kind in KINDS.items():
        gauge("nnstpu_hybrid_layers",
              "layers of the last hybrid model built, by kind (mamba = a "
              "Mamba-2 mixer, attention = grouped attention, experts = a "
              "mixture of experts)", kinds.count(char), kind=kind)
    return decoder.build(
        cfg, init_params,
        lambda p, ids, dtype: apply(cfg, p, ids, dtype, bool(low),
                                    token_chunk),
        f"nemotron_h_{cfg['hidden_size']}x{len(kinds)}", seq, batch, dtype,
        seed, params)


def build_quantized(**kwargs) -> JaxModel:
    """The step below bfloat16: the mixers', attention's, latent and shared
    experts' projections and the head W8A8 (``ops/quant``); the conv, the
    router, the embedding and the routed experts stay as they are.  Takes
    :func:`build`'s kwargs (``low=True`` too: the benchmark's control)."""
    model = build(**kwargs)
    model.params = quantize_weights(model.params,
                                    ("router", "embed", "conv_w"))
    return model
