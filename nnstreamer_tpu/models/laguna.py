"""Decoder-only token model of the ``laguna`` family on the streaming path.

The program is ``models/decoder.py``'s forward pass around these layers,
read from the published ``config.json`` keys:

- ``layer_types`` / ``num_attention_heads_per_layer``: a ``full_attention``
  layer is causal with rotary embeddings on the first
  ``partial_rotary_factor`` of each head (YaRN frequencies, cos/sin scaled
  by ``attention_factor``), a ``sliding_attention`` layer sees
  ``sliding_window`` keys back, plain rotary on the whole head.  Query heads
  differ per layer; all share ``num_key_value_heads`` key/value heads.  The
  projections go to ``ops/fused_attention.attention`` (grouped) unrotated,
  with the layer kind's tables: the rotation is float32 and rounded once
  wherever it runs, in VMEM on the blocked kernel's own blocks or through
  ``ops/fused_attention.rotate`` where the plain path is lowered;
- ``mlp_layer_types``: ``dense`` is a SwiGLU of ``intermediate_size``,
  ``sparse`` the top ``num_experts_per_tok`` of ``num_experts`` SwiGLU
  experts times ``moe_routed_scaling_factor`` beside a shared expert
  (``parallel/moe.moe_top_k``);
- pre-norm RMSNorm, no biases, untied embedding and head.

``num_hidden_layers`` cuts the depth: the first that many entries of the
per-layer lists are built, so the whole model is the same call.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp

from ..backends.jax_backend import JaxModel
from ..ops.fused_attention import attention
from ..parallel.moe import matmul, moe_top_k, swiglu
from . import decoder
from .decoder import load_config, quantize_weights, rms_norm, rotary_tables


def layer(cfg: Dict[str, Any], i: int, p, x, tables, token_chunk=None):
    """Layer ``i`` of the model over ``x`` ``[B, T, d]``."""
    kind = cfg["layer_types"][i]
    heads = cfg["num_attention_heads_per_layer"][i]
    kv = cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p["attn_norm"], eps)
    # q and k go in as the products left them: the lowering rotates them
    o = attention(matmul(h, p["wq"]), heads, True, k=matmul(h, p["wk"]),
                  v=matmul(h, p["wv"]), n_kv_heads=kv,
                  window=(cfg["sliding_window"]
                          if kind == "sliding_attention" else None),
                  rotary=tables[kind])
    x = x + matmul(o, p["wo"])
    h = rms_norm(x, p["mlp_norm"], eps)
    if cfg["mlp_layer_types"][i] == "dense":
        return x + swiglu(h, p["mlp"]["w_in"], p["mlp"]["w_out"])
    return x + moe_top_k(p["moe"], h, cfg["num_experts_per_tok"],
                         cfg["moe_routed_scaling_factor"], token_chunk)


def apply(cfg: Dict[str, Any], params, ids, dtype=jnp.bfloat16,
          token_chunk: Optional[int] = None):
    """``ids`` ``[B, T]`` int32 → float32 logits ``[B, vocab]`` of the last
    position (one window ``[T]`` → ``[vocab]``)."""
    def layers(x):
        tables = {kind: rotary_tables(rope, cfg["head_dim"], x.shape[1])
                  for kind, rope in cfg["rope_parameters"].items()
                  if isinstance(rope, dict)}
        for i, p in enumerate(params["layers"]):
            x = layer(cfg, i, p, x, tables, token_chunk)
        return x

    return decoder.apply(params, ids, dtype, layers, cfg["rms_norm_eps"])


def init_params(cfg: Dict[str, Any], seed: int = 0, dtype=jnp.bfloat16):
    """Seeded random weights in the model's pytree (``decoder.Draws``)."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * dh
    draw = decoder.Draws(seed, dtype)
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        hq = cfg["num_attention_heads_per_layer"][i] * dh
        p = {"attn_norm": draw.gain(d), "wq": draw.matrix(d, hq),
             "wk": draw.matrix(d, kv), "wv": draw.matrix(d, kv),
             "wo": draw.matrix(hq, d), "mlp_norm": draw.gain(d)}
        if cfg["mlp_layer_types"][i] == "dense":
            p["mlp"] = draw.glu(d, cfg["intermediate_size"])
        else:
            e = cfg["num_experts"]
            p["moe"] = dict(
                draw.glu(d, cfg["moe_intermediate_size"], (e,)),
                router=draw.matrix(d, e),
                shared=draw.glu(d, cfg["shared_expert_intermediate_size"]))
        layers.append(p)
    return draw.model(cfg, layers)


def build(config, seq: int, batch: Optional[int] = None,
          dtype=jnp.bfloat16, seed: int = 0, params=None,
          token_chunk: Optional[int] = None) -> JaxModel:
    """Stream-ready model: one frame = ``[seq]`` int32 token ids, the answer
    its next token's float32 logits ``[vocab]``.  ``config``: the published
    ``config.json`` (dict or path); ``params``: its weights (``init_params``'
    pytree), seeded random ones if left out."""
    cfg = load_config(config)
    return decoder.build(
        cfg, init_params,
        lambda p, ids, dtype: apply(cfg, p, ids, dtype, token_chunk),
        f"laguna_{cfg['hidden_size']}x{cfg['num_hidden_layers']}", seq,
        batch, dtype, seed, params)


def build_quantized(**kwargs) -> JaxModel:
    """The step below bfloat16: the projections, the dense MLP, the shared
    experts and the head W8A8 (``ops/quant``); the routed experts and the
    router stay as they are.  Takes :func:`build`'s kwargs."""
    model = build(**kwargs)
    model.params = quantize_weights(model.params)
    return model
