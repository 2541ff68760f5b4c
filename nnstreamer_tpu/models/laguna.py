"""Decoder-only token model of the ``laguna`` family on the streaming path.

One frame is a window of ``T`` token ids; the program is a whole forward
pass over it and returns the logits of the last position (what a serving
engine calls a prefill-only request).  The model is read from the published
``config.json`` keys, per layer:

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

import json
import math
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..backends.jax_backend import JaxModel
from ..ops.fused_attention import attention
from ..parallel.moe import matmul, moe_top_k, swiglu
from ..spec import TensorSpec, TensorsSpec


def load_config(config) -> Dict[str, Any]:
    """A ``config.json`` as a dict: a dict, or the path of the file."""
    if isinstance(config, (str, os.PathLike)):
        with open(config, "r", encoding="utf-8") as f:
            return json.load(f)
    return dict(config)


def rotary_tables(rope: Dict[str, Any], head_dim: int, t: int):
    """``(cos, sin)``, each ``[t, rot/2]`` float32, of one layer kind's
    ``rope_parameters``; ``rot`` = ``head_dim * partial_rotary_factor``."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1))
    inv = 1.0 / rope["rope_theta"] ** (np.arange(0, rot, 2) / rot)
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        factor, orig = rope["factor"], rope["original_max_position_embeddings"]

        def correction(rotations):
            return (rot * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(rope["rope_theta"])))

        low = max(math.floor(correction(rope["beta_fast"])), 0)
        high = min(math.ceil(correction(rope["beta_slow"])), rot - 1)
        ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3),
                       0, 1)
        inv = inv / factor * ramp + inv * (1 - ramp)
        scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    angles = np.arange(t)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(angles) * scale, jnp.float32),
            jnp.asarray(np.sin(angles) * scale, jnp.float32))


def rms_norm(x, gain, eps: float):
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * gain.astype(jnp.float32)).astype(x.dtype)


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
    if ids.ndim == 1:
        return apply(cfg, params, ids[None], dtype, token_chunk)[0]
    t = ids.shape[-1]
    tables = {kind: rotary_tables(rope, cfg["head_dim"], t)
              for kind, rope in cfg["rope_parameters"].items()
              if isinstance(rope, dict)}
    x = jnp.asarray(params["embed"])[ids].astype(dtype)
    for i, p in enumerate(params["layers"]):
        x = layer(cfg, i, p, x, tables, token_chunk)
    last = rms_norm(x[:, -1], params["norm"], cfg["rms_norm_eps"])
    return matmul(last, params["head"]).astype(jnp.float32)


def init_params(cfg: Dict[str, Any], seed: int = 0, dtype=jnp.bfloat16):
    """Seeded random weights in the model's pytree (small sizes: the arrays
    are made on the default device)."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * dh
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

    def w(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dtype)

    def gain():
        return (1 + 0.1 * jax.random.normal(next(keys), (d,))).astype(dtype)

    def glu(width, lead=()):
        return {"w_in": w(*lead, d, 2 * width), "w_out": w(*lead, width, d)}

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        hq = cfg["num_attention_heads_per_layer"][i] * dh
        p = {"attn_norm": gain(), "wq": w(d, hq), "wk": w(d, kv),
             "wv": w(d, kv), "wo": w(hq, d), "mlp_norm": gain()}
        if cfg["mlp_layer_types"][i] == "dense":
            p["mlp"] = glu(cfg["intermediate_size"])
        else:
            e = cfg["num_experts"]
            p["moe"] = dict(glu(cfg["moe_intermediate_size"], (e,)),
                            router=w(d, e),
                            shared=glu(cfg["shared_expert_intermediate_size"]))
        layers.append(p)
    embed = jax.random.normal(next(keys), (cfg["vocab_size"], d), jnp.float32)
    return {"embed": embed.astype(dtype), "layers": layers, "norm": gain(),
            "head": w(d, cfg["vocab_size"])}


def build(config, seq: int, batch: Optional[int] = None,
          dtype=jnp.bfloat16, seed: int = 0, params=None,
          token_chunk: Optional[int] = None) -> JaxModel:
    """Stream-ready model: one frame = ``[seq]`` int32 token ids, the answer
    its next token's float32 logits ``[vocab]``.  ``config``: the published
    ``config.json`` (dict or path); ``params``: its weights (``init_params``'
    pytree), seeded random ones if left out."""
    cfg = load_config(config)
    dtype = jnp.dtype(dtype)  # a launch string gives its name
    if params is None:
        params = init_params(cfg, seed, dtype)
    shape = (seq,) if batch is None else (batch, seq)
    return JaxModel(
        apply=lambda p, ids: apply(cfg, p, ids, dtype, token_chunk),
        params=params,
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.int32, shape=shape)),
        name=f"laguna_{cfg['hidden_size']}x{cfg['num_hidden_layers']}",
    )


def quantize_weights(params, keep=("router", "embed")):
    """``params`` with every 2-D weight W8A8 (``ops/quant``) but the
    subtrees under the keys in ``keep`` and the routed experts (a ``moe``
    dict's own ``w_in`` / ``w_out``)."""
    from ..ops.quant import quantize_weight

    def walk(p, inside_moe=False):
        if isinstance(p, dict):
            return {k: (v if k in keep or
                        (inside_moe and k in ("w_in", "w_out"))
                        else walk(v, k == "moe"))
                    for k, v in p.items()}
        if isinstance(p, list):
            return [walk(v) for v in p]
        return quantize_weight(p) if getattr(p, "ndim", 0) == 2 else p

    return walk(params)


def build_quantized(**kwargs) -> JaxModel:
    """The step below bfloat16: the projections, the dense MLP, the shared
    experts and the head W8A8 (``ops/quant``); the routed experts and the
    router stay as they are.  Takes :func:`build`'s kwargs."""
    model = build(**kwargs)
    model.params = quantize_weights(model.params)
    return model
