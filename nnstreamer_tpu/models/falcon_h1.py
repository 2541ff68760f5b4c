"""Decoder-only hybrid token model of the ``falcon_h1`` family on the
streaming path: in every layer a Mamba-2 mixer and grouped attention read
the same normed input side by side and add into one residual.

The program is ``models/decoder.py``'s forward pass around these layers,
read from the published ``config.json`` keys; every μP multiplier is read
from there and applied to the activations where the family's modelling code
applies it (none is folded into a weight).  Per layer, with ``h =
RMSNorm(x)``:

- *attention*: ``q = h' W_q``, ``k = (h' W_k) · key_multiplier``, ``v = h'
  W_v`` with ``h' = h · attention_in_multiplier``, ``num_attention_heads``
  over ``num_key_value_heads`` heads of ``head_dim``, rotary on the whole
  head (``rope_theta``, the half-split layout), causal softmax at
  ``head_dim ** -0.5`` (``ops/fused_attention.attention`` with the tables:
  the blocked kernel rotates q and k in VMEM), then ``(o W_o) ·
  attention_out_multiplier``;
- *mixer*: ``[z | xBC | dt] = ((h · ssm_in_multiplier) W_in) ⊙ μ``, μ the
  five ``ssm_multipliers`` over the segments z, x, B, C and dt; ``xBC =
  SiLU(causal depthwise conv1d(xBC))`` (``mamba_d_conv`` taps, a bias if
  ``mamba_conv_bias``); x in ``mamba_n_heads`` heads of ``mamba_d_head``,
  B and C in ``mamba_n_groups`` groups of ``mamba_d_state``; ``Δ =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the chunked scan
  ``ops/ssm_scan.ssd_scan`` in chunks of ``mamba_chunk_size`` (the Pallas
  kernel ``nns_ssd_scan`` where a one-device TPU program's shapes tile);
  the gated RMSNorm ``norm(y ⊙ SiLU(z))`` over each of ``mamba_n_groups``
  groups of channels, then its gain (``mamba_norm_before_gate: false``);
  then ``(g W_out) · ssm_out_multiplier``;
- ``x ← x + (m + a)``, then the SwiGLU ``W_down(SiLU((f W_gate) · m_g) ⊙
  (f W_up)) · m_d`` of ``intermediate_size`` over ``f = RMSNorm(x)``,
  ``(m_g, m_d)`` = ``mlp_multipliers``;
- the embedding times ``embedding_multiplier``, the head (untied) over the
  last position times ``lm_head_multiplier``; no biases but the conv's.

The mixer is ``models/decoder.py``'s, which ``models/nemotron_h.py`` runs
over its own config's widths in these keys.  ``layers`` (published
indices) or ``num_hidden_layers`` says how many layers are built: all
layers are alike, so only their number matters here.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..backends.jax_backend import JaxModel
from ..ops.fused_attention import attention
from ..parallel.moe import matmul
from . import decoder
# widths, mup_vector: read off this module by the benchmark's kind
from .decoder import (  # noqa: F401
    layer_ids, load_config, mixer, mixer_params, mup_vector, rms_norm,
    rotary_tables, widths)


def attention_branch(cfg: Dict[str, Any], p, h, tables):
    h = h * cfg["attention_in_multiplier"]
    # q and k go in as the products left them: the lowering rotates them
    o = attention(matmul(h, p["wq"]), cfg["num_attention_heads"], True,
                  k=matmul(h, p["wk"]) * cfg["key_multiplier"],
                  v=matmul(h, p["wv"]),
                  n_kv_heads=cfg["num_key_value_heads"], rotary=tables)
    return matmul(o, p["wo"]) * cfg["attention_out_multiplier"]


def feed_forward(cfg: Dict[str, Any], p, f):
    gate_m, down_m = cfg["mlp_multipliers"]
    gate, up = jnp.split(matmul(f, p["w_in"]), 2, axis=-1)
    return matmul(jax.nn.silu(gate * gate_m) * up, p["w_out"]) * down_m


def layer(cfg: Dict[str, Any], p, x, tables, low: bool = False):
    """One layer over ``x`` ``[B, T, d]``: both mixers read one normed
    input and add into one residual, then the feed-forward."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p["input_norm"], eps)
    x = x + (mixer(cfg, p["mamba"], h, low)
             + attention_branch(cfg, p["attn"], h, tables))
    return x + feed_forward(cfg, p["mlp"], rms_norm(x, p["ff_norm"], eps))


def apply(cfg: Dict[str, Any], params, ids, dtype=jnp.bfloat16,
          low: bool = False):
    """``ids`` ``[B, T]`` int32 → float32 logits ``[B, vocab]`` of the last
    position (one window ``[T]`` → ``[vocab]``)."""
    def layers(x):
        tables = rotary_tables({"rope_theta": cfg["rope_theta"]},
                               cfg["head_dim"], x.shape[1])
        x = x * cfg["embedding_multiplier"]
        for p in params["layers"]:
            x = layer(cfg, p, x, tables, low)
        return x

    return (decoder.apply(params, ids, dtype, layers, cfg["rms_norm_eps"])
            * cfg["lm_head_multiplier"])


def init_params(cfg: Dict[str, Any], seed: int = 0, dtype=jnp.bfloat16):
    """Seeded random weights in the model's pytree (``decoder.Draws``; the
    mixers' ``decoder.mixer_params``) at unit scale through the
    multipliers: a matrix over the μP multiplier(s) its product meets, the
    embedding over ``embedding_multiplier``, so that every branch adds at
    the stream's scale (as the benchmark's kind draws them)."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, (gate_m, down_m) = cfg["intermediate_size"], cfg["mlp_multipliers"]
    into = cfg["attention_in_multiplier"]
    draw = decoder.Draws(seed, dtype)
    matrix = draw.matrix
    layers = []
    for _ in layer_ids(cfg):
        mamba = mixer_params(draw, cfg)
        layers.append({
            "input_norm": draw.gain(d),
            "attn": {"wq": matrix(d, heads * dh, after=into),
                     "wk": matrix(d, kv * dh,
                                  after=into * cfg["key_multiplier"]),
                     "wv": matrix(d, kv * dh, after=into),
                     "wo": matrix(heads * dh, d,
                                  after=cfg["attention_out_multiplier"])},
            "mamba": mamba, "ff_norm": draw.gain(d),
            "mlp": {"w_in": matrix(d, 2 * f,
                                   after=np.repeat([gate_m, 1.0], f)),
                    "w_out": matrix(f, d, after=down_m)}})
    return draw.model(cfg, layers)


def build(config, seq: int, batch: Optional[int] = None,
          dtype=jnp.bfloat16, seed: int = 0, params=None,
          low: bool = False) -> JaxModel:
    """Stream-ready model: one frame = ``[seq]`` int32 token ids, the answer
    its next token's float32 logits ``[vocab]``.  ``config``: the published
    ``config.json`` (dict or path); ``params``: its weights
    (``init_params``' pytree), seeded random ones if left out.  ``low``:
    the scan's carried state and decays in bfloat16 (the benchmark's
    control)."""
    cfg = load_config(config)
    return decoder.build(
        cfg, init_params,
        lambda p, ids, dtype: apply(cfg, p, ids, dtype, bool(low)),
        f"falcon_h1_{cfg['hidden_size']}x{len(layer_ids(cfg))}", seq, batch,
        dtype, seed, params)
