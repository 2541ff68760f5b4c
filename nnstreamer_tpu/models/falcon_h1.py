"""Decoder-only hybrid token model of the ``falcon_h1`` family on the
streaming path: in every layer a Mamba-2 mixer and grouped attention read
the same normed input side by side and add into one residual.

One frame is a window of ``T`` token ids; the program is a whole forward
pass over it and returns the logits of the last position.  The model is
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

The causal conv and the gated norm run through XLA around the scan.
``layers`` (published indices) or ``num_hidden_layers`` says how many
layers are built: all layers are alike, so only their number matters here.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..backends.jax_backend import JaxModel
from ..ops.fused_attention import attention
from ..ops.ssm_scan import ssd_scan
from ..parallel.moe import matmul
from ..spec import TensorSpec, TensorsSpec
from .laguna import load_config, rms_norm, rotary_tables

F32 = jnp.float32


def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The mixer's widths: ``d_ssm`` (heads x head), ``bc`` (B's or C's),
    the conv's channels (x, B and C) and the in-projection's outputs (z,
    xBC, dt)."""
    d_ssm = cfg["mamba_d_ssm"] or cfg["mamba_expand"] * cfg["hidden_size"]
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    conv = d_ssm + 2 * bc
    return {"d_ssm": d_ssm, "bc": bc, "conv": conv,
            "in": d_ssm + conv + cfg["mamba_n_heads"]}


def mup_vector(cfg: Dict[str, Any]) -> np.ndarray:
    """μ over the in-projection's outputs: ``ssm_multipliers`` over z, x, B,
    C and dt, float32."""
    w = widths(cfg)
    mz, mx, mb, mc, mdt = cfg["ssm_multipliers"]
    parts = [(w["d_ssm"], mz), (w["d_ssm"], mx), (w["bc"], mb), (w["bc"], mc),
             (cfg["mamba_n_heads"], mdt)]
    return np.concatenate([np.full(n, m, np.float32) for n, m in parts])


def causal_conv(x, weight, bias):
    """Depthwise causal conv over time: ``x`` ``[B, T, C]``, ``weight``
    ``[K, C]`` (tap ``K - 1`` reads the current token), ``bias`` ``[C]`` or
    None; float32 sums, the result in ``x``'s type."""
    k, t = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(F32)
    out = sum(padded[:, i:i + t] * weight[i].astype(F32) for i in range(k))
    if bias is not None:
        out = out + bias.astype(F32)
    return out.astype(x.dtype)


def gated_norm(y, z, gain, groups: int, eps: float):
    """``RMSNorm(y ⊙ SiLU(z))`` over each of ``groups`` groups of channels,
    times ``gain``, in float32; the result in ``y``'s type."""
    h = y.astype(F32) * jax.nn.silu(z.astype(F32))
    lead, width = h.shape[:-1], h.shape[-1]
    h = h.reshape(*lead, groups, width // groups)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h.reshape(*lead, width) * gain.astype(F32)).astype(y.dtype)


def mixer(cfg: Dict[str, Any], p, h, low: bool = False):
    """The Mamba-2 branch over the normed ``h`` ``[B, T, d]``."""
    w = widths(cfg)
    d_ssm, bc, groups = w["d_ssm"], w["bc"], cfg["mamba_n_groups"]
    zxbcdt = (matmul(h * cfg["ssm_in_multiplier"], p["w_in"]).astype(F32)
              * mup_vector(cfg)).astype(h.dtype)
    z, xbc, dt = jnp.split(zxbcdt, [d_ssm, d_ssm + w["conv"]], axis=-1)
    xbc = jax.nn.silu(causal_conv(
        xbc, p["conv_w"], p["conv_b"] if cfg["mamba_conv_bias"] else None))
    x, b, c = jnp.split(xbc, [d_ssm, d_ssm + bc], axis=-1)
    delta = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
    y = ssd_scan(x, delta, -jnp.exp(p["A_log"].astype(F32)), b, c,
                 p["D"].astype(F32), cfg["mamba_chunk_size"], groups, low)
    g = gated_norm(y, z, p["norm"], groups, cfg["rms_norm_eps"])
    return matmul(g, p["w_out"]) * cfg["ssm_out_multiplier"]


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
    if ids.ndim == 1:
        return apply(cfg, params, ids[None], dtype, low)[0]
    tables = rotary_tables({"rope_theta": cfg["rope_theta"]},
                           cfg["head_dim"], ids.shape[-1])
    x = (jnp.asarray(params["embed"])[ids].astype(dtype)
         * cfg["embedding_multiplier"])
    for p in params["layers"]:
        x = layer(cfg, p, x, tables, low)
    last = rms_norm(x[:, -1], params["norm"], cfg["rms_norm_eps"])
    return (matmul(last, params["head"]).astype(F32)
            * cfg["lm_head_multiplier"])


def depth(cfg: Dict[str, Any]) -> int:
    return len(cfg.get("layers") or range(cfg["num_hidden_layers"]))


def init_params(cfg: Dict[str, Any], seed: int = 0, dtype=jnp.bfloat16):
    """Seeded random weights in the model's pytree (small sizes: the arrays
    are made on the default device) at unit scale through the multipliers:
    a matrix ``N(0, 1 / fan-in)`` over the μP multiplier(s) its product
    meets, the embedding ``N(0, 1)`` over ``embedding_multiplier``, so that
    every branch adds at the stream's scale (as the benchmark's kind draws
    them); the gains ``N(1, 0.1)``, the conv ``U(±1 / sqrt(taps))``, and
    ``A_log``, ``dt_bias`` as Mamba-2 draws them (``A`` in [1, 16], ``Δ`` at
    the bias in [1e-3, 1e-1]), ``D`` in [0.5, 1.5]."""
    d, dh, w = cfg["hidden_size"], cfg["head_dim"], widths(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    nh, taps = cfg["mamba_n_heads"], cfg["mamba_d_conv"]
    f, (gate_m, down_m) = cfg["intermediate_size"], cfg["mlp_multipliers"]
    into = cfg["attention_in_multiplier"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

    def matrix(*shape, after=1.0):
        return (jax.random.normal(next(keys), shape, F32) * shape[-2] ** -0.5
                / jnp.asarray(after, F32)).astype(dtype)

    def gain(n=d):
        return (1 + 0.1 * jax.random.normal(next(keys), (n,))).astype(dtype)

    def uniform(lo, hi, *shape):
        return jax.random.uniform(next(keys), shape, F32, lo, hi)

    layers = []
    for _ in range(depth(cfg)):
        dt0 = jnp.exp(uniform(np.log(1e-3), np.log(1e-1), nh))
        bound = taps ** -0.5
        mamba = {"w_in": matrix(d, w["in"], after=cfg["ssm_in_multiplier"]
                                * mup_vector(cfg)),
                 "conv_w": uniform(-bound, bound, taps, w["conv"]).astype(dtype),
                 "conv_b": uniform(-bound, bound, w["conv"]).astype(dtype),
                 "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
                 "A_log": jnp.log(uniform(1, 16, nh)).astype(dtype),
                 "D": uniform(0.5, 1.5, nh).astype(dtype),
                 "norm": gain(w["d_ssm"]),
                 "w_out": matrix(w["d_ssm"], d, after=cfg["ssm_out_multiplier"])}
        layers.append({
            "input_norm": gain(),
            "attn": {"wq": matrix(d, heads * dh, after=into),
                     "wk": matrix(d, kv * dh,
                                  after=into * cfg["key_multiplier"]),
                     "wv": matrix(d, kv * dh, after=into),
                     "wo": matrix(heads * dh, d,
                                  after=cfg["attention_out_multiplier"])},
            "mamba": mamba, "ff_norm": gain(),
            "mlp": {"w_in": matrix(d, 2 * f, after=np.repeat([gate_m, 1.0], f)),
                    "w_out": matrix(f, d, after=down_m)}})
    embed = jax.random.normal(next(keys), (cfg["vocab_size"], d), F32)
    return {"embed": (embed / cfg["embedding_multiplier"]).astype(dtype),
            "layers": layers, "norm": gain(),
            "head": matrix(d, cfg["vocab_size"],
                           after=cfg["lm_head_multiplier"])}


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
    dtype = jnp.dtype(dtype)  # a launch string gives its name
    if params is None:
        params = init_params(cfg, seed, dtype)
    shape = (seq,) if batch is None else (batch, seq)
    return JaxModel(
        apply=lambda p, ids: apply(cfg, p, ids, dtype, bool(low)),
        params=params,
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.int32, shape=shape)),
        name=f"falcon_h1_{cfg['hidden_size']}x{depth(cfg)}",
    )
