"""Decoder-only hybrid token model of the ``nemotron_h`` family on the
streaming path: a sequence of single-mixer layers, each a Mamba-2 mixer,
grouped attention or a latent mixture of experts, as
``hybrid_override_pattern`` spells them.

One frame is a window of ``T`` token ids; the program is a whole forward
pass over it and returns the logits of the last position, as
``models/falcon_h1.py`` does.  The model is read from the published
``config.json`` keys; every layer is ``x ← x + mixer(RMSNorm(x))`` at
``layer_norm_epsilon``, its mixer chosen by one character of the pattern:

- ``M``, *Mamba-2*: ``models/falcon_h1.mixer`` over this config's widths
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

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..backends.jax_backend import JaxModel
from ..ops.fused_attention import attention
from ..parallel.moe import matmul, moe_top_k
from ..spec import TensorSpec, TensorsSpec
from .falcon_h1 import mixer, widths
from .laguna import load_config, quantize_weights, rms_norm

F32 = jnp.float32
KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def layer_ids(cfg: Dict[str, Any]) -> List[int]:
    """The published indices of the layers built."""
    return list(cfg.get("layers") or range(cfg["num_hidden_layers"]))


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


def experts_held(cfg: Dict[str, Any]) -> Sequence[int]:
    """``(first, count)`` of the routed experts this chip holds."""
    return tuple(cfg.get("experts_held") or (0, cfg["n_routed_experts"]))


def mixer_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """This family's Mamba-2 keys in the ones ``falcon_h1.mixer`` reads."""
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
    if ids.ndim == 1:
        return apply(cfg, params, ids[None], dtype, low, token_chunk)[0]
    x = jnp.asarray(params["embed"])[ids].astype(dtype)
    for kind, p in zip(layer_kinds(cfg), params["layers"]):
        x = layer(cfg, kind, p, x, low, token_chunk)
    last = rms_norm(x[:, -1], params["norm"], cfg["norm_eps"])
    return matmul(last, params["head"]).astype(F32)


def init_params(cfg: Dict[str, Any], seed: int = 0, dtype=jnp.bfloat16):
    """Seeded random weights in the model's pytree (small sizes: the arrays
    are made on the default device): matrices ``N(0, 1 / fan-in)``, the
    embedding ``N(0, 1)``, the gains ``N(1, 0.1)``, the selection bias
    ``N(0, 0.1)``; the mixer's small arrays as Mamba-2 draws them (``A`` in
    [1, 16], ``Δ`` at the bias in [1e-3, 1e-1], the conv ``U(±1 /
    sqrt(taps))``), ``D`` in [0.5, 1.5]."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    w = widths(mixer_config(cfg))
    nh, taps = cfg["mamba_num_heads"], cfg["conv_kernel"]
    e, (_, held) = cfg["n_routed_experts"], experts_held(cfg)
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

    def matrix(*shape):
        return (jax.random.normal(next(keys), shape, F32)
                * shape[-2] ** -0.5).astype(dtype)

    def gain(n=d, mean=1.0):
        return (mean + 0.1 * jax.random.normal(next(keys), (n,))).astype(dtype)

    def uniform(lo, hi, *shape):
        return jax.random.uniform(next(keys), shape, F32, lo, hi)

    layers = []
    for kind in layer_kinds(cfg):
        if kind == "M":
            dt0 = jnp.exp(uniform(np.log(1e-3), np.log(1e-1), nh))
            bound = taps ** -0.5
            p = {"mixer": {
                "w_in": matrix(d, w["in"]),
                "conv_w": uniform(-bound, bound, taps, w["conv"]).astype(dtype),
                "conv_b": uniform(-bound, bound, w["conv"]).astype(dtype),
                "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
                "A_log": jnp.log(uniform(1, 16, nh)).astype(dtype),
                "D": uniform(0.5, 1.5, nh).astype(dtype),
                "norm": gain(w["d_ssm"]), "w_out": matrix(w["d_ssm"], d)}}
        elif kind == "*":
            p = {"attn": {"wq": matrix(d, heads * dh), "wk": matrix(d, kv * dh),
                          "wv": matrix(d, kv * dh),
                          "wo": matrix(heads * dh, d)}}
        else:
            p = {"moe": {"router": matrix(d, e), "bias": gain(e, 0.0),
                         "latent_in": matrix(d, lat),
                         "latent_out": matrix(lat, d),
                         "w_in": matrix(held, lat, f),
                         "w_out": matrix(held, f, lat),
                         "shared": {"w_in": matrix(d, fs),
                                    "w_out": matrix(fs, d)}}}
        layers.append(dict(p, norm=gain()))
    embed = jax.random.normal(next(keys), (cfg["vocab_size"], d), F32)
    return {"embed": embed.astype(dtype), "layers": layers, "norm": gain(),
            "head": matrix(d, cfg["vocab_size"])}


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
    dtype = jnp.dtype(dtype)  # a launch string gives its name
    if params is None:
        params = init_params(cfg, seed, dtype)
    kinds = layer_kinds(cfg)
    _say_kinds(kinds)
    shape = (seq,) if batch is None else (batch, seq)
    return JaxModel(
        apply=lambda p, ids: apply(cfg, p, ids, dtype, bool(low),
                                   token_chunk),
        params=params,
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.int32, shape=shape)),
        name=f"nemotron_h_{cfg['hidden_size']}x{len(kinds)}",
    )


def build_quantized(**kwargs) -> JaxModel:
    """The step below bfloat16: the mixers', attention's, latent and shared
    experts' projections and the head W8A8 (``ops/quant``); the conv, the
    router, the embedding and the routed experts stay as they are.  Takes
    :func:`build`'s kwargs (``low=True`` too: the benchmark's control)."""
    model = build(**kwargs)
    model.params = quantize_weights(model.params,
                                    ("router", "embed", "conv_w"))
    return model


def _say_kinds(kinds: List[str]) -> None:
    from ..obs.metrics import REGISTRY

    gauge = REGISTRY.gauge(
        "nnstpu_hybrid_layers",
        "layers of the last hybrid model built, by kind (mamba = a Mamba-2 "
        "mixer, attention = grouped attention, experts = a mixture of "
        "experts)", labelnames=("kind",))
    for char, kind in KINDS.items():
        gauge.set(kinds.count(char), kind=kind)
