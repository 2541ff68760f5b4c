"""What the decoder-only token models share (``laguna``, ``glm_dsa``,
``axk1``, ``falcon_h1``, ``nemotron_h``), and no family's own.

One frame is a window of ``T`` token ids; a family's program is a whole
forward pass over it and returns the logits of the last position (what a
serving engine calls a prefill-only request).  :func:`build` is the
stream-ready model every family builds around its config and weights,
:func:`apply` the frame of every forward pass around the family's layers;
beside them the published configs' reader, rotary tables, RMSNorm, the
W8A8 step below bfloat16 that every family's ``build_quantized`` takes, and
the Mamba-2 mixer of the hybrids (:func:`mixer`).
"""

from __future__ import annotations

import json
import math
import os
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..backends.jax_backend import JaxModel
from ..ops.ssm_scan import ssd_scan
from ..parallel.moe import matmul
from ..spec import TensorSpec, TensorsSpec

F32 = jnp.float32


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


def layer_ids(cfg: Dict[str, Any]) -> List[int]:
    """The published indices of the layers built: ``layers``, a cut's own
    key, or the first ``num_hidden_layers``."""
    return list(cfg.get("layers") or range(cfg["num_hidden_layers"]))


def experts_held(cfg: Dict[str, Any]) -> Sequence[int]:
    """``(first, count)`` of the routed experts this chip holds:
    ``experts_held``, a cut's own key, or all ``n_routed_experts``."""
    return tuple(cfg.get("experts_held") or (0, cfg["n_routed_experts"]))


def rms_norm(x, gain, eps: float):
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * gain.astype(jnp.float32)).astype(x.dtype)


def apply(params, ids, dtype, layers: Callable, eps: float):
    """``ids`` ``[B, T]`` int32 → float32 logits ``[B, vocab]`` of the last
    position (one window ``[T]`` → ``[vocab]``): the embedding's rows in
    ``dtype``, ``layers(x)`` (the family's layers over ``x`` ``[B, T,
    d]``), then RMSNorm at ``eps`` and the head over the last position."""
    if ids.ndim == 1:
        return apply(params, ids[None], dtype, layers, eps)[0]
    x = layers(jnp.asarray(params["embed"])[ids].astype(dtype))
    last = rms_norm(x[:, -1], params["norm"], eps)
    return matmul(last, params["head"]).astype(jnp.float32)


def build(cfg: Dict[str, Any], init_params: Callable, forward: Callable,
          name: str, seq: int, batch: Optional[int], dtype, seed: int,
          params) -> JaxModel:
    """Stream-ready model: one frame = ``[seq]`` int32 token ids (``[batch,
    seq]`` with a batch), the answer ``forward(params, ids, dtype)``, its
    next token's float32 logits.  ``params``: the weights,
    ``init_params(cfg, seed, dtype)``'s seeded ones if ``None``."""
    dtype = jnp.dtype(dtype)  # a launch string gives its name
    if params is None:
        params = init_params(cfg, seed, dtype)
    shape = (seq,) if batch is None else (batch, seq)
    return JaxModel(
        apply=lambda p, ids: forward(p, ids, dtype),
        params=params,
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.int32, shape=shape)),
        name=name,
    )


class Draws:
    """A family's seeded random weights (small sizes: the arrays are made on
    the default device), each array from the next key of ``seed``'s, in
    ``dtype`` but the uniforms: matrices ``N(0, 1 / fan-in)`` over the μP
    multiplier(s) their product meets where there are any, gains ``N(mean,
    0.1)``, and around the layers the embedding ``N(0, 1)``, the final gain
    and the head (:meth:`model`)."""

    def __init__(self, seed: int, dtype):
        self.keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))
        self.dtype = dtype

    def matrix(self, *shape, after=None):
        a = jax.random.normal(next(self.keys), shape, F32) * shape[-2] ** -0.5
        if after is not None:
            a = a / jnp.asarray(after, F32)
        return a.astype(self.dtype)

    def gain(self, n: int, mean: float = 1.0):
        return (mean + 0.1 * jax.random.normal(next(self.keys), (n,))).astype(
            self.dtype)

    def uniform(self, lo, hi, *shape):
        return jax.random.uniform(next(self.keys), shape, F32, lo, hi)

    def glu(self, d: int, width: int, lead=()):
        """A SwiGLU's ``w_in`` ``[.., d, 2 width]`` and ``w_out``."""
        return {"w_in": self.matrix(*lead, d, 2 * width),
                "w_out": self.matrix(*lead, width, d)}

    def model(self, cfg: Dict[str, Any], layers):
        """The pytree around ``layers``; the embedding over
        ``embedding_multiplier`` and the head over ``lm_head_multiplier``
        where the config holds them."""
        d, vocab = cfg["hidden_size"], cfg["vocab_size"]
        embed = jax.random.normal(next(self.keys), (vocab, d), F32)
        if cfg.get("embedding_multiplier") is not None:
            embed = embed / cfg["embedding_multiplier"]
        return {"embed": embed.astype(self.dtype), "layers": layers,
                "norm": self.gain(d),
                "head": self.matrix(d, vocab,
                                    after=cfg.get("lm_head_multiplier"))}


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


# -- the Mamba-2 mixer of the hybrids -----------------------------------------
#
# Over a config's widths in ``falcon_h1``'s keys (``nemotron_h.mixer_config``
# gives its own in them); a μP multiplier the config does not hold is 1, and
# the mixer then multiplies by nothing.  The causal conv and the gated norm
# run through XLA around the scan.

def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The mixer's widths: ``d_ssm`` (heads x head), ``bc`` (B's or C's),
    the conv's channels (x, B and C) and the in-projection's outputs (z,
    xBC, dt)."""
    d_ssm = cfg["mamba_d_ssm"] or cfg["mamba_expand"] * cfg["hidden_size"]
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    conv = d_ssm + 2 * bc
    return {"d_ssm": d_ssm, "bc": bc, "conv": conv,
            "in": d_ssm + conv + cfg["mamba_n_heads"]}


def mup_vector(cfg: Dict[str, Any]) -> Optional[np.ndarray]:
    """μ over the in-projection's outputs: ``ssm_multipliers`` over z, x, B,
    C and dt, float32; ``None`` where the config has none."""
    if cfg.get("ssm_multipliers") is None:
        return None
    w = widths(cfg)
    mz, mx, mb, mc, mdt = cfg["ssm_multipliers"]
    parts = [(w["d_ssm"], mz), (w["d_ssm"], mx), (w["bc"], mb), (w["bc"], mc),
             (cfg["mamba_n_heads"], mdt)]
    return np.concatenate([np.full(n, m, np.float32) for n, m in parts])


def _times(a, multiplier):
    """``a · multiplier``; an absent multiplier is 1 and multiplies
    nothing."""
    return a if multiplier is None else a * multiplier


def mixer_params(draw: Draws, cfg: Dict[str, Any]):
    """A mixer's seeded weights at ``cfg``'s widths: ``w_in`` and ``w_out``
    over the multipliers their products meet, the conv ``U(±1 /
    sqrt(taps))``, ``A_log`` and ``dt_bias`` as Mamba-2 draws them (``A`` in
    [1, 16], ``Δ`` at the bias in [1e-3, 1e-1]), ``D`` in [0.5, 1.5] and the
    gated norm's gain ``N(1, 0.1)``."""
    w, d, dtype = widths(cfg), cfg["hidden_size"], draw.dtype
    nh, bound = cfg["mamba_n_heads"], cfg["mamba_d_conv"] ** -0.5
    into = _times(mup_vector(cfg), cfg.get("ssm_in_multiplier"))
    dt0 = jnp.exp(draw.uniform(np.log(1e-3), np.log(1e-1), nh))
    return {"w_in": draw.matrix(d, w["in"], after=into),
            "conv_w": draw.uniform(-bound, bound, cfg["mamba_d_conv"],
                                   w["conv"]).astype(dtype),
            "conv_b": draw.uniform(-bound, bound, w["conv"]).astype(dtype),
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
            "A_log": jnp.log(draw.uniform(1, 16, nh)).astype(dtype),
            "D": draw.uniform(0.5, 1.5, nh).astype(dtype),
            "norm": draw.gain(w["d_ssm"]),
            "w_out": draw.matrix(w["d_ssm"], d,
                                 after=cfg.get("ssm_out_multiplier"))}


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
    """The Mamba-2 branch over the normed ``h`` ``[B, T, d]``; the
    multipliers ``ssm_in_multiplier``, ``ssm_multipliers`` and
    ``ssm_out_multiplier`` where the config holds them."""
    w = widths(cfg)
    d_ssm, bc, groups = w["d_ssm"], w["bc"], cfg["mamba_n_groups"]
    zxbcdt = matmul(_times(h, cfg.get("ssm_in_multiplier")), p["w_in"])
    mup = mup_vector(cfg)
    if mup is not None:
        zxbcdt = (zxbcdt.astype(F32) * mup).astype(h.dtype)
    z, xbc, dt = jnp.split(zxbcdt, [d_ssm, d_ssm + w["conv"]], axis=-1)
    xbc = jax.nn.silu(causal_conv(
        xbc, p["conv_w"], p["conv_b"] if cfg["mamba_conv_bias"] else None))
    x, b, c = jnp.split(xbc, [d_ssm, d_ssm + bc], axis=-1)
    delta = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
    y = ssd_scan(x, delta, -jnp.exp(p["A_log"].astype(F32)), b, c,
                 p["D"].astype(F32), cfg["mamba_chunk_size"], groups, low)
    g = gated_norm(y, z, p["norm"], groups, cfg["rms_norm_eps"])
    return _times(matmul(g, p["w_out"]), cfg.get("ssm_out_multiplier"))
