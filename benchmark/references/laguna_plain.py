"""Plain reference of the ``laguna`` configurations: the forward pass as the
published ``config.json`` describes it, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no kernel, no grouped
product and no sorting.  Imports nothing of the program.

What the config leaves open is set as the configuration's ``assumed`` list
says, and marked ``ASSUMED`` below where it enters.

It walks the model layer by layer and keeps the residual stream of all
sampled frames in float32.  The weights arrive on the host in the served
type; one layer's (one block of experts') are cast to float32 on the device
at a time, attention runs a frame and a key/value head's group of query
heads at a time with its explicit ``T x T`` mask, and every expert runs over
every token, its result kept where the router chose it (a masked dense sum).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 32      # experts cast to float32 and run at a time
F32 = jnp.float32


def _f32(a):
    """On the device in the type it was served in, float32 from there."""
    return jnp.asarray(a).astype(F32)


def inverse_frequencies(rope: Dict[str, Any], rot: int) -> np.ndarray:
    """One per pair of rotated dims.  ``default``: theta^(-2i/rot).  ``yarn``
    (Peng et al. 2023, as ``transformers`` computes it): the interpolated
    frequency (divided by ``factor``) where a dim turns fewer than
    ``beta_slow`` times over the original context, the plain one where it
    turns more than ``beta_fast`` times, a linear ramp between."""
    inv = np.array([rope["rope_theta"] ** (-2.0 * i / rot)
                    for i in range(rot // 2)])
    if rope.get("rope_type", "default") != "yarn":
        return inv
    orig = rope["original_max_position_embeddings"]

    def dim_of(turns):  # the (fractional) pair index that turns that often
        return (rot * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(rope["rope_theta"])))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), rot - 1)
    out = np.empty_like(inv)
    for i in range(rot // 2):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out[i] = (1 - ramp) * inv[i] + ramp * inv[i] / rope["factor"]
    return out


def rotary(rope: Dict[str, Any], head_dim: int, t: int):
    """cos and sin ``[t, rot/2]``; YaRN scales both by ``attention_factor``."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1))
    angle = np.arange(t)[:, None] * inverse_frequencies(rope, rot)[None, :]
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        scale = rope.get("attention_factor") \
            or 0.1 * math.log(rope["factor"]) + 1.0
    return (jnp.asarray(np.cos(angle) * scale, F32),
            jnp.asarray(np.sin(angle) * scale, F32))


def rotate(x, cos, sin):
    """``x`` ``[t, heads, head_dim]``: the first ``rot`` dims of a head
    rotated, pair ``i`` being dims ``(i, i + rot/2)`` (the checkpoint
    layout of ``transformers``' ``rotate_half``), the rest untouched."""
    half = cos.shape[-1]
    a, b = x[..., :half], x[..., half:2 * half]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, x[..., 2 * half:]],
                           axis=-1)


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


@partial(jax.jit, static_argnames=("window",))
def attend_group(h, wq, wk, wv, wo, cos, sin, window):
    """One frame ``h`` ``[t, d]`` through one key/value head and its group
    of query heads: ``wq`` ``[d, g, dh]``, ``wk``/``wv`` ``[d, dh]``, ``wo``
    ``[g, dh, d]``.  Returns the group's part of the output ``[t, d]``."""
    t, dh = h.shape[0], wk.shape[-1]
    q = rotate(jnp.einsum("td,dgh->tgh", h, wq), cos, sin)
    k = rotate((h @ wk)[:, None, :], cos, sin)[:, 0]
    v = h @ wv
    scores = jnp.einsum("tgh,sh->gts", q, k) * dh ** -0.5
    row, col = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = col <= row                       # causal
    if window is not None:
        seen &= col > row - window          # keys i - window + 1 ... i
    scores = jnp.where(seen[None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("gts,sh,ghd->td", weights, v, wo)


@jax.jit
def glu(x, w_in, w_out):
    """ASSUMED: ``gating: true`` is the gated (GLU) feed-forward with SiLU,
    ``w_in`` = ``[gate | up]``."""
    width = w_out.shape[-2]
    y = x @ w_in
    return (jax.nn.silu(y[..., :width]) * y[..., width:]) @ w_out


@partial(jax.jit, static_argnames=("top_k",))
def route(h, router, top_k, scaling):
    """ASSUMED: scores by sigmoid, the ``top_k`` highest of a token (the
    lower index first among equals) renormalised to sum 1, then times
    ``moe_routed_scaling_factor``.  Returns ``[tokens, experts]`` weights,
    0 where an expert was not chosen."""
    scores = jax.nn.sigmoid(h @ router)
    chosen = jnp.argsort(-scores, axis=-1, stable=True)[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / picked.sum(axis=-1, keepdims=True) * scaling
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(picked)


@jax.jit
def experts_block(h, gates, w_in, w_out):
    """A block of experts over every token, each expert's result times the
    token's weight for it (0 for most)."""
    width = w_out.shape[-2]
    y = jnp.einsum("nd,edf->enf", h, w_in)
    y = jax.nn.silu(y[..., :width]) * y[..., width:]
    return jnp.einsum("enf,efd,ne->nd", y, w_out, gates)


def attention_layer(s, i, p, x):
    kind = s["layer_types"][i]
    heads, kv = s["num_attention_heads_per_layer"][i], s["num_key_value_heads"]
    dh, d = s["head_dim"], s["hidden_size"]
    group = heads // kv
    window = s["sliding_window"] if kind == "sliding_attention" else None
    cos, sin = rotary(s["rope_parameters"][kind], dh, x.shape[1])
    gain = _f32(p["attn_norm"])
    wq = _f32(p["wq"]).reshape(d, kv, group, dh)
    wk, wv = (_f32(p[n]).reshape(d, kv, dh) for n in ("wk", "wv"))
    wo = _f32(p["wo"]).reshape(kv, group, dh, d)
    out = []
    for frame in x:
        h = rms_norm(frame, gain, s["rms_norm_eps"])
        o = sum(attend_group(h, wq[:, g], wk[:, g], wv[:, g], wo[g], cos, sin,
                             window) for g in range(kv))
        out.append(frame + o)
    return jnp.stack(out)


def mlp_layer(s, i, p, x):
    n, t, d = x.shape
    h = rms_norm(x, _f32(p["mlp_norm"]), s["rms_norm_eps"]).reshape(n * t, d)
    if s["mlp_layer_types"][i] == "dense":
        y = glu(h, _f32(p["mlp"]["w_in"]), _f32(p["mlp"]["w_out"]))
        return x + y.reshape(x.shape)
    moe = p["moe"]
    gates = route(h, _f32(moe["router"]), s["num_experts_per_tok"],
                  float(s["moe_routed_scaling_factor"]))
    # ASSUMED: the shared expert is added unweighted
    y = glu(h, _f32(moe["shared"]["w_in"]), _f32(moe["shared"]["w_out"]))
    y = list(y.reshape(x.shape))
    for e in range(0, s["num_experts"], EXPERT_BLOCK):
        at = slice(e, e + EXPERT_BLOCK)
        w_in, w_out = _f32(moe["w_in"][at]), _f32(moe["w_out"][at])
        for f in range(n):  # a frame's tokens at a time
            rows = slice(f * t, (f + 1) * t)
            y[f] = y[f] + experts_block(h[rows], gates[rows, at], w_in, w_out)
    return x + jnp.stack(y)


def forward(sizes: Dict[str, Any], cfg: Dict[str, Any], weights,
            frames: np.ndarray) -> np.ndarray:
    """Logits ``(n, vocab)`` float32 of the last position of ``frames``
    ``(n, seq)`` int32.  ASSUMED: no q/k norm, no attention output gate."""
    del cfg
    s = sizes
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(weights["embed"])[np.asarray(frames)], F32)
        for i, p in enumerate(weights["layers"]):
            x = attention_layer(s, i, p, x)
            x = mlp_layer(s, i, p, x)
        last = rms_norm(x[:, -1], _f32(weights["norm"]), s["rms_norm_eps"])
        return np.asarray(last @ _f32(weights["head"]), np.float32)
