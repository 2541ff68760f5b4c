"""Plain reference of the ``axk1`` configurations: the forward pass as the
published ``config.json`` of the ``axk1`` family (``deepseek_v3``-shaped)
describes it, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no kernel, no grouped
product, no sorting of pairs and no scan.  Imports nothing of the program.

What the config leaves open is set as the configuration's ``assumed`` list
says, and marked ``ASSUMED`` below where it enters.

It walks the model layer by layer and keeps the residual stream of all
sampled frames in float32.  The weights arrive on the host in the served
type and in a checkpoint's layout (rotary dims in interleaved pairs); one
layer's (one expert's) are cast to float32 on the device at a time.
Attention is the per-head form with dense scores over every causal key, a
block of query rows at a time so that a 16 k window fits the chip; every
held expert runs over every token, its result kept where the router chose
it.  The share is the configuration's: ``experts_held`` = ``[first, count]``
of the router's ``n_routed_experts`` (what the experts held elsewhere would
add is left out, here as in the program), and the vocabulary is the slice
the weights hold.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 256             # query rows scored at a time


def _f32(a):
    """On the device in the type it was served in, float32 from there."""
    return jnp.asarray(a).astype(F32)


def yarn_mscale(factor: float, s: float) -> float:
    """``0.1 s ln(factor) + 1``; 1 where nothing is stretched."""
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 and s else 1.0


def rotary(s: Dict[str, Any], t: int):
    """cos and sin ``[t, rot/2]``.  ``rope_scaling.type: yarn``: pair ``i``
    turns at ``theta^(-2i/rot)``; the pairs that turn more than
    ``beta_fast`` times within the original context keep that frequency,
    those that turn fewer than ``beta_slow`` times are slowed by ``factor``,
    and the pairs between blend the two linearly; cos and sin are scaled by
    ``m(mscale) / m(mscale_all_dim)``."""
    rot, theta = s["qk_rope_head_dim"], float(s["rope_theta"])
    inv = np.array([theta ** (-2.0 * i / rot) for i in range(rot // 2)])
    scaling = s.get("rope_scaling") or {}
    amplitude = 1.0
    if scaling.get("type") == "yarn":
        factor = float(scaling["factor"])
        orig = scaling["original_max_position_embeddings"]

        def pair_that_turns(times):
            return rot * math.log(orig / (times * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(pair_that_turns(scaling["beta_fast"])), 0)
        high = min(math.ceil(pair_that_turns(scaling["beta_slow"])), rot - 1)
        slowed = np.clip((np.arange(rot // 2) - low)
                         / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv * (1.0 - slowed) + inv / factor * slowed
        amplitude = (yarn_mscale(factor, scaling.get("mscale", 0))
                     / yarn_mscale(factor, scaling.get("mscale_all_dim", 0)))
    angle = np.arange(t)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(angle) * amplitude, F32),
            jnp.asarray(np.sin(angle) * amplitude, F32))


def softmax_scale(s: Dict[str, Any]) -> float:
    """``1 / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, times
    ``m(mscale_all_dim)^2`` under YaRN."""
    scale = (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]) ** -0.5
    scaling = s.get("rope_scaling") or {}
    if scaling.get("type") == "yarn":
        scale *= yarn_mscale(float(scaling["factor"]),
                             scaling.get("mscale_all_dim", 0)) ** 2
    return scale


def rotate(x, cos, sin):
    """``x`` ``[t, heads, rot]`` rotated in interleaved pairs: pair ``i`` is
    dims ``(2i, 2i + 1)``.  ASSUMED: the family's checkpoints hold the rotary
    dims interleaved (``rope_interleave``, which the config does not
    state)."""
    a, b = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


@partial(jax.jit, static_argnames=("scale",))
def attend_rows(q, k, v, row0, scale):
    """A block of query rows ``q`` ``[r, heads, d]`` from row ``row0`` on
    against every key ``k`` ``[t, heads, d]``: dense scores, the softmax
    over the keys up to each query's own position, times ``v`` ``[t, heads,
    dv]``."""
    scores = jnp.einsum("rhd,shd->hrs", q, k) * scale
    rows = row0 + jnp.arange(q.shape[0])[:, None]
    seen = jnp.arange(k.shape[0])[None, :] <= rows
    weights = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hrs,shd->rhd", weights, v)


def attention_layer(s, p, x):
    """Latent attention of one layer over frames ``x`` ``[n, t, d]``."""
    t = x.shape[1]
    heads, rank = s["num_attention_heads"], s["kv_lora_rank"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    eps = s["rms_norm_eps"]
    cos, sin = rotary(s, t)
    scale = softmax_scale(s)
    w = {n: _f32(p[n]) for n in ("attn_norm", "w_dq", "q_norm", "w_uq",
                                 "w_dkv", "kv_norm", "w_uk", "w_uv", "wo")}
    rows = min(ROWS, t)
    out = []
    for frame in x:
        h = rms_norm(frame, w["attn_norm"], eps)
        c_q = rms_norm(h @ w["w_dq"], w["q_norm"], eps)
        q = (c_q @ w["w_uq"]).reshape(t, heads, dn + dr)
        down = h @ w["w_dkv"]
        c_kv = rms_norm(down[:, :rank], w["kv_norm"], eps)
        k_r = rotate(down[:, None, rank:], cos, sin)
        q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], cos, sin)], -1)
        k = jnp.concatenate([(c_kv @ w["w_uk"]).reshape(t, heads, dn),
                             jnp.broadcast_to(k_r, (t, heads, dr))], -1)
        v = (c_kv @ w["w_uv"]).reshape(t, heads, dv)
        o = jnp.concatenate([attend_rows(q[r:r + rows], k, v, r, scale)
                             for r in range(0, t, rows)])
        out.append(frame + o.reshape(t, heads * dv) @ w["wo"])
    return jnp.stack(out)


@jax.jit
def glu(x, w_in, w_out):
    """SwiGLU, ``w_in`` = ``[gate | up]`` (``hidden_act: silu``)."""
    width = w_out.shape[-2]
    y = x @ w_in
    return (jax.nn.silu(y[..., :width]) * y[..., width:]) @ w_out


@partial(jax.jit, static_argnames=("top_k", "n_group", "topk_group"))
def route(h, router, top_k, n_group, topk_group, scaling):
    """``scoring_func: sigmoid`` with the group-limited choice: the experts
    stand in ``n_group`` groups of equal size in index order; ASSUMED (the
    family's published convention): a group's score is the sum of its two
    highest expert scores; the ``topk_group`` highest groups are kept and
    the ``top_k`` highest experts among theirs chosen (the lower index first
    among equals, groups and experts alike), their scores renormalised to
    sum 1 (``norm_topk_prob``) and times ``routed_scaling_factor``.
    ASSUMED: ``topk_method: "none"`` adds no selection bias.  Returns
    ``[tokens, experts]`` weights, 0 where an expert was not chosen."""
    scores = jax.nn.sigmoid(h @ router)
    n, e = scores.shape
    rows = jnp.arange(n)[:, None]
    grouped = scores.reshape(n, n_group, e // n_group)
    best_two = -jnp.sort(-grouped, axis=-1)[..., :2]
    kept = jnp.argsort(-best_two.sum(axis=-1), axis=-1,
                       stable=True)[:, :topk_group]
    open_ = jnp.zeros((n, n_group), bool).at[rows, kept].set(True)
    choice = jnp.where(jnp.repeat(open_, e // n_group, axis=1), scores,
                       -jnp.inf)
    chosen = jnp.argsort(-choice, axis=-1, stable=True)[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / picked.sum(axis=-1, keepdims=True) * scaling
    return jnp.zeros_like(scores).at[rows, chosen].set(picked)


def is_dense(s, i: int) -> bool:
    """``first_k_dense_replace`` leading dense layers; after them every
    ``moe_layer_freq``-th layer is sparse."""
    return (i < s["first_k_dense_replace"]
            or i % s.get("moe_layer_freq", 1) != 0)


def mlp_layer(s, i, p, x):
    """A frame's tokens at a time (the dense layer's hidden rows of a 16 k
    window are 2.4 GB in float32)."""
    gain, eps = _f32(p["mlp_norm"]), s["rms_norm_eps"]
    if is_dense(s, i):
        w_in, w_out = _f32(p["mlp"]["w_in"]), _f32(p["mlp"]["w_out"])
        return jnp.stack([f + glu(rms_norm(f, gain, eps), w_in, w_out)
                          for f in x])
    moe = p["moe"]
    hs = [rms_norm(f, gain, eps) for f in x]
    gates = [route(h, _f32(moe["router"]), s["num_experts_per_tok"],
                   s.get("n_group", 1), s.get("topk_group", 1),
                   float(s["routed_scaling_factor"])) for h in hs]
    # the shared expert is every token's, unweighted
    w_in, w_out = _f32(moe["shared"]["w_in"]), _f32(moe["shared"]["w_out"])
    ys = [glu(h, w_in, w_out) for h in hs]
    # the share: expert ``first + e`` is row ``e`` of the weights held
    first, count = s.get("experts_held") or (0, s["n_routed_experts"])
    for e in range(count):
        w_in, w_out = _f32(moe["w_in"][e]), _f32(moe["w_out"][e])
        ys = [y + g[:, first + e, None] * glu(h, w_in, w_out)
              for y, g, h in zip(ys, gates, hs)]
    return x + jnp.stack(ys)


def forward(sizes: Dict[str, Any], cfg: Dict[str, Any], weights,
            frames: np.ndarray) -> np.ndarray:
    """Logits ``(n, vocab)`` float32 of the last position of ``frames``
    ``(n, seq)`` int32."""
    del cfg
    s = sizes
    ids = s.get("layers") or range(s["num_hidden_layers"])
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(weights["embed"])[np.asarray(frames)], F32)
        for i, p in zip(ids, weights["layers"]):
            x = attention_layer(s, p, x)
            x = mlp_layer(s, i, p, x)
        last = rms_norm(x[:, -1], _f32(weights["norm"]), s["rms_norm_eps"])
        return np.asarray(last @ _f32(weights["head"]), np.float32)
