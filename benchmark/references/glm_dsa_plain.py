"""Plain reference of the ``glm_dsa`` configurations: the forward pass as the
published ``config.json`` of the ``glm_moe_dsa`` family describes it, in
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``,
with no kernel, no grouped product, no sorting of pairs and no scan.
Imports nothing of the program.

What the config leaves open is set as the configuration's ``assumed`` list
says, and marked ``ASSUMED`` below where it enters.

It walks the model layer by layer and keeps the residual stream of all
sampled frames in float32.  The weights arrive on the host in the served
type and in a checkpoint's layout (rotary dims in interleaved pairs); one
layer's (one expert's) are cast to float32 on the device at a time.
Attention is the per-head form with dense ``T x T`` scores and the
selection laid over them as a mask, a block of query rows at a time so that
a 16 k window fits the chip; the indexer scores every pair and a stable
sort picks each query's keys; every held expert runs over every token, its
result kept where the router chose it.  The share is the configuration's:
``experts_held`` = ``[first, count]`` of the router's ``n_routed_experts``
(what the experts held elsewhere would add is left out, here as in the
program), and the vocabulary is the slice the weights hold.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 256             # query rows scored at a time


def _f32(a):
    """On the device in the type it was served in, float32 from there."""
    return jnp.asarray(a).astype(F32)


def rotary(s: Dict[str, Any], t: int):
    """cos and sin ``[t, rot/2]``: the default type, theta^(-2i/rot)."""
    rot = s["qk_rope_head_dim"]
    theta = s["rope_parameters"]["rope_theta"]
    inv = np.array([theta ** (-2.0 * i / rot) for i in range(rot // 2)])
    angle = np.arange(t)[:, None] * inv[None, :]
    return jnp.asarray(np.cos(angle), F32), jnp.asarray(np.sin(angle), F32)


def rotate(x, cos, sin):
    """``x`` ``[t, heads, rot]`` rotated in interleaved pairs: pair ``i`` is
    dims ``(2i, 2i + 1)`` (``rope_interleave: true``)."""
    a, b = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def layer_norm(x, scale, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale + bias


@jax.jit
def index_scores(q_i, k_i, w):
    """ASSUMED (the published kernel's Hadamard rotation of q_I and k_I is
    orthogonal and leaves the scores as they are; its FP8 is left out):
    ``I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s])`` for a block of
    queries ``q_i`` ``[r, heads, width]`` against every key ``[t, width]``."""
    return jnp.einsum("rhs,rh->rs",
                      jax.nn.relu(jnp.einsum("rhd,sd->rhs", q_i, k_i)), w)


@partial(jax.jit, static_argnames=("top_k",))
def pick(scores, row0, top_k):
    """Of each row's causal scores the ``top_k`` highest (the earlier key
    first among equals; every causal key where there are no more than
    ``top_k``), as a ``[r, t]`` mask."""
    r, t = scores.shape
    rows = row0 + jnp.arange(r)[:, None]
    causal = jnp.arange(t)[None, :] <= rows
    # -0.0 counts as 0.0: equal scores are told apart by their index alone
    scores = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    chosen = jnp.argsort(-scores, axis=-1, stable=True)[:, :top_k]
    picked = jnp.zeros((r, t), bool).at[jnp.arange(r)[:, None], chosen].set(
        True)
    return picked & causal


def selection(s, p, h, c_q, cos, sin):
    """A ``full`` layer's indexer over one frame: ``[t, t]`` bool.
    ASSUMED: the rotary dims are the first ``qk_rope_head_dim`` of each
    indexer head; ``k_I`` goes through a LayerNorm with scale and bias at
    ``rms_norm_eps``; the heads' weights are ``h W_Iw`` times ``heads^-1/2
    width^-1/2``."""
    t = h.shape[0]
    heads, width, rot = s["index_n_heads"], s["index_head_dim"], \
        s["qk_rope_head_dim"]
    q_i = (c_q @ _f32(p["wq"])).reshape(t, heads, width)
    k_i = layer_norm(h @ _f32(p["wk"]), _f32(p["k_norm"]["scale"]),
                     _f32(p["k_norm"]["bias"]), s["rms_norm_eps"])[:, None]
    q_i, k_i = (jnp.concatenate([rotate(a[..., :rot], cos, sin),
                                 a[..., rot:]], axis=-1) for a in (q_i, k_i))
    w = (h @ _f32(p["w_heads"])) * (heads * width) ** -0.5
    rows = min(ROWS, t)
    return jnp.concatenate([
        pick(index_scores(q_i[r:r + rows], k_i[:, 0], w[r:r + rows]), r,
             min(s["index_topk"], t))
        for r in range(0, t, rows)])


@jax.jit
def attend_rows(q, k, v, seen):
    """A block of query rows ``q`` ``[r, heads, d]`` against every key ``k``
    ``[t, heads, d]``: dense scores, the softmax over the keys ``seen``
    ``[r, t]`` lets through, times ``v`` ``[t, heads, dv]``."""
    scores = jnp.einsum("rhd,shd->hrs", q, k) * q.shape[-1] ** -0.5
    weights = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hrs,shd->rhd", weights, v)


def attention_layer(s, i, p, x, selections, keep):
    """Latent attention of published layer ``i`` over frames ``x`` ``[n, t,
    d]``; ``selections[f]`` is frame ``f``'s current selection, replaced
    where the layer is a ``full`` one."""
    t = x.shape[1]
    heads, rank = s["num_attention_heads"], s["kv_lora_rank"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    eps = s["rms_norm_eps"]
    cos, sin = rotary(s, t)
    w = {n: _f32(p[n]) for n in ("attn_norm", "w_dq", "q_norm", "w_uq",
                                 "w_dkv", "kv_norm", "w_uk", "w_uv", "wo")}
    rows = min(ROWS, t)
    out = []
    for f, frame in enumerate(x):
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
        if s["indexer_types"][i] == "full":
            selections[f] = selection(s, p["indexer"], h, c_q, cos, sin)
            if keep is not None:
                keep.append((i, f, np.asarray(selections[f])))
        o = jnp.concatenate([attend_rows(q[r:r + rows], k, v,
                                         selections[f][r:r + rows])
                             for r in range(0, t, rows)])
        out.append(frame + o.reshape(t, heads * dv) @ w["wo"])
    return jnp.stack(out)


@jax.jit
def glu(x, w_in, w_out):
    """SwiGLU, ``w_in`` = ``[gate | up]`` (``hidden_act: silu``)."""
    width = w_out.shape[-2]
    y = x @ w_in
    return (jax.nn.silu(y[..., :width]) * y[..., width:]) @ w_out


@partial(jax.jit, static_argnames=("top_k",))
def route(h, router, bias, top_k, scaling):
    """``scoring_func: sigmoid``, ``topk_method: noaux_tc`` with ``n_group``
    = ``topk_group`` = 1: the ``top_k`` highest of ``score + bias`` (the
    lower index first among equals), their unbiased scores renormalised to
    sum 1 (``norm_topk_prob``) and times ``routed_scaling_factor``.
    Returns ``[tokens, experts]`` weights, 0 where an expert was not
    chosen."""
    scores = jax.nn.sigmoid(h @ router)
    chosen = jnp.argsort(-(scores + bias), axis=-1, stable=True)[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / picked.sum(axis=-1, keepdims=True) * scaling
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(picked)


def mlp_layer(s, i, p, x):
    """A frame's tokens at a time (the dense layer's hidden rows of a 16 k
    window are 1.6 GB in float32)."""
    gain, eps = _f32(p["mlp_norm"]), s["rms_norm_eps"]
    if s["mlp_layer_types"][i] == "dense":
        w_in, w_out = _f32(p["mlp"]["w_in"]), _f32(p["mlp"]["w_out"])
        return jnp.stack([f + glu(rms_norm(f, gain, eps), w_in, w_out)
                          for f in x])
    moe = p["moe"]
    hs = [rms_norm(f, gain, eps) for f in x]
    gates = [route(h, _f32(moe["router"]), _f32(moe["bias"]),
                   s["num_experts_per_tok"],
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
            frames: np.ndarray, keep: Optional[List] = None) -> np.ndarray:
    """Logits ``(n, vocab)`` float32 of the last position of ``frames``
    ``(n, seq)`` int32.  ``keep``, a list, is given ``(layer, frame, [t, t]
    bool)`` for every selection made (the tests')."""
    del cfg
    s = sizes
    ids = s.get("layers") or range(s["num_hidden_layers"])
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(weights["embed"])[np.asarray(frames)], F32)
        selections = [None] * len(frames)
        for i, p in zip(ids, weights["layers"]):
            x = attention_layer(s, i, p, x, selections, keep)
            x = mlp_layer(s, i, p, x)
        last = rms_norm(x[:, -1], _f32(weights["norm"]), s["rms_norm_eps"])
        return np.asarray(last @ _f32(weights["head"]), np.float32)
