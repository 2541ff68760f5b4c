"""Plain reference of the ``nemotron_h`` configurations: the forward pass as
the published ``config.json`` and the family's modelling code describe it,
in ``jax.numpy`` and float32 under ``jax.default_matmul_precision
("highest")``, with no kernel, no chunks and no sort.  Imports nothing of
the program.

Every layer is ``x ← x + mixer(RMSNorm(x))``, the mixer chosen by the
layer's character of ``hybrid_override_pattern`` (``M`` Mamba-2, ``*``
attention, ``E`` the latent mixture of experts).  The state-space layer is
the *sequential recurrence* of its definition, ``lax.scan`` over the tokens,

    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ,    y_t = S_t C_t + D x_t,

so that the program's chunked scan is checked against the definition.  The
routed experts held here are a plain loop, each over every token with the
token's weight for it (zero where the token did not choose it): a dense
mask, not a sort.  What the config leaves open is set as the
configuration's ``assumed`` list says, and marked ``ASSUMED`` below where it
enters.  Departures from the published model, both the program's and this
file's: the experts held elsewhere add nothing (a chip's share), the
vocabulary is the rows held, the MTP layer is not run.

It walks the model layer by layer and keeps the residual stream of all
sampled frames in float32.  The weights arrive on the host in the served
type; one layer's are cast to float32 on the device at a time (a routed
expert's at a time), attention runs a frame and a key/value head's group
at a time with its explicit ``T x T`` mask, the recurrence over all frames
at once, and the head in blocks of rows.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HEAD_ROWS = 32768      # rows of the head cast to float32 at a time
F32 = jnp.float32


def _f32(a):
    """On the device in the type it was served in, float32 from there."""
    return jnp.asarray(a).astype(F32)


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def kinds(s: Dict[str, Any]) -> List[str]:
    """The pattern's character of each layer built: the pattern holds one a
    published layer (picked by ``layers``) or one a layer built."""
    pattern = s["hybrid_override_pattern"]
    ids = s.get("layers") or range(s["num_hidden_layers"])
    return list(pattern) if len(pattern) == len(ids) else [pattern[i]
                                                           for i in ids]


@jax.jit
def attend_group(h, wq, wk, wv, wo):
    """One frame ``h`` ``[t, d]`` through one key/value head and its group
    of query heads: ``wq`` ``[d, g, dh]``, ``wk``/``wv`` ``[d, dh]``, ``wo``
    ``[g, dh, d]``.  ASSUMED: no rotary (the family's attention applies
    none, whatever ``rope_theta`` says), scale ``dh^-1/2``."""
    t, dh = h.shape[0], wk.shape[-1]
    q = jnp.einsum("td,dgh->tgh", h, wq)
    k, v = h @ wk, h @ wv
    scores = jnp.einsum("tgh,sh->gts", q, k) * dh ** -0.5
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    weights = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("gts,sh,ghd->td", weights, v, wo)


def attention(s, p, h):
    """The attention layer over ``h`` ``[n, t, d]`` (the normed input)."""
    heads, kv, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                     s["head_dim"])
    d, group = s["hidden_size"], heads // kv
    wq = _f32(p["wq"]).reshape(d, kv, group, dh)
    wk, wv = (_f32(p[n]).reshape(d, kv, dh) for n in ("wk", "wv"))
    wo = _f32(p["wo"]).reshape(kv, group, dh, d)
    return jnp.stack([sum(attend_group(frame, wq[:, g], wk[:, g], wv[:, g],
                                       wo[g]) for g in range(kv))
                      for frame in h])


@partial(jax.jit, static_argnames=("groups",))
def recurrence(x, dt, a, b, c, d, groups):
    """The selective state-space recurrence token by token: ``x`` ``[n, t,
    H, P]``, ``dt`` (Δ) ``[n, t, H]``, ``a`` (A) and ``d`` (D) ``[H]``,
    ``b``/``c`` ``[n, t, G, N]``; head ``i`` reads group ``i // (H / G)``.
    Returns ``y`` ``[n, t, H, P]``."""
    n, _, heads, p = x.shape
    per = heads // groups

    def step(state, inputs):
        xt, dtt, bt, ct = inputs                 # [n, H, P], [n, H], [n, G, N]
        # each head's group, a token at a time: no [n, t, H, N] copy of B, C
        bt, ct = (jnp.repeat(m, per, axis=1) for m in (bt, ct))
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("nhpk,nhk->nhp", state, ct) + d[:, None] * xt

    init = jnp.zeros((n, heads, p, b.shape[-1]), F32)
    _, y = jax.lax.scan(step, init, tuple(jnp.swapaxes(m, 0, 1)
                                          for m in (x, dt, b, c)))
    return jnp.swapaxes(y, 0, 1)


def mamba(s, p, h):
    """The Mamba-2 mixer over ``h`` ``[n, t, d]``."""
    n, t, _ = h.shape
    heads, groups = s["mamba_num_heads"], s["n_groups"]
    d_ssm = heads * s["mamba_head_dim"]
    bc = groups * s["ssm_state_size"]
    proj = h @ _f32(p["w_in"])
    z, xbc, dt = jnp.split(proj, [d_ssm, 2 * d_ssm + 2 * bc], axis=-1)
    # the causal depthwise conv: tap K - 1 reads the current token
    w = _f32(p["conv_w"])
    taps = w.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + t] * w[i] for i in range(taps))
    if s["use_conv_bias"]:
        conv = conv + _f32(p["conv_b"])
    xbc = jax.nn.silu(conv)
    x, b, c = jnp.split(xbc, [d_ssm, d_ssm + bc], axis=-1)
    # ASSUMED: time_step_limit (0, inf), the family's default: no clamp
    delta = jax.nn.softplus(dt + _f32(p["dt_bias"]))
    y = recurrence(x.reshape(n, t, heads, -1), delta,
                   -jnp.exp(_f32(p["A_log"])), b.reshape(n, t, groups, -1),
                   c.reshape(n, t, groups, -1), _f32(p["D"]),
                   groups).reshape(n, t, d_ssm)
    # ASSUMED: the gated RMSNorm with the gate first, over groups of
    # d_ssm / n_groups channels, at layer_norm_epsilon
    g = (y * jax.nn.silu(z)).reshape(n, t, groups, -1)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                     + s["layer_norm_epsilon"])
    return g.reshape(n, t, d_ssm) * _f32(p["norm"]) @ _f32(p["w_out"])


@jax.jit
def relu2_mlp(x, w_up, w_down):
    """ASSUMED: ``relu2`` = ``relu(x)²``, non-gated."""
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


@jax.jit
def _route(h, router, bias):
    return jax.nn.sigmoid(h @ router), bias


def experts(s, p, h):
    """The latent mixture of experts over ``h`` ``[n, t, d]``: routed on
    ``h``, the held experts over the latent ``z = h W_lat_in``, their
    weighted sum in the latent width, then ``W_lat_out``, beside the shared
    expert on ``h``."""
    shape = h.shape
    h = h.reshape(-1, shape[-1])
    top_k, scaling = s["num_experts_per_tok"], s["routed_scaling_factor"]
    first, held = s.get("experts_held") or (0, s["n_routed_experts"])
    scores, bias = _route(h, _f32(p["router"]), _f32(p["bias"]))
    # the choice by score + bias, the weights the chosen unbiased scores,
    # renormalised (norm_topk_prob) and times routed_scaling_factor
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = top / top.sum(axis=-1, keepdims=True) * scaling
    # ASSUMED: the latent projections have no norm and no bias
    z = h @ _f32(p["latent_in"])
    combined = jnp.zeros_like(z)
    for j in range(held):
        mine = jnp.sum(jnp.where(chosen == first + j, weights, 0.0), axis=-1)
        combined = combined + mine[:, None] * relu2_mlp(
            z, _f32(p["w_in"][j]), _f32(p["w_out"][j]))
    # ASSUMED: the routed sum is combined in the latent width, then brought
    # back; the shared expert reads h and is added unweighted
    out = combined @ _f32(p["latent_out"]) + relu2_mlp(
        h, _f32(p["shared"]["w_in"]), _f32(p["shared"]["w_out"]))
    return out.reshape(shape)


MIXERS = {"M": ("mixer", mamba), "*": ("attn", attention),
          "E": ("moe", experts)}


def forward(sizes: Dict[str, Any], cfg: Dict[str, Any], weights,
            frames: np.ndarray) -> np.ndarray:
    """Logits ``(n, vocab)`` float32 of the last position of ``frames``
    ``(n, seq)`` int32."""
    del cfg
    s = sizes
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(weights["embed"])[np.asarray(frames)], F32)
        for kind, p in zip(kinds(s), weights["layers"]):
            key, mix = MIXERS[kind]
            h = rms_norm(x, _f32(p["norm"]), s["layer_norm_epsilon"])
            x = x + mix(s, p[key], h)
        last = rms_norm(x[:, -1], _f32(weights["norm"]), s["norm_eps"])
        head = weights["head"]
        logits = jnp.concatenate(
            [last @ _f32(head[:, r:r + HEAD_ROWS])
             for r in range(0, head.shape[1], HEAD_ROWS)], axis=-1)
        return np.asarray(logits, np.float32)
