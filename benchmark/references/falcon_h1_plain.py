"""Plain reference of the ``falcon_h1`` configurations: the forward pass as
the published ``config.json`` and the family's modelling code describe it,
in ``jax.numpy`` and float32 under ``jax.default_matmul_precision
("highest")``, with no kernel and no chunks.  Imports nothing of the
program.

The state-space layer is the *sequential recurrence* of its definition,
``lax.scan`` over the tokens,

    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ,    y_t = S_t C_t + D x_t,

so that the program's chunked algorithm is checked against the definition
and not against itself.  What the config leaves open is set as the
configuration's ``assumed`` list says, and marked ``ASSUMED`` below where
it enters.

It walks the model layer by layer and keeps the residual stream of all
sampled frames in float32.  The weights arrive on the host in the served
type; one layer's are cast to float32 on the device at a time, attention
and the feed-forward run a frame at a time (attention a key/value head's
group of query heads at a time, with its explicit ``T x T`` mask), the
recurrence over all frames at once, and the head in blocks of rows.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

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


def rotary(theta: float, head_dim: int, t: int):
    """cos and sin ``[t, head_dim / 2]``: plain rotary over the whole head."""
    inv = np.array([theta ** (-2.0 * i / head_dim)
                    for i in range(head_dim // 2)])
    angle = np.arange(t)[:, None] * inv[None, :]
    return jnp.asarray(np.cos(angle), F32), jnp.asarray(np.sin(angle), F32)


def rotate(x, cos, sin):
    """``x`` ``[t, heads, head_dim]``, pair ``i`` = dims ``(i, i + half)``
    (``rotate_half``)."""
    half = cos.shape[-1]
    a, b = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


@jax.jit
def attend_group(h, wq, wk, wv, wo, cos, sin, key_multiplier):
    """One frame ``h`` ``[t, d]`` (already times
    ``attention_in_multiplier``) through one key/value head and its group of
    query heads: ``wq`` ``[d, g, dh]``, ``wk``/``wv`` ``[d, dh]``, ``wo``
    ``[g, dh, d]``.  The key is scaled before it is rotated, as the family's
    code does."""
    t, dh = h.shape[0], wk.shape[-1]
    q = rotate(jnp.einsum("td,dgh->tgh", h, wq), cos, sin)
    k = rotate((h @ wk * key_multiplier)[:, None, :], cos, sin)[:, 0]
    v = h @ wv
    scores = jnp.einsum("tgh,sh->gts", q, k) * dh ** -0.5
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    weights = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("gts,sh,ghd->td", weights, v, wo)


def attention(s, p, h):
    """The attention branch over ``h`` ``[n, t, d]`` (the normed input)."""
    heads, kv, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                     s["head_dim"])
    d, group = s["hidden_size"], heads // kv
    cos, sin = rotary(s["rope_theta"], dh, h.shape[1])
    wq = _f32(p["wq"]).reshape(d, kv, group, dh)
    wk, wv = (_f32(p[n]).reshape(d, kv, dh) for n in ("wk", "wv"))
    wo = _f32(p["wo"]).reshape(kv, group, dh, d)
    out = []
    for frame in h * s["attention_in_multiplier"]:
        out.append(sum(attend_group(frame, wq[:, g], wk[:, g], wv[:, g],
                                    wo[g], cos, sin, s["key_multiplier"])
                       for g in range(kv)))
    return jnp.stack(out) * s["attention_out_multiplier"]


@partial(jax.jit, static_argnames=("groups",))
def recurrence(x, dt, a, b, c, d, groups):
    """The selective state-space recurrence token by token: ``x`` ``[n, t,
    H, P]``, ``dt`` (Δ) ``[n, t, H]``, ``a`` (A) and ``d`` (D) ``[H]``,
    ``b``/``c`` ``[n, t, G, N]``; head ``i`` reads group ``i // (H / G)``.
    Returns ``y`` ``[n, t, H, P]``."""
    n, _, heads, p = x.shape
    per = heads // groups
    b, c = (jnp.repeat(m, per, axis=2) for m in (b, c))

    def step(state, inputs):
        xt, dtt, bt, ct = inputs                 # [n, H, P], [n, H], [n, H, N]
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("nhpk,nhk->nhp", state, ct) + d[:, None] * xt

    init = jnp.zeros((n, heads, p, b.shape[-1]), F32)
    _, y = jax.lax.scan(step, init, tuple(jnp.swapaxes(m, 0, 1)
                                          for m in (x, dt, b, c)))
    return jnp.swapaxes(y, 0, 1)


def mixer(s, p, h):
    """The Mamba-2 branch over ``h`` ``[n, t, d]``."""
    n, t, _ = h.shape
    heads, groups = s["mamba_n_heads"], s["mamba_n_groups"]
    d_ssm = s["mamba_d_ssm"] or s["mamba_expand"] * s["hidden_size"]
    bc = groups * s["mamba_d_state"]
    sizes = [d_ssm, d_ssm, bc, bc, heads]
    mup = jnp.concatenate([jnp.full(k, m, F32)
                           for k, m in zip(sizes, s["ssm_multipliers"])])
    proj = (h * s["ssm_in_multiplier"]) @ _f32(p["w_in"]) * mup
    z, xbc, dt = jnp.split(proj, [d_ssm, 2 * d_ssm + 2 * bc], axis=-1)
    # the causal depthwise conv: tap K - 1 reads the current token
    w = _f32(p["conv_w"])
    taps = w.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + t] * w[i] for i in range(taps))
    if s["mamba_conv_bias"]:
        conv = conv + _f32(p["conv_b"])
    xbc = jax.nn.silu(conv)
    x, b, c = jnp.split(xbc, [d_ssm, d_ssm + bc], axis=-1)
    delta = jax.nn.softplus(dt + _f32(p["dt_bias"]))
    # ASSUMED: time_step_limit (0, inf), the family's default: no clamp
    y = recurrence(x.reshape(n, t, heads, -1), delta, -jnp.exp(_f32(p["A_log"])),
                   b.reshape(n, t, groups, -1), c.reshape(n, t, groups, -1),
                   _f32(p["D"]), groups).reshape(n, t, d_ssm)
    # gated RMSNorm, the gate first (mamba_norm_before_gate: false), over
    # each of the groups of channels
    g = (y * jax.nn.silu(z)).reshape(n, t, groups, -1)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                     + s["rms_norm_eps"])
    g = g.reshape(n, t, d_ssm) * _f32(p["norm"])
    return g @ _f32(p["w_out"]) * s["ssm_out_multiplier"]


@jax.jit
def swiglu(f, w_in, w_out, gate_m, down_m):
    """ASSUMED: ``w_in`` = ``[gate | up]``; the family's code scales the
    gate before the SiLU and the down projection's output."""
    width = w_out.shape[0]
    y = f @ w_in
    return (jax.nn.silu(y[..., :width] * gate_m) * y[..., width:]) @ w_out * down_m


def layer(s, p, x):
    eps = s["rms_norm_eps"]
    h = rms_norm(x, _f32(p["input_norm"]), eps)
    m = mixer(s, p["mamba"], h)
    x = x + (m + attention(s, p["attn"], h))
    gain = _f32(p["ff_norm"])
    w_in, w_out = _f32(p["mlp"]["w_in"]), _f32(p["mlp"]["w_out"])
    gate_m, down_m = s["mlp_multipliers"]
    return jnp.stack([frame + swiglu(rms_norm(frame, gain, eps), w_in, w_out,
                                     gate_m, down_m) for frame in x])


def forward(sizes: Dict[str, Any], cfg: Dict[str, Any], weights,
            frames: np.ndarray) -> np.ndarray:
    """Logits ``(n, vocab)`` float32 of the last position of ``frames``
    ``(n, seq)`` int32."""
    del cfg
    s = sizes
    with jax.default_matmul_precision("highest"):
        x = (jnp.asarray(np.asarray(weights["embed"])[np.asarray(frames)], F32)
             * s["embedding_multiplier"])
        for p in weights["layers"]:
            x = layer(s, p, x)
        last = rms_norm(x[:, -1], _f32(weights["norm"]), s["rms_norm_eps"])
        head = weights["head"]
        logits = jnp.concatenate(
            [last @ _f32(head[:, r:r + HEAD_ROWS])
             for r in range(0, head.shape[1], HEAD_ROWS)], axis=-1)
        return np.asarray(logits * s["lm_head_multiplier"], np.float32)
