"""Plain reference of the ``vit`` configurations: straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision, no kernels, no
batching tricks, layer by layer so that it fits.

It imports nothing of the program and takes nothing the program made: the
weights are the harness's own (``model_kinds/vit.init_weights``), the frames
the harness's own (``traffic_kinds``).  The equations are the repo's ViT as
the configuration files state it under ``assumed``: patch embedding plus
learned positions, pre-LN blocks (LayerNorm eps 1e-5, softmax attention with
1/sqrt(head) scaling, tanh-approximated GELU as ``jax.nn.gelu`` defaults),
final LayerNorm, a linear head per token, the mean over tokens.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _layernorm(p, x, eps=1e-5):
    import jax

    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


def _dense(p, x):
    return x @ _f32(p["w"]) + _f32(p["b"])


def _embed(params, frames_u8, patch, add, div):
    import jax.numpy as jnp

    x = (frames_u8.astype(jnp.float32) + add) / div
    n, h, w, c = x.shape
    gh, gw = h // patch, w // patch
    x = x.reshape(n, gh, patch, gw, patch, c).transpose(0, 1, 3, 2, 4, 5)
    toks = x.reshape(n, gh * gw, patch * patch * c)
    return _dense(params["embed"], toks) + _f32(params["pos_embed"])


def _block(blk, y, heads):
    import jax
    import jax.numpy as jnp

    n, t, d = y.shape
    qkv = _dense(blk["qkv"], _layernorm(blk["ln1"], y))
    q, k, v = (a.reshape(n, t, heads, d // heads)
               for a in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) * (d // heads) ** -0.5
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v)
    y = y + _dense(blk["proj"], o.reshape(n, t, d))
    z = jax.nn.gelu(_dense(blk["ff1"], _layernorm(blk["ln2"], y)))
    return y + _dense(blk["ff2"], z)


def _head(params, y):
    return _dense(params["head"], _layernorm(params["ln_f"], y)).mean(axis=-2)


def forward(sizes: Dict[str, Any], cfg: Dict[str, Any], weights,
            frames_u8: np.ndarray, chunk: int = 4) -> np.ndarray:
    """Logits ``(n, classes)`` float32 of ``frames_u8`` ``(n, H, W, 3)``,
    normalised as ``cfg["normalize"]`` says.  ``weights`` are the harness's
    host arrays: each block goes to the device when its turn comes and every
    chunk of frames passes through it there."""
    import jax
    import jax.numpy as jnp

    heads = int(weights["n_heads"])
    normalize = cfg["normalize"]
    embed = jax.jit(lambda p, x: _embed(p, x, sizes["patch"],
                                        normalize["add"], normalize["div"]))
    block = jax.jit(lambda blk, y: _block(blk, y, heads))
    head = jax.jit(_head)
    outer = jax.device_put({k: v for k, v in weights.items()
                            if k not in ("blocks", "n_heads")})
    with jax.default_matmul_precision("highest"):
        ys = [embed(outer, jnp.asarray(frames_u8[i:i + chunk]))
              for i in range(0, len(frames_u8), chunk)]
        for blk in weights["blocks"]:
            blk = jax.device_put(blk)
            ys = [block(blk, y) for y in ys]
        out = [np.asarray(head(outer, y), np.float32) for y in ys]
    return np.concatenate(out, axis=0)
