"""Streaming transformer encoder — the long-context model family.

The reference's model zoo stops at CNN/LSTM-era nets (survey §2.3/§4
fixtures); a TPU-native streaming framework must also carry long sequences
(aggregated sensor windows, token streams) through attention models.  This
encoder runs its attention in one of three modes, all producing identical
results:

- ``full``    — single-device attention (golden path),
- ``ring``    — sequence-parallel ring attention over a mesh axis
  (:func:`nnstreamer_tpu.parallel.ring_attention.ring_attention`),
- ``ulysses`` — all-to-all head-parallel attention
  (:func:`nnstreamer_tpu.parallel.sequence.ulysses_attention`).

Pre-LN blocks, bfloat16-friendly, pure pytree params (shards under
``NamedSharding`` like the rest of the zoo).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..backends.jax_backend import JaxModel
from ..spec import TensorSpec, TensorsSpec
from .layers import Params, dense_init, ensure_batched


def _proj(p: Params, x, dtype):
    """``x @ w + b`` with the weight leaf deciding the path: an int8
    :class:`~nnstreamer_tpu.ops.quant.QuantizedWeight` (from
    ``quantize_params``) runs the W8A8 MXU matmul with per-token dynamic
    scales (:func:`~nnstreamer_tpu.ops.quant.matmul_int8`); a float leaf
    takes the plain ``dtype`` matmul.  Weight-only dequant is pointless
    for transformer matmuls on TPU (same bf16 compute) — quantized params
    mean W8A8 here."""
    from ..ops.quant import QuantizedWeight, matmul_int8

    w = p["w"]
    if isinstance(w, QuantizedWeight):
        return matmul_int8(x, w, dtype) + p["b"].astype(dtype)
    return x @ w.astype(dtype) + p["b"].astype(dtype)


def _layernorm(p: Params, x, eps: float = 1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    # keep the residual stream in the compute dtype (f32 params would
    # silently promote bf16 activations)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def _ln_init(d) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def init_params(
    key,
    d_model: int = 128,
    n_heads: int = 8,
    n_layers: int = 2,
    d_ff: int = 512,
    d_in: int = 64,
    n_out: int = 16,
    moe_experts: int = 0,
) -> Params:
    """``moe_experts > 0`` replaces every block's dense FFN with a switch
    MoE of that many experts (:mod:`nnstreamer_tpu.parallel.moe`) — the
    expert dim shards over an ``ep`` mesh axis."""
    if d_model % n_heads != 0:
        raise ValueError(f"d_model {d_model} not divisible by n_heads {n_heads}")
    keys = iter(jax.random.split(key, 4 + 6 * n_layers))
    params: Params = {
        "embed": dense_init(next(keys), d_in, d_model),
        "blocks": [],
        "ln_f": _ln_init(d_model),
        "head": dense_init(next(keys), d_model, n_out),
        "n_heads": n_heads,
    }
    for _ in range(n_layers):
        blk = {
            "ln1": _ln_init(d_model),
            "qkv": dense_init(next(keys), d_model, 3 * d_model),
            "proj": dense_init(next(keys), d_model, d_model),
            "ln2": _ln_init(d_model),
        }
        if moe_experts > 0:
            from ..parallel.moe import init_moe_params

            blk["moe"] = init_moe_params(next(keys), d_model, d_ff, moe_experts)
        else:
            blk["ff1"] = dense_init(next(keys), d_model, d_ff)
            blk["ff2"] = dense_init(next(keys), d_ff, d_model)
        params["blocks"].append(blk)
    return params


def _block_apply(
    blk: Params,
    y,
    h: int,
    attn: str,
    mesh,
    axis: str,
    causal: bool,
    dtype,
    moe_mesh=None,
    moe_axis: str = "ep",
):
    """One pre-LN encoder block (attention + FFN/MoE with residuals)."""
    z = _layernorm(blk["ln1"], y)
    o = _attention(_proj(blk["qkv"], z, dtype), h, attn, mesh, axis, causal)
    y = y + _proj(blk["proj"], o, dtype)
    return _ffn_residual(blk, y, dtype, moe_mesh, moe_axis)


def _ffn_residual(blk: Params, y, dtype, moe_mesh=None, moe_axis: str = "ep"):
    """ln2 + (dense-gelu FFN | switch MoE) + residual — shared by the
    full-sequence block and the stepwise decode path so the
    stepwise == full equivalence can't drift."""
    z = _layernorm(blk["ln2"], y)
    if "moe" in blk:
        from ..parallel.moe import moe_ffn

        return y + moe_ffn(blk["moe"], z, mesh=moe_mesh, axis=moe_axis,
                           dtype=dtype)
    z = jax.nn.gelu(_proj(blk["ff1"], z, dtype))
    return y + _proj(blk["ff2"], z, dtype)


def _attention(qkv, h: int, attn: str, mesh, axis: str, causal: bool):
    """Attention over the fused projection ``[B, T, 3*d]`` → ``[B, T, d]``.

    ``full`` is one algorithm with two lowerings, and the program's lowering
    chooses (:func:`nnstreamer_tpu.ops.fused_attention.attention`): the
    Pallas kernel that keeps the scores on chip for a one-device TPU program
    whose shape tiles, ``full_attention`` as XLA lowers it everywhere else."""
    if attn == "full":
        from ..ops.fused_attention import attention

        return attention(qkv, h, causal)
    b, t, d3 = qkv.shape
    q, k, v = (a.reshape(b, t, h, d3 // (3 * h))
               for a in jnp.split(qkv, 3, axis=-1))
    if attn == "ring":
        from ..parallel.ring_attention import ring_attention

        o = ring_attention(q, k, v, mesh, axis=axis, causal=causal)
    elif attn == "ulysses":
        from ..parallel.sequence import ulysses_attention

        o = ulysses_attention(q, k, v, mesh, axis=axis, causal=causal)
    else:
        raise ValueError(f"unknown attention mode {attn!r}")
    return o.reshape(b, t, d3 // 3)


def apply(
    params: Params,
    x,
    attn: str = "full",
    mesh=None,
    axis: str = "sp",
    causal: bool = True,
    dtype=jnp.float32,
    moe_mesh=None,
    moe_axis: str = "ep",
):
    """(B, T, d_in) or (T, d_in) features → (B, T, n_out) / (T, n_out)."""
    x, squeezed = ensure_batched(x, 3)
    h = params["n_heads"]
    y = _proj(params["embed"], x.astype(dtype), dtype)
    pe = params.get("pos_embed")
    if pe is not None:  # learned positional embeddings (ViT-style callers)
        y = y + pe.astype(dtype)
    for blk in params["blocks"]:
        y = _block_apply(
            blk, y, h, attn, mesh, axis, causal, dtype,
            moe_mesh=moe_mesh, moe_axis=moe_axis,
        )
    y = _layernorm(params["ln_f"], y)
    out = _proj(params["head"], y, dtype).astype(jnp.float32)
    return out[0] if squeezed else out


def build(
    seq_len: int = 256,
    d_in: int = 64,
    n_out: int = 16,
    d_model: int = 128,
    n_heads: int = 8,
    n_layers: int = 2,
    attn: str = "full",
    mesh=None,
    axis: str = "sp",
    causal: bool = True,
    batch: Optional[int] = None,
    dtype=jnp.float32,
    seed: int = 0,
    params: Optional[Params] = None,
    moe_experts: int = 0,
    moe_mesh=None,
    moe_axis: str = "ep",
) -> JaxModel:
    """Stream-ready encoder: one frame = one (T, d_in) feature window (the
    tensor_aggregator output shape)."""
    if params is None:
        params = init_params(
            jax.random.PRNGKey(seed), d_model, n_heads, n_layers,
            4 * d_model, d_in, n_out, moe_experts=moe_experts,
        )
    shape: Tuple[Optional[int], ...] = (seq_len, d_in)
    if batch is not None:
        shape = (batch,) + shape
    return JaxModel(
        apply=lambda p, x: apply(
            p, x, attn=attn, mesh=mesh, axis=axis, causal=causal, dtype=dtype,
            moe_mesh=moe_mesh, moe_axis=moe_axis,
        ),
        params=params,
        input_spec=TensorsSpec.of(TensorSpec(dtype=np.float32, shape=shape)),
        name=f"transformer_{attn}_{d_model}x{n_layers}",
    )


def build_quantized(**kwargs) -> JaxModel:
    """W8A8 encoder: every matmul (embed, qkv, proj, ffn, head) runs
    int8 x int8 → int32 on the MXU with per-token dynamic activation
    scales (:func:`~nnstreamer_tpu.ops.quant.matmul_int8`) — the LLM-era
    serving quantization, same tier as
    ``mobilenet_v2.build_quantized(int8_convs=True)``.  Attention itself
    stays in the compute dtype.  Takes :func:`build`'s kwargs; the decode
    cell inherits the quantized leaves automatically (``_proj`` dispatches
    on the leaf type), so stepwise==full equivalence holds under int8
    too."""
    from ..ops.quant import quantize_model

    if kwargs.get("moe_experts", 0):
        raise NotImplementedError(
            "build_quantized does not cover MoE blocks: the expert weights "
            "(w1/w2, expert-stacked 3-D) need expert-level scale handling "
            "and only the gate would quantize — use the dense-FFN encoder "
            "for W8A8"
        )
    return quantize_model(build(**kwargs))


def decode_step(params: Params, x_t, cache, pos, dtype=jnp.float32,
                window: bool = False):
    """One autoregressive step with a KV cache.

    The reference's streaming recurrence is the LSTM cell cycled through
    repo slots (``tests/nnstreamer_repo_lstm``); this is the transformer-era
    analog: per-step state is the layers' K/V cache, carried through the
    same repo-slot machinery (or any stream state channel).

    - ``x_t``: (d_in,) — one step's features;
    - ``cache``: (L, 2, T_max, d_model) — per-layer K and V, concatenated
      head-merged (static shape; position ``pos`` indexes the write slot);
    - ``pos``: (1,) int32 — current step index (< T_max unless ``window``).

    Returns ``(y_t (n_out,), cache', pos+1)``.  Equivalent to running the
    full causal :func:`apply` over the whole prefix and taking the last
    token's output — pinned by tests.

    Two capacity disciplines:

    - ``window=False`` (default): past ``T_max`` the output saturates to
      NaN (loudly wrong beats silently-stale attention; size the cache for
      the stream or reset the slots).
    - ``window=True``: the cache is a **ring** — token ``a`` writes slot
      ``a % T_max`` and attention sees exactly the last ``T_max`` tokens
      (sliding-window attention).  The stream can run forever at constant
      memory — the TPU-native infinite-decode discipline.  Requires
      ``pos_embed``-free params (the default encoder): absolute learned
      positions cannot wrap.

    MoE blocks are rejected: switch capacity is a sequence-level quantity,
    so a per-token step cannot reproduce the full pass's drop semantics.
    """
    if any("moe" in blk for blk in params["blocks"]):
        raise NotImplementedError(
            "decode_step does not support MoE blocks (capacity semantics "
            "are sequence-level); use the dense-FFN encoder for decode"
        )
    pe = params.get("pos_embed")
    if window and pe is not None:
        raise ValueError(
            "window=True needs pos_embed-free params: absolute learned "
            "positions cannot wrap a ring cache"
        )
    h = params["n_heads"]
    t_max = cache.shape[2]
    p_idx = pos[0]
    slot = p_idx % t_max if window else p_idx
    y = _proj(params["embed"], x_t[None].astype(dtype), dtype)  # (1, d)
    if pe is not None:
        y = y + jax.lax.dynamic_slice_in_dim(pe, p_idx, 1, 0).astype(dtype)
    d = y.shape[-1]
    idx = jnp.arange(t_max)
    if window:
        # slot s holds absolute token (p_idx - (p_idx - s) mod T_max):
        # live iff that token exists (dist <= p_idx); dist < T_max always,
        # so after warm-up every slot is live — a full sliding window
        live = (p_idx - idx) % t_max <= p_idx
    else:
        live = idx <= p_idx
    new_cache = []
    for li, blk in enumerate(params["blocks"]):
        z = _layernorm(blk["ln1"], y[None])[0]
        qkv = _proj(blk["qkv"], z, dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)  # (1, d) each
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache[li, 0].astype(dtype), k, slot, 0
        )
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache[li, 1].astype(dtype), v, slot, 0
        )
        new_cache.append(jnp.stack([ck, cv]))
        # causal attention of the single query against the cached prefix
        # (ring mode: attention is permutation-invariant over the cached
        # set, so slot order does not matter once the mask is right)
        qh = q.reshape(1, h, d // h)
        kh = ck.reshape(t_max, h, d // h)
        vh = cv.reshape(t_max, h, d // h)
        s = jnp.einsum("qhd,khd->hqk", qh, kh) * (d // h) ** -0.5
        s = jnp.where(live[None, None, :], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", w, vh).reshape(1, d)
        y = y + _proj(blk["proj"], o, dtype)
        y = _ffn_residual(blk, y[None], dtype)[0]
    y = _layernorm(params["ln_f"], y[None])[0]
    out = _proj(params["head"], y, dtype).astype(jnp.float32)
    if not window:
        # overflow: a step past the cache capacity would clamp the write
        # slot and attend over stale state — saturate to NaN so the
        # caller notices
        out = jnp.where(p_idx < t_max, out, jnp.nan)
        return out[0], jnp.stack(new_cache).astype(cache.dtype), pos + 1
    # ring mode runs FOREVER: keep pos bounded in [0, 2*T_max) so the
    # int32 counter can never overflow at step 2**31 (the wrap preserves
    # slot ≡ pos mod T_max and the mask is all-live past warm-up anyway)
    nxt = pos + 1
    nxt = jnp.where(nxt >= 2 * t_max, nxt - t_max, nxt)
    return out[0], jnp.stack(new_cache).astype(cache.dtype), nxt


def init_decode_cache(n_layers: int, d_model: int, t_max: int,
                      dtype=jnp.float32):
    """Zeroed KV cache for :func:`decode_step`."""
    return jnp.zeros((n_layers, 2, t_max, d_model), dtype)


def prefill(params: Params, xs, t_max: int, n_valid=None,
            dtype=jnp.float32):
    """Process a whole ``(T, d_in)`` prompt in ONE causal pass and return
    ``(y_last, cache, pos)`` — continuation state for :func:`decode_step`.

    The serving-engine prefill/decode split (Orca/vLLM discipline): a
    T-token prompt costs one compiled program instead of T per-token
    ticks, and the matmuls run at sequence arithmetic intensity instead
    of batch-1.  Numerically equivalent to stepping :func:`decode_step`
    over the prompt — pinned by tests.

    ``n_valid`` (int32 scalar, default T) supports LENGTH BUCKETING: pad
    the prompt to a bucketed T, pass the real length, and compile once
    per bucket instead of once per length.  Rows past ``n_valid`` are
    masked out of the attention AND zeroed in the returned cache, and
    ``y_last``/``pos`` come from the real length, so padding is
    invisible to the continuation.

    Same restrictions as :func:`decode_step`: no MoE blocks; T must be
    ≤ ``t_max`` (the ring-window case is covered because positions
    0..T-1 map to slots 0..T-1 while T ≤ t_max).
    """
    if any("moe" in blk for blk in params["blocks"]):
        raise NotImplementedError(
            "prefill does not support MoE blocks (capacity semantics are "
            "sequence-level relative to the FULL batch); use the dense-FFN "
            "encoder for decode"
        )
    t = xs.shape[0]
    if t > t_max:
        raise ValueError(f"prompt length {t} exceeds cache t_max {t_max}")
    if n_valid is None:
        n_valid = t
    n_valid = jnp.asarray(n_valid, jnp.int32)
    h = params["n_heads"]
    y = _proj(params["embed"], xs.astype(dtype), dtype)  # (T, d)
    pe = params.get("pos_embed")
    if pe is not None:
        y = y + pe[:t].astype(dtype)
    d = y.shape[-1]
    tok = jnp.arange(t)
    valid = tok < n_valid                                 # (T,)
    new_cache = []
    for blk in params["blocks"]:
        z = _layernorm(blk["ln1"], y[None])[0]
        qkv = _proj(blk["qkv"], z, dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)              # (T, d) each
        # padded rows must be invisible to the continuation: zero them in
        # the cache (decode_step's live mask only excludes idx > pos, and
        # pos == n_valid overwrites exactly one of them)
        kz = jnp.where(valid[:, None], k, 0.0)
        vz = jnp.where(valid[:, None], v, 0.0)
        ck = jnp.zeros((t_max, d), dtype).at[:t].set(kz)
        cv = jnp.zeros((t_max, d), dtype).at[:t].set(vz)
        new_cache.append(jnp.stack([ck, cv]))
        qh = q.reshape(t, h, d // h)
        kh = k.reshape(t, h, d // h)
        vh = v.reshape(t, h, d // h)
        s = jnp.einsum("qhd,khd->hqk", qh, kh) * (d // h) ** -0.5
        causal = tok[None, :, None] >= tok[None, None, :]  # q >= k
        mask = causal & valid[None, None, :]
        s = jnp.where(mask, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", w, vh).reshape(t, d)
        y = y + _proj(blk["proj"], o, dtype)
        y = _ffn_residual(blk, y[None], dtype)[0]
    y = _layernorm(params["ln_f"], y[None])[0]
    out = _proj(params["head"], y, dtype).astype(jnp.float32)  # (T, n_out)
    y_last = jnp.take(out, n_valid - 1, axis=0)
    cache = jnp.stack(new_cache)
    return y_last, cache, n_valid.reshape(1)


def build_decode_cell(
    t_max: int = 128,
    d_in: int = 64,
    n_out: int = 16,
    d_model: int = 128,
    n_heads: int = 8,
    n_layers: int = 2,
    dtype=jnp.float32,
    seed: int = 0,
    params: Optional[Params] = None,
    window: bool = False,
) -> JaxModel:
    """Stream-ready decode cell: inputs ``(x_t, cache, pos)`` → outputs
    ``(y_t, cache', pos')`` — cycle cache/pos through repo slots exactly
    like the LSTM cell's (h, c).  ``window=True``: ring cache / sliding
    -window attention — the stream runs forever at constant memory
    (see :func:`decode_step`)."""
    if params is None:
        params = init_params(
            jax.random.PRNGKey(seed), d_model, n_heads, n_layers,
            4 * d_model, d_in, n_out,
        )
    return JaxModel(
        apply=lambda p, x_t, cache, pos: decode_step(
            p, x_t, cache, pos, dtype=dtype, window=window
        ),
        params=params,
        input_spec=TensorsSpec(tensors=(
            TensorSpec(dtype=np.float32, shape=(d_in,)),
            TensorSpec(dtype=np.float32,
                       shape=(n_layers, 2, t_max, d_model)),
            TensorSpec(dtype=np.int32, shape=(1,)),
        )),
        name=f"transformer_decode_{d_model}x{n_layers}"
             + ("_win" if window else ""),
    )


def build_pipelined(
    mesh,
    axis: str = "pp",
    seq_len: int = 64,
    d_in: int = 64,
    n_out: int = 16,
    d_model: int = 128,
    n_heads: int = 8,
    n_layers: int = 4,
    batch: int = 8,
    microbatches: Optional[int] = None,
    causal: bool = True,
    dtype=jnp.float32,
    seed: int = 0,
) -> JaxModel:
    """Encoder with its block stack **pipelined over the ``pp`` mesh axis**
    (GPipe microbatch rotation, :mod:`nnstreamer_tpu.parallel.pipeline_par`).

    ``n_layers`` must divide evenly into ``mesh.shape[axis]`` stages;
    embed/head run replicated around the pipelined trunk.  Numerics match
    the sequential :func:`apply` exactly — pinned by tests."""
    from ..parallel.pipeline_par import gpipe_apply, stack_stage_params

    s = mesh.shape[axis]
    if n_layers % s:
        raise ValueError(f"n_layers {n_layers} not divisible by {s} stages")
    per_stage = n_layers // s
    params = init_params(
        jax.random.PRNGKey(seed), d_model, n_heads, n_layers,
        4 * d_model, d_in, n_out,
    )
    h = n_heads

    # blocks → (stage, layer_within_stage) stacked pytree
    blocks = params["blocks"]
    stages = [
        jax.tree.map(lambda *ls: jnp.stack(ls), *blocks[i * per_stage:(i + 1) * per_stage])
        for i in range(s)
    ]
    stage_stacked = stack_stage_params(stages)
    outer = {k: v for k, v in params.items() if k != "blocks"}

    def stage_fn(stage_params, x):
        def body(y, blk):
            return _block_apply(blk, y, h, "full", None, "sp", causal, dtype), None

        y, _ = jax.lax.scan(body, x, stage_params)
        return y

    def pipelined_apply(p, x):
        outer_p, stacked = p
        y = _proj(outer_p["embed"], x.astype(dtype), dtype)
        y = gpipe_apply(
            stage_fn, stacked, y, mesh, axis=axis, microbatches=microbatches
        )
        y = _layernorm(outer_p["ln_f"], y)
        return _proj(outer_p["head"], y, dtype).astype(jnp.float32)

    return JaxModel(
        apply=pipelined_apply,
        params=(outer, stage_stacked),
        input_spec=TensorsSpec.of(
            TensorSpec(dtype=np.float32, shape=(batch, seq_len, d_in))
        ),
        name=f"transformer_pp{s}_{d_model}x{n_layers}",
    )
