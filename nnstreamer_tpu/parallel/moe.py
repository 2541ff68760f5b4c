"""Mixture-of-experts FFN with expert parallelism (the ``ep`` mesh axis).

The reference's distributed story stops at process-level stream branching;
a TPU-native framework must also scale *within* a model.  This is the
canonical GSPMD switch-routing MoE (top-1 gating, capacity-bounded einsum
dispatch — the Mesh-TensorFlow/Switch-Transformer formulation, kept fully
static for XLA):

- ``gate``: tokens → expert logits (replicated weights);
- dispatch: one-hot ``(tokens, experts, capacity)`` mask built from a
  cumsum position-in-expert — no dynamic shapes, dropped tokens fall out
  of the mask (standard capacity-factor semantics);
- expert FFN: ``(experts, capacity, d)`` batch, with the **expert dim
  sharded over the ``ep`` axis** via sharding constraints — XLA inserts
  the all_to_all exchanges on the way in and out;
- combine: gate-weighted un-dispatch back to ``(tokens, d)``.

Everything is an einsum over static shapes, so the same code runs single
-device (mesh=None) and expert-parallel with identical numerics — tests
pin that equivalence.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import Primitive
from jax.interpreters import ad, mlir

from ..models.layers import Params, _normal, dense_init
from ..ops.quant import QuantizedWeight, matmul_int8


def init_moe_params(
    key,
    d_model: int,
    d_ff: int,
    n_experts: int,
) -> Params:
    kg, kw1, kw2 = jax.random.split(key, 3)
    gate = dense_init(kg, d_model, n_experts)
    # per-expert FFN weights, stacked on the (shardable) expert dim;
    # host-numpy init at the zoo's He scale (layers.py conventions)
    return {
        "gate": gate,
        "w1": _normal(kw1, (n_experts, d_model, d_ff), np.sqrt(2.0 / d_model)),
        "b1": jnp.zeros((n_experts, d_ff), jnp.float32),
        "w2": _normal(kw2, (n_experts, d_ff, d_model), np.sqrt(2.0 / d_ff)),
        "b2": jnp.zeros((n_experts, d_model), jnp.float32),
    }


def _expert_sharding(mesh, axis: str, rank: int):
    from .mesh import batch_sharding

    return batch_sharding(mesh, rank, axis)


def moe_ffn(
    params: Params,
    x,
    mesh=None,
    axis: str = "ep",
    capacity_factor: float = 2.0,
    dtype=jnp.float32,
):
    """Switch-style top-1 MoE over the trailing feature dim.

    ``x``: (..., d_model) → same shape.  With ``mesh``, the expert batch
    shards over ``axis`` (sharding constraints; XLA places the
    all_to_all); without, it is an ordinary local einsum chain.
    """
    _count_moe_lowering("switch")
    orig_shape = x.shape
    d = orig_shape[-1]
    t = 1
    for s in orig_shape[:-1]:
        t *= s
    xt = x.reshape(t, d).astype(dtype)
    e = params["w1"].shape[0]
    cap = max(1, int(np.ceil(t * capacity_factor / e)))

    # maybe_dequantize: a generic ops.quant.quantize_params walk turns the
    # gate's 2-D "w" leaf into a QuantizedWeight (which has no .astype) —
    # routing logits are tiny, so dequant-to-float is the right path
    from ..ops.quant import maybe_dequantize

    logits = (xt @ maybe_dequantize(params["gate"]["w"], dtype)
              + params["gate"]["b"].astype(dtype))
    probs = jax.nn.softmax(logits, axis=-1)  # (t, e)
    expert = jnp.argmax(probs, axis=-1)  # (t,)
    gate_w = jnp.max(probs, axis=-1)  # (t,)

    # Routing bookkeeping stays in int32 regardless of the compute dtype:
    # in bf16 a cumsum above 256 rounds, colliding tokens in capacity slots
    # and silently corrupting dispatch/combine (advisor r3, medium).
    onehot_i = jax.nn.one_hot(expert, e, dtype=jnp.int32)  # (t, e)
    # position of each token within its expert's capacity buffer
    pos = (jnp.cumsum(onehot_i, axis=0) - onehot_i) * onehot_i  # (t, e)
    pos_idx = jnp.sum(pos, axis=-1)  # (t,) int32
    keep = (pos_idx < cap).astype(dtype)  # overflow tokens drop
    onehot = onehot_i.astype(dtype)
    pos_onehot = jax.nn.one_hot(pos_idx, cap, dtype=dtype)  # (t, cap)
    # dispatch mask (t, e, cap): token t → slot (expert, position)
    dispatch = onehot[:, :, None] * pos_onehot[:, None, :] * keep[:, None, None]

    expert_in = jnp.einsum("tec,td->ecd", dispatch, xt)  # (e, cap, d)
    if mesh is not None:
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, _expert_sharding(mesh, axis, 3)
        )
    h = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", expert_in, params["w1"].astype(dtype))
        + params["b1"].astype(dtype)[:, None, :]
    )
    expert_out = (
        jnp.einsum("ecf,efd->ecd", h, params["w2"].astype(dtype))
        + params["b2"].astype(dtype)[:, None, :]
    )
    if mesh is not None:
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, _expert_sharding(mesh, axis, 3)
        )
    combine = dispatch * gate_w[:, None, None]  # (t, e, cap)
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    # a dropped (over-capacity) token has an all-zero combine row → zero
    # MoE output; the caller's residual connection carries it through
    # (standard switch-transformer drop semantics)
    return out.reshape(orig_shape).astype(x.dtype)


def place_moe_params(params: Params, mesh, axis: str = "ep") -> Params:
    """Shard the stacked expert weights over the ``ep`` axis; gate
    replicates (every token computes routing locally)."""
    from .mesh import replicated

    def shard_expert(a, rank):
        return jax.device_put(a, _expert_sharding(mesh, axis, rank))

    return {
        "gate": jax.tree.map(
            lambda a: jax.device_put(a, replicated(mesh)), params["gate"]
        ),
        "w1": shard_expert(params["w1"], 3),
        "b1": shard_expert(params["b1"], 2),
        "w2": shard_expert(params["w2"], 3),
        "b2": shard_expert(params["b2"], 2),
    }


# -- top-k of many experts, nothing dropped ----------------------------------
#
# The switch layer above sends a token to one expert and drops what exceeds a
# capacity.  This one keeps every assignment at static shapes: the ``tokens x
# k`` (token, expert) pairs are sorted by expert, so that each expert's rows
# lie together, and one grouped matrix product runs every expert over its own
# rows, whatever their number.

def matmul(x, w):
    """``x @ w`` in ``x``'s type; a ``QuantizedWeight`` (``ops/quant``) runs
    W8A8."""
    if isinstance(w, QuantizedWeight):
        return matmul_int8(x, w, x.dtype)
    return x @ w.astype(x.dtype)


def swiglu(x, w_in, w_out):
    """``(silu(x @ gate) * (x @ up)) @ w_out``, ``w_in`` = ``[gate | up]``."""
    gate, up = jnp.split(matmul(x, w_in), 2, axis=-1)
    return matmul(jax.nn.silu(gate) * up, w_out)


def route_top_k(x, router, top_k: int, scaling: float = 1.0, bias=None,
                n_group: Optional[int] = None,
                topk_group: Optional[int] = None):
    """``(weights, experts)``, both ``[tokens, top_k]``: sigmoid scores of
    ``x @ router`` in float32, the ``top_k`` highest a token, renormalised
    to sum 1 and times ``scaling``.  With ``bias`` ``[experts]`` the choice
    is by ``score + bias`` and the weights are the chosen experts' unbiased
    scores (a load-balancing bias that steers the choice alone).

    ``n_group``, ``topk_group``: the group-limited choice.  The experts
    stand in ``n_group`` groups of equal size in index order, a group's
    score is the sum of its two highest (biased) scores, the ``topk_group``
    highest groups are kept (the lower index among equals) and the
    ``top_k`` experts are chosen among the kept groups' alone.  Left out,
    or with one group, every expert stands for choice."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if group_limited(n_group):
        n, e = choice.shape
        grouped = choice.reshape(n, n_group, e // n_group)
        _, kept = jax.lax.top_k(
            jax.lax.top_k(grouped, 2)[0].sum(axis=-1), topk_group)
        open_ = (kept[:, :, None] == jnp.arange(n_group)).any(axis=1)
        choice = jnp.where(open_[:, :, None], grouped, -jnp.inf).reshape(n, e)
    if choice is scores:
        top, experts = jax.lax.top_k(scores, top_k)
    else:
        _, experts = jax.lax.top_k(choice, top_k)
        top = jnp.take_along_axis(scores, experts, axis=-1)
    return top / top.sum(axis=-1, keepdims=True) * scaling, experts


def group_limited(n_group: Optional[int]) -> bool:
    """Whether a router of ``n_group`` groups limits the choice at all."""
    return n_group is not None and n_group > 1


def _routed_grouped(x, weights, experts, w_in, w_out):
    """Through XLA: a ``ragged_dot`` for gate|up, a fusion, a ``ragged_dot``
    for down, each over the pairs' rows in HBM."""
    n, k = experts.shape
    e = w_in.shape[0]
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)      # assignments, by expert
    sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
    rows = x[order // k]                        # [n*k, d], expert-major
    gate, up = jnp.split(jax.lax.ragged_dot(rows, w_in, sizes), 2, axis=-1)
    out = jax.lax.ragged_dot((jax.nn.silu(gate) * up).astype(x.dtype), w_out,
                             sizes)
    back = jnp.argsort(order)                   # where each pair's row went
    out = out[back].reshape(n, k, -1)
    return jnp.einsum("nkd,nk->nd", out, weights.astype(out.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _routed_fused(x, weights, experts, w_in, w_out, **kernel):
    """Through ``ops/grouped_experts``: one kernel over the pairs' rows, which
    also weighs each row in float32, so a token's ``k`` rows are only summed.
    The same stable sort by expert, with the pairs' weights carried along as
    an operand of it and the groups' sizes read off the sorted experts: on
    the v5e a gather of 262 144 scalars takes 1.9 ms and ``bincount``'s
    scatter 2.3 ms, a sixth of the kernel's time each (PERF.md, PR 35).
    ``kernel``: its tile and interpret arguments (the tests')."""
    from ..ops.grouped_experts import grouped_experts

    n, k = experts.shape
    e = w_in.shape[0]
    by_expert, order, pair_weights = jax.lax.sort(
        (experts.reshape(-1).astype(jnp.int32),
         jnp.arange(n * k, dtype=jnp.int32),
         weights.reshape(-1).astype(jnp.float32)), num_keys=1, is_stable=True)
    sizes = jnp.diff(jnp.searchsorted(
        by_expert, jnp.arange(e + 1, dtype=jnp.int32))).astype(jnp.int32)
    out = grouped_experts(x[order // k], w_in, w_out, sizes, pair_weights,
                          **kernel)
    back = jnp.argsort(order)
    return out[back].reshape(n, k, -1).sum(
        axis=1, dtype=jnp.float32).astype(x.dtype)


# How many sorted rows one pass of a share's walk takes: twice what an even
# routing sends to the experts held, in whole tiles.
SHARE_ROW_TILE = 256


def share_rows(pairs: int, held: int, total: int) -> int:
    even = -(-pairs * held // total)
    return min(pairs, -(-2 * even // SHARE_ROW_TILE) * SHARE_ROW_TILE)


def _combine_plain(out, y, token, w):
    """``out[token[j]] += w[j] * y[j]`` as XLA's scatter-add, product and sum
    in float32; ``token[j] == len(out)`` is nobody's row and dropped."""
    return out.at[token].add(
        y.astype(jnp.float32) * w.astype(jnp.float32)[:, None], mode="drop")


def _routed_share(x, weights, experts, w_in, w_out, *, first: int,
                  total: int, combine=_combine_plain):
    """The part of the layer's result that experts ``first ... first + E``
    give, ``E`` = ``w_in.shape[0]`` of the ``total`` the router chose among:
    the pairs of an expert held sort to the front, by expert, the others
    behind them, and the held ones alone are gathered and multiplied.  The
    sorted rows are walked in passes of :func:`share_rows` rows, as many
    passes as the routing needs (a loop whose count is read off the sort:
    one where the load is near even, ``pairs / rows`` where every pick of
    every token is held), so the buffer is a pass's and no pair is dropped
    whatever the routing.  Nothing stands in for the experts held
    elsewhere: their pairs add zero here.

    A pass's results go back by its own rows alone: row ``j`` is its pair's
    token's, weighed by the pair's weight (in ``x``'s type) and added to the
    token's float32 row by ``combine`` (:func:`_combine_plain`, or
    ``ops/combine_rows`` where the lowering rule takes the kernel); the
    pass's rows past the held pairs are no expert's and nobody's."""
    n, k = experts.shape
    held = w_in.shape[0]
    pairs = n * k
    rows = share_rows(pairs, held, total)
    local = experts.reshape(-1).astype(jnp.int32) - first
    by_expert, order, pair_weights = jax.lax.sort(
        (jnp.where((local >= 0) & (local < held), local, held),
         jnp.arange(pairs, dtype=jnp.int32),
         weights.reshape(-1).astype(x.dtype).astype(jnp.float32)),
        num_keys=1, is_stable=True)
    starts = jnp.searchsorted(
        by_expert, jnp.arange(held + 1, dtype=jnp.int32)).astype(jnp.int32)
    n_held = starts[-1]
    order = jnp.pad(order, (0, rows))           # the last pass may overhang
    pair_weights = jnp.pad(pair_weights, (0, rows))

    def one_pass(c, out):
        r0 = c * rows
        token = jax.lax.dynamic_slice(order, (r0,), (rows,)) // k
        sizes = jnp.diff(jnp.clip(starts, r0, r0 + rows))
        gate, up = jnp.split(jax.lax.ragged_dot(x[token], w_in, sizes), 2,
                             axis=-1)
        y = jax.lax.ragged_dot((jax.nn.silu(gate) * up).astype(x.dtype),
                               w_out, sizes)
        live = r0 + jnp.arange(rows, dtype=jnp.int32) < n_held
        return combine(out, y, jnp.where(live, token, n),
                       jax.lax.dynamic_slice(pair_weights, (r0,), (rows,)))

    out = jax.lax.fori_loop(0, (n_held + rows - 1) // rows, one_pass,
                            jnp.zeros((n, x.shape[-1]), jnp.float32))
    return out.astype(x.dtype)


# One primitive, as ``ops/fused_attention.attention_p``: a trace does not know
# what it will be lowered for, so which of the two runs the products is the
# lowering rule's choice.  ``first``/``total``: the share (``None``: all);
# ``choice``: how the router chose, for the rule to count.

routed_experts_p = Primitive("nns_routed_experts")
routed_experts_p.def_impl(jax.jit(
    routed_experts_p.bind, static_argnames=("first", "total", "choice")))
routed_experts_p.def_abstract_eval(lambda x, *_, **__: x)


def routed_experts(x, weights, experts, w_in, w_out,
                   first: Optional[int] = None, total: Optional[int] = None,
                   choice: str = "global"):
    """Every (token, expert) pair of ``experts`` ``[tokens, k]`` through its
    expert's SwiGLU (``w_in`` ``[E, d, 2f]``, ``w_out`` ``[E, f, d]``), the
    ``k`` results of a token summed under ``weights``.  ``[tokens, d]``.

    The pairs are sorted by expert either way.  A TPU program for one device
    whose shapes the grouped kernel tiles (``ops/grouped_experts.tiles``)
    runs the experts in that kernel; any other program (the CPU's, one that
    GSPMD partitions, small or odd shapes, experts that are no plain arrays)
    runs them as two ``ragged_dot`` products through XLA.

    ``first``, ``total``: this layer holds experts ``first ... first + E``
    of the ``total`` that ``experts`` counts over (one chip's share of an
    expert-parallel layer); the result is the held experts' part
    (:func:`_routed_share`: XLA's ``ragged_dot`` in every program; a pass's
    results go back to their tokens through ``ops/combine_rows``' kernel in
    a one-device TPU program whose shapes ``combine_rows.tiles()``, as
    XLA's scatter-add in any other, ``nnstpu_moe_share_combine_total``).
    Left out, or with ``E == total``, the layer holds them all.
    ``choice``: ``"global"`` or ``"group_limited"``, how :func:`route_top_k`
    chose ``experts``; the lowering rule counts a layer under it
    (``nnstpu_moe_routing_total``)."""
    if first is None or w_in.shape[0] == total:
        first = total = None
    elif not 0 <= first <= total - w_in.shape[0]:
        raise ValueError(f"experts {first}...{first + w_in.shape[0]} of "
                         f"{total}")
    if isinstance(w_in, QuantizedWeight) or isinstance(w_out, QuantizedWeight):
        if first is not None:
            raise NotImplementedError("a share of quantized experts")
        _count_moe_lowering("grouped", choice)
        return _routed_grouped(x, weights, experts, w_in, w_out)
    return routed_experts_p.bind(x, weights, experts, w_in, w_out,
                                 first=first, total=total, choice=choice)


def _xla_path(first, total, combine=_combine_plain):
    if first is None:
        return _routed_grouped
    return functools.partial(_routed_share, first=first, total=total,
                             combine=combine)


def _lower_grouped(ctx, *operands, first=None, total=None, choice="global",
                   combine=_combine_plain):
    _count_moe_lowering("grouped", choice)
    if first is not None:
        _count_share_combine(
            "plain" if combine is _combine_plain else "kernel")
    _say_held(ctx.avals_in[3].shape[0], total)
    return mlir.lower_fun(_xla_path(first, total, combine),
                          multiple_results=False)(ctx, *operands)


def _lower_tpu(ctx, *operands, first=None, total=None, choice="global"):
    from ..ops import combine_rows
    from ..ops.fused_attention import _on_one_device
    from ..ops.grouped_experts import tiles

    x, _, experts, w_in, w_out = ctx.avals_in
    kernels = (_on_one_device(ctx.module_context.axis_context)
               and x.dtype == w_in.dtype == w_out.dtype)
    if first is not None:
        # a share's products are XLA's either way; its way back is the
        # kernel's where a pass's rows and the tokens' tile
        rows = share_rows(experts.size, w_in.shape[0], total)
        return _lower_grouped(
            ctx, *operands, first=first, total=total, choice=choice,
            combine=functools.partial(combine_rows.combine_rows,
                                      interpret=False)
            if kernels and combine_rows.tiles(x.shape, rows, x.dtype)
            else _combine_plain)
    if not (kernels and tiles((experts.size, x.shape[-1]), w_in.shape,
                              w_out.shape, x.dtype)):
        return _lower_grouped(ctx, *operands, choice=choice)
    _count_moe_lowering("fused", choice)
    _say_held(w_in.shape[0], total)
    return mlir.lower_fun(functools.partial(_routed_fused, interpret=False),
                          multiple_results=False)(ctx, *operands)


# not cacheable: every layer is lowered, and counted, on its own
mlir.register_lowering(routed_experts_p, _lower_grouped, cacheable=False)
mlir.register_lowering(routed_experts_p, _lower_tpu, platform="tpu",
                       cacheable=False)
# derivatives are the XLA path's (no vmap: ``ragged_dot`` has none over its
# group sizes either)
ad.primitive_jvps[routed_experts_p] = \
    lambda primals, tangents, first, total, choice: jax.jvp(
        _xla_path(first, total), primals,
        tuple(ad.instantiate_zeros(t) for t in tangents))


def _say_held(held: int, total: Optional[int]) -> None:
    from ..obs.metrics import REGISTRY

    total = held if total is None else total
    REGISTRY.gauge(
        "nnstpu_moe_held_experts",
        "experts whose weights the last expert layer lowered holds, of the "
        "experts its router chooses among", labelnames=("of",),
    ).set(held, of=str(total))


def _count_share_combine(way: str) -> None:
    from ..obs.metrics import REGISTRY

    REGISTRY.counter(
        "nnstpu_moe_share_combine_total",
        "expert layers holding a share of their experts lowered into a "
        "program, by how a pass's results go back to their tokens (kernel = "
        "sorted by token and summed as a banded one-hot product in the "
        "Pallas kernel, plain = XLA's scatter-add)", labelnames=("way",),
    ).inc(way=way)


def _count_moe_lowering(path: str, choice: Optional[str] = None) -> None:
    from ..obs.metrics import REGISTRY

    REGISTRY.counter(
        "nnstpu_moe_lowerings_total",
        "expert layers lowered into a program, by the path chosen (fused = "
        "top-k without drops through the grouped Pallas kernel, grouped = "
        "the same through XLA's grouped matrix product, switch = top-1 with "
        "a capacity)", labelnames=("path",),
    ).inc(path=path)
    if choice is not None:
        REGISTRY.counter(
            "nnstpu_moe_routing_total",
            "top-k expert layers lowered into a program, by how their router "
            "chooses (global = the k highest of all experts, group_limited = "
            "the k highest among the experts of the highest-scoring groups)",
            labelnames=("choice",),
        ).inc(choice=choice)


def moe_top_k(params: Params, x, top_k: int, scaling: float = 1.0,
              token_chunk: Optional[int] = None, first: Optional[int] = None,
              n_group: Optional[int] = None,
              topk_group: Optional[int] = None):
    """Top-``top_k`` of ``E`` SwiGLU experts beside a shared one.

    ``params``: ``router`` ``[d, E]``, ``w_in`` ``[E, d, 2f]``, ``w_out``
    ``[E, f, d]`` and, if the layer has one, ``shared`` (``w_in`` ``[d,
    2f]``, ``w_out`` ``[f, d]``), which every token goes through unweighted,
    and ``bias`` ``[E]``, which steers the choice (:func:`route_top_k`).
    ``x``: ``[..., d]`` → the same.  Router scores and the choice are float32
    whatever ``x`` is.  ``token_chunk`` walks the tokens in chunks of that
    many (a ``lax.scan``), so that the ``k``-fold copies of the activations
    that the grouped product reads and writes stay a chunk's size.

    ``first``: the layer is one chip's share of an expert-parallel one:
    ``w_in`` and ``w_out`` hold experts ``first ... first + w_in.shape[0]``
    of the router's ``E``.  The router stays ``E`` wide and picks among all
    of them, the held experts' part of the routed sum is computed
    (:func:`routed_experts`), and the shared expert whole.

    ``n_group``, ``topk_group``: the router's group-limited choice
    (:func:`route_top_k`); a token none of whose kept groups holds an expert
    held here sends nothing to the routed part.
    """
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    total = params["router"].shape[-1]

    def chunk(xc):
        w, experts = route_top_k(xc, params["router"], top_k, scaling,
                                 params.get("bias"), n_group, topk_group)
        out = routed_experts(
            xc, w, experts, params["w_in"], params["w_out"], first, total,
            "group_limited" if group_limited(n_group) else "global")
        if "shared" in params:
            out = out + swiglu(xc, params["shared"]["w_in"],
                               params["shared"]["w_out"])
        return out

    n = xt.shape[0]
    if token_chunk is None or token_chunk >= n or n % token_chunk:
        return chunk(xt).reshape(shape)
    _, out = jax.lax.scan(lambda c, xc: (c, chunk(xc)), None,
                          xt.reshape(n // token_chunk, token_chunk, -1))
    return out.reshape(shape)
