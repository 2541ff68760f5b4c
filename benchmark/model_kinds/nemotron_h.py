"""Model kind ``nemotron_h``: the repo's sequential hybrid token model, a
Mamba-2 mixer, grouped attention or a latent mixture of relu² experts in
each layer (``nnstreamer_tpu/models/nemotron_h.py``), at a configuration's
sizes.

A configuration's file holds the published ``config.json`` keys at its top
level as they are run, laid out as the ``glm_dsa`` kind's: every width
whole, the depth cut to the layers ``build.layers`` names (published
indices) with ``hybrid_override_pattern`` their slice, ``n_routed_experts``
the experts *held here* and ``vocab_size`` the rows held here.  ``build``
also gives ``router_experts`` (the published expert count, which the router
keeps), ``first_expert`` (where this chip's share starts), ``seq`` (the
window a frame holds) and ``token_chunk`` (the tokens an expert layer takes
at a time); ``rehearsal`` is what a CPU run overrides.  ``sizes`` hands the
program and the reference one dict in the program's keys:
``n_routed_experts`` the router's width, ``experts_held`` = ``[first,
count]``, ``layers``.

The weights are made here from a seed, on the host, in the served type.  The
work functions count the algorithm's work from the shapes: the Mamba
layers' scan (the Gram of C against B once a group), the causal half of the
scores, ``num_experts_per_tok`` of ``n_routed_experts`` experts a token of
which the share held here, in the latent width, and the shared expert.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

# at the top, so that a program without the model fails as the kind is
# imported and not after the weights are made
from nnstreamer_tpu.models import nemotron_h as program
from nnstreamer_tpu.ops import ssm_scan
from nnstreamer_tpu.parallel import moe

BYTES_PER_VALUE = 2  # bf16, the dtype the configuration states
# init_weights' decisive routers (as the ``glm_dsa`` kind's): how far along
# an expert's direction a token's embedding lies if the expert was drawn for
# it, what a router reads of that direction, and the deviation of the
# selection bias
TOKEN_MARK = 16.0
ROUTER_GAIN = 0.5
BIAS_STD = 0.02
HARNESS_KEYS = ("source", "published", "kind", "reference", "dtype",
                "weights_seed", "reduced", "assumed", "limits",
                "rehearsal_limits", "build", "rehearsal", "name",
                "deployment")


def sizes(cfg: Dict[str, Any], rehearsal: bool = False) -> Dict[str, Any]:
    """The model's ``config.json`` as the program reads it, with ``seq``,
    ``token_chunk`` and ``layers``."""
    s = {k: v for k, v in cfg.items() if k not in HARNESS_KEYS}
    s.update(cfg["build"])
    if rehearsal:
        s.update(cfg["rehearsal"])
    s["experts_held"] = [s.pop("first_expert"), s["n_routed_experts"]]
    s["n_routed_experts"] = s.pop("router_experts")
    return s


def frame_shape(s: Dict[str, Any]) -> Tuple[int]:
    return (s["seq"],)


def _count(s: Dict[str, Any], kind: str) -> int:
    return program.layer_kinds(s).count(kind)


def _mixer(s: Dict[str, Any]) -> Dict[str, int]:
    return program.widths(program.mixer_config(s))


def frame_flops(s: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs one frame needs (a multiply-add is 2): per Mamba layer its in-
    and out-projections, the causal conv and the scan
    (:func:`narrow_scan_work`); per attention layer q, k, v and o and the
    score and value products over the causal half; per expert layer the
    router, the latent projections, the shared expert and the share of a
    token's ``num_experts_per_tok`` routed experts that an even routing
    sends to the experts held here (:func:`latent_experts_work`); the head
    at the last position."""
    t, d, w = s["seq"], s["hidden_size"], _mixer(s)
    mamba, attn, experts = (_count(s, k) for k in "M*E")
    hq = s["num_attention_heads"] * s["head_dim"]
    kv = s["num_key_value_heads"] * s["head_dim"]
    parts = {
        "mixer_projections": mamba * 2.0 * t * d * (w["in"] + w["d_ssm"]),
        "conv": mamba * 2.0 * t * s["conv_kernel"] * w["conv"],
        "ssm_scan": narrow_scan_work(s)["flops"],
        "attention_projections": attn * 2.0 * t * d * (2 * hq + 2 * kv),
        "attention": attn * 4.0 * (t * (t + 1) // 2) * hq,
        "router": experts * 2.0 * t * d * s["n_routed_experts"],
        "latent_projections": experts * 4.0 * t * d * s["moe_latent_size"],
        "shared_experts": experts * 4.0 * t * d
        * s["moe_shared_expert_intermediate_size"],
        "routed_experts": latent_experts_work(s)["flops"],
        "head": 2.0 * d * s["vocab_size"],
    }
    return dict(parts, total=float(sum(parts.values())))


def narrow_scan_work(s: Dict[str, Any]) -> Dict[str, float]:
    """The Mamba layers' scan of one frame, a token and layer at a time: the
    Gram of C against B once a group and chunk (``2 Q N`` a token and
    group), the masked Gram times X and the state's C product and update
    once a head (``2 Q P + 4 N P`` a token and head: what the work needs,
    not the whole lane tile the kernel's shared_tile body multiplies); x, B
    and C read and y written once in the served type, Δ read once in
    float32 (the conv and the gate run outside the kernel)."""
    t, n = s["seq"], _count(s, "M")
    q, states = s["chunk_size"], s["ssm_state_size"]
    heads, groups, p = (s["mamba_num_heads"], s["n_groups"],
                        s["mamba_head_dim"])
    per_token = (2 * q * states * groups
                 + heads * (2 * q * p + 4 * states * p))
    values = 2 * heads * p + 2 * groups * states
    return {"flops": float(n * t * per_token),
            "bytes": float(n * t * (values * BYTES_PER_VALUE + 4 * heads))}


def latent_experts_work(s: Dict[str, Any]) -> Dict[str, float]:
    """The routed experts held here, in the latent width, over one frame:
    the share of a token's ``num_experts_per_tok`` pairs that an even
    routing sends to them, each ``2 · 2 · latent · f``; the tokens' latent
    rows read and their float32 combined rows written once a layer (the
    weights are no frame's bytes, as in the ``glm_dsa`` kind's
    ``held_experts``)."""
    t, n = s["seq"], _count(s, "E")
    held = s["experts_held"][1] / s["n_routed_experts"]
    pairs = t * s["num_experts_per_tok"] * held
    lat = s["moe_latent_size"]
    return {"flops": float(n * pairs * 4 * lat * s["moe_intermediate_size"]),
            "bytes": float(n * t * lat * (BYTES_PER_VALUE + 4))}


def marks(s: Dict[str, Any]) -> Dict[str, Dict[str, list]]:
    """``narrow_scan``: the kernel by its name, and on the plain path the
    ops that hold a chunk's per-head ``Q x Q`` decays or its per-head ``P x
    N`` states.  ``latent_experts``: ``ragged-dot`` and the way back's
    kernel by name, and the ops over a share's pass of sorted rows (its
    latent rows and its hidden rows), the pass of ``c`` tokens' pairs that
    ``moe.share_rows`` sizes.  No mark names an array that a loop carries
    whole (the token chunks, a chunk's float32 sum the passes add into), or
    the loop would count beside its body."""
    q, heads = s["chunk_size"], s["mamba_num_heads"]
    c = s["token_chunk"]
    passed = moe.share_rows(c * s["num_experts_per_tok"],
                            s["experts_held"][1], s["n_routed_experts"])
    return {"narrow_scan": {"names": [ssm_scan.KERNEL_NAME],
                            "dims": [[heads, q, q],
                                     [heads, s["mamba_head_dim"],
                                      s["ssm_state_size"]]]},
            "latent_experts": {
                "names": ["ragged-dot", "nns_combine_rows"],
                "dims": [[passed, s["moe_latent_size"]],
                         [passed, s["moe_intermediate_size"]]]}}


def param_count(s: Dict[str, Any]) -> int:
    d, w = s["hidden_size"], _mixer(s)
    hq = s["num_attention_heads"] * s["head_dim"]
    kv = s["num_key_value_heads"] * s["head_dim"]
    lat, e = s["moe_latent_size"], s["n_routed_experts"]
    mamba = (d * w["in"] + w["d_ssm"] * d                  # in, out
             + (s["conv_kernel"] + 1) * w["conv"]          # conv, bias
             + 3 * s["mamba_num_heads"] + w["d_ssm"])      # A, D, Δ; norm
    attn = 2 * d * hq + 2 * d * kv
    experts = ((d + 1) * e + 2 * d * lat                   # router, bias
               + 2 * d * s["moe_shared_expert_intermediate_size"]
               + s["experts_held"][1] * 2 * lat * s["moe_intermediate_size"])
    per_kind = {"M": mamba, "*": attn, "E": experts}
    layers = sum(per_kind[k] + d for k in program.layer_kinds(s))
    return 2 * s["vocab_size"] * d + d + layers


def init_weights(s: Dict[str, Any], seed: int):
    """``models/nemotron_h``'s pytree: bf16 numpy arrays on the host, as a
    checkpoint's load leaves them.  Every large array is drawn in slabs of
    at most 32 M values, each from a generator of its own spawned from
    ``seed``, a few slabs at a time in threads: matrices ``N(0, 1 /
    fan-in)``, the embedding ``N(0, 1)``, the norms' gains ``N(1, 0.1)``.
    The mixer's small arrays as Mamba-2 initialises them, drawn: the conv
    ``U(±1 / sqrt(taps))`` (weights and bias), ``A_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of a log-uniform Δ in [1e-3, 1e-1],
    ``D ~ U(0.5, 1.5)``.

    The routers are decisive, as the ``glm_dsa`` kind's and for its reason
    (top-22 of 512 under bf16 has many near-ties at the 22nd score): expert
    ``e`` of the router's ``n_routed_experts`` has a direction of ``±1 /
    sqrt(hidden)``, the same in every layer, its score reads
    ``ROUTER_GAIN`` of the stream along it, and a token's embedding lies
    ``TOKEN_MARK`` along the directions of the ``num_experts_per_tok``
    experts drawn for it uniformly, held here or not (an even load over
    the experts held).  The directions are orthonormal, and the matrices
    that write into the stream (each layer's last) have them projected out
    of their columns, so that no layer adds along them: at 11 layers of
    unit-scale outputs the choice would otherwise blur by the last expert
    layer (with the marks alone half the tokens' 22nd and 23rd scores lie
    within 0.02 there; PERF.md §2).  The selection bias is ``N(0,
    BIAS_STD)``."""
    from concurrent.futures import ThreadPoolExecutor

    import ml_dtypes
    import numpy as np

    d, dh, w = s["hidden_size"], s["head_dim"], _mixer(s)
    hq = s["num_attention_heads"] * dh
    kv = s["num_key_value_heads"] * dh
    heads, taps = s["mamba_num_heads"], s["conv_kernel"]
    e, k = s["n_routed_experts"], s["num_experts_per_tok"]
    held, lat = s["experts_held"][1], s["moe_latent_size"]
    f, fs = s["moe_intermediate_size"], s["moe_shared_expert_intermediate_size"]
    small = np.random.default_rng([seed, 0x55])
    bf16 = ml_dtypes.bfloat16
    jobs = []  # (array, rows, std, mean, what is added, projected out)
    directions = np.linalg.qr(np.random.default_rng([seed, 0xD]).standard_normal(
        (d, e), dtype=np.float32))[0].T                 # [e, d] orthonormal
    drawn = np.random.default_rng([seed, 0xE]).random(
        (s["vocab_size"], e), dtype=np.float32)
    chosen = np.argpartition(drawn, k, axis=1)[:, :k]  # a token's experts

    def token_marks(r0, r1):
        along = np.zeros((r1 - r0, e), np.float32)
        np.put_along_axis(along, chosen[r0:r1], np.float32(TOKEN_MARK), 1)
        return along @ directions

    def normal(shape, std, mean=0.0, plus=None, writes=False):
        a = np.empty(shape, bf16)
        flat = a.reshape(shape[0], -1) if len(shape) > 1 else a.reshape(1, -1)
        step = max(1, (32 << 20) // flat.shape[1])
        jobs.extend((flat, r, min(r + step, flat.shape[0]), std, mean, plus,
                     writes) for r in range(0, flat.shape[0], step))
        return a

    def matrix(*shape, writes=False):
        return normal(shape, shape[-2] ** -0.5, writes=writes)

    def gain(n=d):
        return normal((n,), 0.1, 1.0)

    def uniform(lo, hi, *shape):
        return small.uniform(lo, hi, shape).astype(np.float32)

    layers = []
    for kind in program.layer_kinds(s):
        if kind == "M":
            dt0 = np.exp(uniform(np.log(1e-3), np.log(1e-1), heads))
            bound = taps ** -0.5
            p = {"mixer": {
                "w_in": matrix(d, w["in"]),
                "conv_w": uniform(-bound, bound, taps, w["conv"]).astype(bf16),
                "conv_b": uniform(-bound, bound, w["conv"]).astype(bf16),
                "dt_bias": (dt0 + np.log(-np.expm1(-dt0))).astype(bf16),
                "A_log": np.log(uniform(1, 16, heads)).astype(bf16),
                "D": uniform(0.5, 1.5, heads).astype(bf16),
                "norm": gain(w["d_ssm"]),
                "w_out": matrix(w["d_ssm"], d, writes=True)}}
        elif kind == "*":
            p = {"attn": {"wq": matrix(d, hq), "wk": matrix(d, kv),
                          "wv": matrix(d, kv),
                          "wo": matrix(hq, d, writes=True)}}
        else:
            p = {"moe": {
                "router": normal((d, e), 1e-3, plus=lambda r0, r1:
                                 ROUTER_GAIN * directions.T[r0:r1]),
                "bias": normal((e,), BIAS_STD),
                "latent_in": matrix(d, lat),
                "latent_out": matrix(lat, d, writes=True),
                "w_in": matrix(held, lat, f), "w_out": matrix(held, f, lat),
                "shared": {"w_in": matrix(d, fs),
                           "w_out": matrix(fs, d, writes=True)}}}
        layers.append(dict(p, norm=gain()))
    weights = {"embed": normal((s["vocab_size"], d), 1.0, plus=token_marks),
               "layers": layers, "norm": gain(),
               "head": matrix(d, s["vocab_size"])}

    def draw(job, seq):
        flat, r0, r1, std, mean, plus, writes = job
        a = np.random.default_rng(seq).standard_normal(
            (r1 - r0, flat.shape[1]), dtype=np.float32)
        a *= np.float32(std)
        if mean:
            a += np.float32(mean)
        if plus is not None:
            a += plus(r0, r1)
        if writes:  # nothing written along a router's direction
            a -= (a @ directions.T) @ directions
        flat[r0:r1] = a

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(draw, jobs, np.random.SeedSequence(seed).spawn(len(jobs))))
    return weights


def build_program(s: Dict[str, Any], weights, batch: int,
                  control: bool = False):
    """The system under test: ``nemotron_h.build`` over ``weights`` at this
    configuration's sizes.  ``control=True`` is the program's own step below
    bfloat16: the Mamba layers' carried state and decays in bfloat16
    (``low``) and the projections W8A8 (``build_quantized``); it exists to
    be refused by the comparison."""
    import jax.numpy as jnp

    build = program.build_quantized if control else program.build
    return build(config=s, seq=s["seq"], batch=batch, dtype=jnp.bfloat16,
                 params=weights, low=control, token_chunk=s["token_chunk"])
