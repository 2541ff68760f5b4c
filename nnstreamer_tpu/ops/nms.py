"""On-device greedy non-maximum suppression (the segment-compile NMS op).

The bounding-box decoders run the reference's greedy IoU-0.5 suppression
(``tensordec-boundingbox.c:740-780``) as a Python pair loop on host —
O(K²) `iou()` calls per frame, the single heaviest host leg of the SSD
pipelines.  Whole-segment compilation (``graph/segments.py``) folds the
decode INTO the detector's XLA program, so NMS needs a device form whose
verdicts are **bit-identical** to the host loop:

- boxes arrive as *integer-valued* float32 pixel coordinates (the shared
  ``decoders.bounding_boxes.px`` rounding rule quantizes before NMS, as
  the host path does);
- the host compares ``inter/union > 0.5`` in float64.  With integer
  areas (< 2²⁴, exact in float32) that comparison is equivalent to the
  all-integer ``2·inter > union`` — which both numpy and XLA evaluate
  exactly, so no float-division ULP can ever flip a suppression verdict
  between the host and device paths;
- suppression is sequential by construction (row *i*'s survival depends
  on rows < *i*), expressed as a ``lax.fori_loop`` over the candidate
  rows, each step masking the rows a surviving candidate suppresses.

Two entry points:

- :func:`nms_keep` — pure jax/XLA, the default inside fused segments;
- :func:`pallas_nms_keep` — the same algorithm as a single Pallas
  program (``[segment] pallas_nms``): one kernel computes the pairwise
  suppression matrix in VMEM and walks it sequentially, for the regimes
  where XLA stalls fusing the O(K²) mask chain into its consumer.
  Off-TPU it runs in interpret mode, so behavior is platform-independent
  (same posture as :mod:`.pallas_kernels`).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# pairwise width/height use the reference's inclusive-pixel convention
# (x2 - x1 + 1, tensordec-boundingbox.c:744) — see decoders.bounding_boxes.iou


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pairwise_suppression(xc, yc, wc, hc, xr, yr, wr, hr):
    """``iou(i, j) > 0.5`` for column-form boxes *i* ``(K, 1)`` against
    row-form boxes *j* ``(1, K)`` — the one copy of the arithmetic, shared
    by the XLA form and the Pallas kernel body."""
    iw = jnp.maximum(
        0.0, jnp.minimum(xc + wc, xr + wr) - jnp.maximum(xc, xr) + 1.0)
    ih = jnp.maximum(
        0.0, jnp.minimum(yc + hc, yr + hr) - jnp.maximum(yc, yr) + 1.0)
    inter = iw * ih
    union = wc * hc + wr * hr - inter
    # iou > 0.5  ⟺  2·inter > union: exact on integer-valued floats,
    # immune to the float-division rounding the direct form would add
    return (union > 0.0) & (2.0 * inter > union)


def suppression_matrix(x, y, w, h):
    """(K, K) bool: ``iou(i, j) > 0.5`` under the host loop's exact
    arithmetic.  Inputs are integer-valued float32 pixel boxes."""
    cols = (v[:, None] for v in (x, y, w, h))
    rows = (v[None, :] for v in (x, y, w, h))
    return _pairwise_suppression(*cols, *rows)


def greedy_keep(sup, valid):
    """Sequential greedy pass over score-ordered rows: row *i* (if still
    kept) suppresses every later row it overlaps.  ``valid`` seeds the
    keep mask — rows below the detection threshold neither survive nor
    suppress, exactly like the host loop that never sees them."""
    k = sup.shape[0]
    idx = jnp.arange(k)

    def body(i, keep):
        mask = sup[i] & (idx > i) & keep[i]
        return keep & ~mask

    return lax.fori_loop(0, k, body, valid)


def nms_keep(x, y, w, h, valid):
    """Pure-XLA NMS: keep mask over score-ordered integer-pixel boxes."""
    return greedy_keep(suppression_matrix(x, y, w, h), valid)


def pallas_nms_keep(x, y, w, h, valid, interpret: Optional[bool] = None):
    """The same greedy pass as one Pallas program: boxes land in VMEM
    once, the suppression matrix never materializes in HBM, and the
    sequential walk runs in-kernel.  Inputs/outputs match
    :func:`nms_keep` bit-for-bit (the kernel body *is* the same
    arithmetic).

    Mosaic has no 1-D dynamic indexing, so everything in the kernel is
    2-D: boxes arrive as a ``(4, Kp)`` row block *and* its ``(Kp, 4)``
    transpose (the pairwise terms are then plain broadcasts), the 0/1
    suppression matrix is parked in a VMEM scratch whose row *i* is a
    dynamic sublane slice, and ``keep[i]`` is a masked lane reduction."""
    if interpret is None:
        interpret = _interpret()
    k = int(x.shape[0])
    pad = -k % 128  # lane-align the row vectors for the TPU layout
    kp = k + pad

    def _pad(v, fill=0.0):
        return jnp.pad(v.astype(jnp.float32), (0, pad), constant_values=fill)

    # padded rows: never valid, and placed where no real box overlaps them
    rows = jnp.stack([_pad(x), _pad(y), _pad(w, fill=-1.0),
                      _pad(h, fill=-1.0)])
    seed = _pad(valid.astype(jnp.float32)).reshape(1, kp)

    def kernel(row_ref, col_ref, v_ref, out_ref, sup_ref):
        xr, yr, wr, hr = (row_ref[i:i + 1, :] for i in range(4))  # (1, Kp)
        xc, yc, wc, hc = (col_ref[:, i:i + 1] for i in range(4))  # (Kp, 1)
        sup_ref[...] = jnp.where(
            _pairwise_suppression(xc, yc, wc, hc, xr, yr, wr, hr), 1.0, 0.0)
        lane = lax.broadcasted_iota(jnp.int32, (1, kp), 1)

        def body(i, keep):
            keep_i = jnp.max(jnp.where(lane == i, keep, 0.0),
                             axis=1, keepdims=True)
            mask = (sup_ref[pl.ds(i, 1), :] > 0.0) & (lane > i) \
                & (keep_i > 0.0)
            return jnp.where(mask, 0.0, keep)

        out_ref[...] = lax.fori_loop(0, k, body, v_ref[...]).astype(jnp.int32)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, kp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((kp, kp), jnp.float32)],
        interpret=interpret,
    )(rows, rows.T, seed)
    return out[0, :k] != 0


def keep_fn(use_pallas: bool):
    """The NMS implementation a fused segment should trace, per the
    ``[segment] pallas_nms`` knob (resolved once at install time — the
    choice is baked into the compiled program and its fingerprint)."""
    return pallas_nms_keep if use_pallas else nms_keep
