"""Reader ``device_trace``: per-layer metrics from the profiler's trace.

Work comes from the configuration's shapes (the model kind's work
functions), time from the trace's device lines, peaks from ``peaks.py``.
Where several chips ran, each number is the mean over them.
"""

from __future__ import annotations

from typing import Optional

from .. import arithmetic


def _mean(values):
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None


def step_mfu(ctx) -> Optional[float]:
    """Frames finished in the traced steps x FLOPs a frame needs, over the
    summed device time of the model's executable x the chip's peak."""
    if not ctx.slices:
        return None
    flops = ctx.kind.frame_flops(ctx.sizes)["total"] * ctx.frames_per_step
    return _mean(
        arithmetic.share_pct(s.steps * flops / ctx.peak.flops_per_s / ctx.chips,
                             s.model_ns / 1e9)
        for s in ctx.slices)


def roofline(ctx, label: str) -> Optional[float]:
    """The part of the program its kind marks ``label``: the least time for
    its work (``kind.<label>_work(sizes)``: FLOPs and bytes one frame needs;
    the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s) over
    the summed device time of the ops that carry the mark.  Nothing where
    the kind has no such work function or no traced op carries the mark."""
    work_of = getattr(ctx.kind, f"{label}_work", None) if ctx.slices else None
    if work_of is None:
        return None
    work = work_of(ctx.sizes)
    frames = ctx.frames_per_step / ctx.chips
    least = arithmetic.least_time_s(work["flops"] * frames,
                                    work["bytes"] * frames, ctx.peak)
    ctx.notes[f"{label}_bound"] = least["bound"]
    return _mean(arithmetic.share_pct(s.steps * least["seconds"],
                                      s.marked_ns.get(label, 0.0) / 1e9)
                 for s in ctx.slices)


def attention_roofline(ctx) -> Optional[float]:
    """Attention's work (4 T^2 d FLOPs a layer; q, k, v, o once) over the
    device time of the ops marked ``attention``: the fused kernel by its
    name, and the ops that hold a ``T x T`` array where a program has them."""
    return roofline(ctx, "attention")


def device_idle_pct(ctx) -> Optional[float]:
    """1 - union of the device's op intervals over the traced steps."""
    return _mean(100.0 * (1.0 - s.busy_ns / s.window_ns)
                 for s in ctx.slices if s.window_ns > 0)
