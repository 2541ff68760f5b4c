"""Metric arithmetic over the harness's own stamps.

Every end-to-end number is taken over every frame and all the time of the
window: a rate is frames over the window's seconds, a tail is the tail of
all frames.  Nothing here is a median of chunks.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default), ``q`` in [0, 100]."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def round_close_ns(sink_ns: List[List[int]], t1_ns: int) -> Optional[int]:
    """Where a window that may last until ``t1`` closes when labels come a
    round at a time (label ``k`` of every stream is one batch): the end of
    the first round that ends at or after ``t1``.  None where no round does."""
    for labels in zip(*sink_ns):
        if max(labels) >= t1_ns:
            return max(labels)
    return None


def window_metrics(push_ns: List[List[int]], sink_ns: List[List[int]],
                   t0_ns: int, t1_ns: int,
                   close_ns: Optional[int] = None) -> Dict[str, float]:
    """End-to-end numbers of one window that opens at ``t0`` and lasts until
    ``close`` (``t1`` where none is given; never before ``t1``).

    ``push_ns[s][k]`` / ``sink_ns[s][k]``: when stream ``s`` pushed its
    ``k``-th frame and when that frame's label reached the stream's sink
    (matched by order; a frame that never arrived has no sink stamp).

    - ``attempted``: frames pushed in ``[t0, t1)``;
    - ``arrived``: of those, the ones whose label arrived (at any time: a
      late frame is late, its latency counts the wait);
    - ``frames_per_s``: every label that arrives in ``(t0, close]``, whenever
      its frame was pushed, over all of ``close - t0``.  Labels come a batch
      at a time, so a window cut at ``t1`` itself would read in steps of one
      batch (3.8 % at 26 batches a window).  The harness opens the window at
      the end of a round and closes it at the end of the round in flight at
      ``t1`` (``round_close_ns``): whole rounds over all the time they took,
      ``t1 - t0`` or a little more, never less - a stall that lasts past
      ``t1`` lengthens the window by as much;
    - latencies: over every attempted frame that arrived.
    """
    if t1_ns <= t0_ns:
        raise ValueError("empty window")
    if close_ns is None:
        close_ns = t1_ns
    if close_ns < t1_ns:
        raise ValueError("the window closes before its time is up")
    attempted = arrived = labels = 0
    lat_ms: List[float] = []
    for pushes, sinks in zip(push_ns, sink_ns):
        labels += sum(1 for ts in sinks if t0_ns < ts <= close_ns)
        for k, tp in enumerate(pushes):
            if t0_ns <= tp < t1_ns:
                attempted += 1
                if k < len(sinks):
                    arrived += 1
                    lat_ms.append((sinks[k] - tp) / 1e6)
    out = {
        "attempted": attempted,
        "arrived": arrived,
        "window_s": (close_ns - t0_ns) / 1e9,
        "frames_per_s": labels / ((close_ns - t0_ns) / 1e9),
        "latency_samples": len(lat_ms),
    }
    if lat_ms:
        out["frame_latency_p50_ms"] = percentile(lat_ms, 50)
        out["frame_latency_p95_ms"] = percentile(lat_ms, 95)
    return out


def share_pct(least_s: float, measured_s: float) -> Optional[float]:
    """``least_s`` (the least time the chip could take) as a share of the
    time it took, in percent.  Nothing to read gives nothing — never 0.
    No clamp: a share over 100 means the work is counted too high or the
    time leaves out part of it, and has to show."""
    if measured_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / measured_s


def least_time_s(flops: float, nbytes: float, peak) -> Dict[str, float]:
    """Roofline least time: the larger of FLOPs over peak FLOP/s and bytes
    over peak bytes/s, with which of the two bounds it."""
    t_f = flops / peak.flops_per_s
    t_b = nbytes / peak.bytes_per_s
    return {"seconds": max(t_f, t_b), "compute_s": t_f, "memory_s": t_b,
            "bound": "compute" if t_f >= t_b else "memory"}
