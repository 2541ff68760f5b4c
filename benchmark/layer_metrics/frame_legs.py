"""Reader ``frame_legs``: a frame's latency from inside the program.

A pipeline that starts while the hook bus has a listener (a ``--trace 1``
run) records, beside what ``program_spans`` reads, for every frame a
collect pad or a queue holds a ``<element>.pad_wait`` record (cat ``wait``)
under the frame's own trace, a ``coalesce`` record for every frame a mux or
a merge makes of others (args ``parents``), and ``host_stall`` /
``gc_pause`` records (cat ``host``) from a beat that notes when the whole
process came late.  ``nnstreamer_tpu.obs.collector.frame_legs`` follows
every source frame by those ids to its round's ``device_exec`` and its
stream's sink and splits its life into forward, wait, device and return.

The harness's side of the join is by stream and order: stream ``s`` is the
``s``-th source by name (``cam0`` .. ``cam47``, ``client0`` ..), its last
sink is ``out<s>`` (both closed-loop kinds name it so), and its ``k``-th
``<src>.push`` instant is the frame stamped ``push_ns[s][k]``.  A frame is
the window's where that stamp lies in ``[t0_ns, t1_ns)``, as
``arithmetic.window_metrics`` counts it.

A program that records no ``pad_wait`` (the parent of the PR that brought
it), or a ring that dropped records, reads as nothing: every function
returns ``None``.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from .. import arithmetic

SINK = "out{}"          # stream s's last sink, in every closed-loop kind
KEPT_STALLS = 64        # host records kept in the notes


def source_order(records) -> Dict[str, List[int]]:
    """Source element -> the times of its ``<src>.push`` instants, in
    order: the ``k``-th is the harness's frame ``k`` of that stream."""
    order: Dict[str, List[int]] = {}
    for r in records:
        if r[5] == "source" and r[4].endswith(".push"):
            order.setdefault(r[4][:-len(".push")], []).append(r[1])
    for stamps in order.values():
        stamps.sort()
    return order


def gaps(records) -> List[tuple]:
    """``program_spans``' gaps as intervals: the end of round ``k - 1``'s
    ``device_exec`` to the end of round ``k``'s ``<filter>.invoke``."""
    done: Dict[int, int] = {}
    enqueued: Dict[int, int] = {}
    for r in records:
        k = r[9].get("round") if isinstance(r[9], dict) else None
        if k is None or r[0] != "X":
            continue
        if r[4] == "device_exec":
            done[k] = max(done.get(k, 0), r[1] + r[2])
        elif r[5] == "stage" and r[4].endswith(".invoke"):
            enqueued[k] = r[1] + r[2]
    return [(done[k - 1], max(end, done[k - 1]))
            for k, end in sorted(enqueued.items()) if k - 1 in done]


def host_records(records, t0_ns: int) -> List[dict]:
    """Every ``host_stall`` / ``gc_pause`` of the run: when (ms from the
    window's opening), how long, its args, and whether it overlapped a gap
    (the device had nothing to run while the host stood still)."""
    idle = gaps(records)
    out = []
    for r in records:
        if r[5] != "host" or r[0] != "X":
            continue
        a, b = r[1], r[1] + r[2]
        out.append(dict(r[9] or {}, name=r[4], at_ms=(a - t0_ns) / 1e6,
                        ms=r[2] / 1e6,
                        in_gap=any(a < g1 and g0 < b for g0, g1 in idle)))
    return out


def window_legs(records, res, legs_of) -> Optional[dict]:
    """The window's frames with their legs, each beside the harness's own
    latency of the same frame where its answer arrived; ``legs_of`` is the
    program's ``collector.frame_legs``.  None for a ring without waits and
    for a window none of whose frames joined."""
    if not any(r[5] == "wait" for r in records):
        return None
    order = source_order(records)
    stream = {src: s for s, src in enumerate(
        sorted(order, key=lambda n: (len(n), n)))}
    nth = {src: {ts: k for k, ts in enumerate(stamps)}
           for src, stamps in order.items()}
    frames = []
    for f in legs_of(records, {src: SINK.format(s)
                               for src, s in stream.items()}):
        s, k = stream[f["source"]], nth[f["source"]][f["push_ns"]]
        if s >= len(res.push_ns) or k >= len(res.push_ns[s]) \
                or not (res.t0_ns <= res.push_ns[s][k] < res.t1_ns):
            continue
        harness_ns = res.sink_ns[s][k] - res.push_ns[s][k] \
            if k < len(res.sink_ns[s]) else None
        frames.append(dict(f, harness_ns=harness_ns))
    if not frames:
        return None
    stalls = [r for r in records if r[4] == "host_stall" and r[0] == "X"
              and r[1] < res.t1_ns and res.t0_ns < r[1] + r[2]]
    return {"frames": frames,
            "stall_ms_max": max((r[2] for r in stalls), default=0) / 1e6}


def _ms(frames, key: str, q: float) -> Optional[float]:
    if not frames:
        return None
    return arithmetic.percentile([f[key] / 1e6 for f in frames], q)


def by_pad(frames) -> Dict[str, float]:
    """Median wait per pad, ``<element>.<pad>``: the pad that reads ~0 is
    the round's carrier's."""
    held: Dict[str, List[float]] = {}
    for f in frames:
        for name, pad, ns in f["waits"]:
            held.setdefault(f"{name[:-len('.pad_wait')]}.{pad}",
                            []).append(ns / 1e6)
    return {pad: arithmetic.percentile(v, 50) for pad, v in held.items()}


def _summary(ctx) -> Optional[dict]:
    """The window's legs, read once a run and kept on ``ctx``."""
    if hasattr(ctx, "frame_legs"):
        return ctx.frame_legs
    ctx.frame_legs = None
    from nnstreamer_tpu.obs import collector, spans

    legs_of = getattr(collector, "frame_legs", None)
    if legs_of is None or spans.recorder_stats()["dropped"]:
        return None
    records = spans.snapshot()
    ctx.frame_legs = window_legs(records, ctx.result, legs_of)
    if ctx.frame_legs is not None:
        frames = ctx.frame_legs["frames"]
        ctx.notes.update(
            frames_joined=len(frames),
            frame_wait_ms_by_pad=by_pad(frames),
            frame_device_ms_p50=_ms(frames, "device_ns", 50),
            frame_forward_ms_p50=_ms(frames, "forward_ns", 50),
            # the harness's own p50 over the same frames, for the sum of
            # the four legs' medians to be held against
            frame_harness_ms_p50=_ms(
                [f for f in frames if f["harness_ns"] is not None],
                "harness_ns", 50),
            host_records=host_records(
                records, ctx.result.t0_ns)[:KEPT_STALLS])
    return ctx.frame_legs


def _leg(ctx, key: str, q: float) -> Optional[float]:
    summary = _summary(ctx)
    return _ms(summary["frames"], key, q) if summary else None


def frame_wait_ms_p50(ctx) -> Optional[float]:
    """Median over the window's frames of what a frame waited in collect
    pads and queues until the round that took it was booked."""
    return _leg(ctx, "wait_ns", 50)


def frame_wait_ms_p95(ctx) -> Optional[float]:
    """The same waits' 95th percentile."""
    return _leg(ctx, "wait_ns", 95)


def frame_return_ms_p50(ctx) -> Optional[float]:
    """Median of the way back: the end of the round's ``device_exec`` to
    the end of the stream's sink span."""
    return _leg(ctx, "return_ns", 50)


def frame_unaccounted_pct(ctx) -> Optional[float]:
    """What the program's legs fail to cover of the harness's latency,
    over the frames both hold: 100 x (sum harness - sum legs) / sum
    harness.  None, never 0, where no frame joined."""
    summary = _summary(ctx)
    both = [f for f in summary["frames"] if f["harness_ns"] is not None] \
        if summary else []
    harness = sum(f["harness_ns"] for f in both)
    if harness <= 0:
        return None
    legs = sum(f["forward_ns"] + f["wait_ns"] + f["device_ns"]
               + f["return_ns"] for f in both)
    return 100.0 * (harness - legs) / harness


def host_stall_ms_max(ctx) -> Optional[float]:
    """The longest ``host_stall`` that overlaps the window; 0.0 in a run
    that met none."""
    summary = _summary(ctx)
    return summary["stall_ms_max"] if summary else None
