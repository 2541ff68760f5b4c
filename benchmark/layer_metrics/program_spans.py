"""Reader ``program_spans``: per-layer metrics from the program's own spans.

A pipeline that starts while the hook bus has a listener (the harness's
``device_dispatch`` callback of a ``--trace 1`` run) records to
``nnstreamer_tpu.obs.spans``' ring, on ``perf_counter_ns`` like the
harness's ``t0_ns``/``t1_ns``:

- a ``dispatch`` span per element and frame (args ``element``: its class),
  nested by ``parent`` as the chain nests;
- ``stage`` spans ``<filter>.invoke`` (host side of upload + enqueue, args
  ``round``) and ``<mux>.ticket_wait`` (a collected round queueing behind
  the round before it);
- ``device_exec`` on the reaper's track: enqueue -> done, args ``round``.

The *gap* of round ``k`` is ``[end of device_exec(k-1), end of
<filter>.invoke(k)]``: the device has nothing to run.  Two threads share
it: the one that carried round ``k-1`` (its return path, until it hands
the mux's ticket over) and the one that carries round ``k`` (from the end
of its ticket wait to the enqueue).  Inside the gap each span's *self*
time (its clipped length less its children's) goes to the stage of its
element's class; what no span of those two threads' chains covers is the
unnamed rest.  A program that records none of this (the parent of the PR
that brought it) reads as nothing: every function returns ``None``.
"""

from __future__ import annotations

import collections
from typing import Callable, Dict, List, NamedTuple, Optional

from .. import arithmetic

# the program's element classes (``graph/registry.py``), by the stage their
# self time belongs to; the filter's own span less its invoke is dispatch
# work on the way in
COLLECT = frozenset({
    "TensorConverter", "TensorMux", "TensorBatch", "TensorMerge",
    "TensorTransform", "TensorFilter", "TensorUpload", "DynBatch",
    "TensorAggregator", "TensorCrop", "TensorRate", "TensorIf", "Queue",
    "Valve", "InputSelector", "SparseEnc", "TensorQueryClient"})
RETURN = frozenset({
    "Tee", "TensorSplit", "TensorUnbatch", "DynUnbatch", "TensorDemux",
    "TensorDecoder", "TensorSink", "AppSink", "FakeSink", "FileSink",
    "TensorSave", "TensorRepoSink", "OutputSelector", "SparseDec",
    "TensorDebug"})


class Span(NamedTuple):
    """One complete record of the ring, as the reader uses it."""
    name: str
    cat: str
    tid: str
    start: int
    end: int
    sid: int
    parent: int
    args: dict


def complete_spans(records) -> List[Span]:
    """The ``X`` records of ``spans.snapshot()`` (``obs/flight.py``'s
    layout: ph, ts, dur, tid, name, cat, trace, span, parent, args)."""
    return [Span(r[4], r[5], r[3], r[1], r[1] + r[2], r[7], r[8],
                 r[9] if isinstance(r[9], dict) else {})
            for r in records if r[0] == "X"]


def stage_of(span: Span) -> Optional[str]:
    if span.cat == "stage":
        return "invoke" if span.name.endswith(".invoke") else None
    element = span.args.get("element")
    if element in COLLECT:
        return "collect"
    if element in RETURN:
        return "return"
    return None


class Chains:
    """The dispatch and stage spans of a snapshot, nested by parent."""

    def __init__(self, spans: List[Span],
                 key: Callable[[Span], Optional[str]] = stage_of):
        self.key = key
        self.by_sid: Dict[int, Span] = {}
        self.children: Dict[int, List[Span]] = {}
        for s in spans:
            if s.cat in ("dispatch", "stage"):
                self.by_sid[s.sid] = s
                self.children.setdefault(s.parent, []).append(s)

    def root(self, span: Span) -> Span:
        """The outermost span of ``span``'s chain on its own thread."""
        while True:
            up = self.by_sid.get(span.parent)
            if up is None or up.tid != span.tid:
                return span
            span = up

    def descendants(self, span: Span):
        for c in self.children.get(span.sid, ()):
            yield c
            yield from self.descendants(c)

    def self_time(self, span: Span, a: int, b: int,
                  into: Dict[str, float]) -> int:
        """Adds to ``into``, by ``key``, the self time of ``span`` and of all
        below it inside ``[a, b)``; returns ``span``'s clipped length."""
        lo, hi = max(span.start, a), min(span.end, b)
        if hi <= lo:
            return 0
        below = sum(self.self_time(c, a, b, into)
                    for c in self.children.get(span.sid, ()))
        stage = self.key(span)
        if stage is not None:
            into[stage] += (hi - lo) - below
        return hi - lo


def rounds(records, t0_ns: int, t1_ns: int,
           key: Callable[[Span], Optional[str]] = stage_of) -> List[dict]:
    """Per round whose ``<filter>.invoke`` starts in ``[t0, t1)`` and whose
    predecessor's ``device_exec`` is in the ring: the gap (ns), its parts
    (by stage, or by whatever ``key`` names a span), the unnamed rest, and
    the ticket wait that ended inside it."""
    spans = complete_spans(records)
    chains = Chains(spans, key)
    done: Dict[int, int] = {}
    invokes: Dict[int, Span] = {}
    for s in spans:
        k = s.args.get("round")
        if k is None:
            continue
        if s.name == "device_exec":
            done[k] = max(done.get(k, 0), s.end)
        elif s.cat == "stage" and s.name.endswith(".invoke"):
            invokes[k] = s
    out = []
    for k in sorted(invokes):
        inv, before = invokes[k], invokes.get(k - 1)
        if not (t0_ns <= inv.start < t1_ns) or before is None \
                or k - 1 not in done:
            continue
        g0, g1 = done[k - 1], max(inv.end, done[k - 1])
        mine, theirs = chains.root(inv), chains.root(before)
        waits = [s for s in chains.descendants(mine)
                 if s.name.endswith(".ticket_wait") and g0 < s.end <= g1]
        # the hand-over: before it the round before holds the ticket, after
        # it this round's thread does
        hand = max((s.end for s in waits),
                   default=min(max(mine.start, g0), g1))
        parts: Dict[str, float] = collections.defaultdict(float)
        chains.self_time(theirs, g0, hand, parts)
        chains.self_time(mine, hand, g1, parts)
        gap = g1 - g0
        out.append({"round": k, "gap_ns": gap, "parts": dict(parts),
                    "unnamed": gap - sum(parts.values()),
                    "ticket_wait_ns": sum(s.end - s.start for s in waits),
                    "period_ns": inv.start - before.start})
    return out


def _summary(ctx) -> Optional[dict]:
    """The window's rounds, read once a run and kept on ``ctx``."""
    if hasattr(ctx, "program_spans"):
        return ctx.program_spans
    ctx.program_spans = None
    from nnstreamer_tpu.obs import spans

    stats = spans.recorder_stats()
    records = spans.snapshot()
    res = ctx.result
    per_round = rounds(records, res.t0_ns, res.t1_ns) \
        if not stats["dropped"] else []
    ctx.notes.update(span_records=len(records),
                     span_dropped=stats["dropped"])
    if per_round:
        ctx.notes.update(
            gap_rounds=len(per_round),
            gap_ms=[round(r["gap_ns"] / 1e6, 3) for r in per_round],
            round_period_ms_mean=sum(r["period_ns"] for r in per_round)
            / len(per_round) / 1e6)
        ctx.program_spans = {"rounds": per_round}
    return ctx.program_spans


def _mean_ms(ctx, stage: str) -> Optional[float]:
    summary = _summary(ctx)
    if summary is None:
        return None
    per_round = summary["rounds"]
    return sum(r["parts"].get(stage, 0.0)
               for r in per_round) / len(per_round) / 1e6


def host_gap_ms_mean(ctx) -> Optional[float]:
    """Mean over the window's rounds of the gap: mean x rounds is the time
    the device waited for the host (the gap is bimodal; a median would
    read one of its two lengths)."""
    summary = _summary(ctx)
    if summary is None:
        return None
    per_round = summary["rounds"]
    return sum(r["gap_ns"] for r in per_round) / len(per_round) / 1e6


def gap_return_ms_mean(ctx) -> Optional[float]:
    """Self time inside the gap of the return path (``RETURN``'s classes):
    unbatch, demux, decoders, sinks and their callbacks."""
    return _mean_ms(ctx, "return")


def gap_collect_ms_mean(ctx) -> Optional[float]:
    """Self time inside the gap of the forward path up to the filter
    (``COLLECT``'s classes): converters, muxes, batch, merge, transform."""
    return _mean_ms(ctx, "collect")


def gap_invoke_ms_mean(ctx) -> Optional[float]:
    """``<filter>.invoke`` inside the gap: upload + enqueue."""
    return _mean_ms(ctx, "invoke")


def gap_unnamed_pct(ctx) -> Optional[float]:
    """Share of the gaps' time that no span of the two chains covers:
    hand-over of the ticket, thread wake-up, the interpreter lock."""
    summary = _summary(ctx)
    if summary is None:
        return None
    total = sum(r["gap_ns"] for r in summary["rounds"])
    if total <= 0:
        return None
    return 100.0 * sum(r["unnamed"] for r in summary["rounds"]) / total


def ticket_wait_ms_p50(ctx) -> Optional[float]:
    """Median over the window's rounds of the ``ticket_wait`` that ended in
    the round's gap (0 for a round that did not wait): a collected batch
    queueing behind the round before it."""
    summary = _summary(ctx)
    if summary is None:
        return None
    return arithmetic.percentile(
        [r["ticket_wait_ns"] / 1e6 for r in summary["rounds"]], 50)


def compile_s(ctx) -> Optional[float]:
    """Sum of the ``nnstpu_compile_seconds`` histogram (``record_compile``:
    lower + compile of every executable a backend built, whatever the
    gate) when the reader runs."""
    del ctx
    from nnstreamer_tpu.obs.metrics import REGISTRY

    hist = REGISTRY.get("nnstpu_compile_seconds")
    if hist is None:
        return None
    total = sum(child.sum for _, child in hist.children())
    return total if total > 0 else None
