"""Per-frame span tracing: where did *this* frame spend its time.

PR 1's tracers answer "how slow is the pipeline on average"; this module
answers the per-frame question that actually drives tuning of the
dynbatch/mux/TPU-invoke hot path (the NNStreamer paper motivates
per-element pipeline profiling; the on-device inference literature shows
stage-level timelines are what exposes batching and transfer stalls):

- every frame gets a ``trace_id``/``span_id`` context stamped into
  ``Frame.meta`` at the source (a **mutable list**, so the shallow
  ``with_tensors`` meta copy shares it across payload swaps, queue hops,
  and thread boundaries — the GstMeta discipline);
- hook-bus callbacks (:class:`SpanTracer`) open/close spans at dispatch
  enter/exit, record queue push/pop occupancy, and mark every pad push
  as a potential cross-thread **flow**: a push records a flow-start, and
  whichever thread next touches the frame records the flow-finish —
  pairs that never left their thread are dropped at export time;
- coalescing elements (``tensor_dynbatch``, ``tensor_mux``,
  ``tensor_merge``) stamp the combined frame with a fresh span whose
  **parent links** name every constituent frame's span
  (:func:`merge_context`); an element that holds a frame until others
  arrive (a collect pad, a ``queue``, ``tensor_dynbatch``) writes the
  time it held it as a ``<element>.pad_wait`` record of cat ``wait``
  under the frame's own trace (:class:`PadWaits`);
- records land in a bounded per-thread ring (:class:`~.flight.
  FlightRecorder`) — zero cost when disabled (the ``enabled`` module
  flag is one load + truth test, same discipline as ``obs/hooks.py``,
  pinned by the micro-benchmark in ``tests/test_observability.py``);
- :func:`chrome_trace` renders a snapshot as Chrome trace-event JSON
  (loads in Perfetto / ``chrome://tracing``, one row per element
  thread, flow arrows following each frame across threads);
  :func:`waterfall` renders the same data as a plain-text per-frame
  timeline for terminals and bug reports.

Activation: ``NNSTPU_TRACERS=spans`` (conf-driven, like every tracer),
``pipeline.attach_tracer("spans")``, or :func:`enable` for non-pipeline
surfaces (``QueryServer`` without a local pipeline).  Ring capacity
comes from ``NNSTPU_FLIGHT_RECORDS`` / ini ``[obs] flight_records``.

Cross-process traces: ``elements/query.py`` carries ``(trace_id,
span_id)`` on the NNSQ wire (version-gated header flag), so
QueryServer-side spans attach to the client's trace and a client→server
→reply round trip decomposes end to end.
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .flight import DEFAULT_CAPACITY, FlightRecorder
from .tracers import Tracer

# Frame.meta keys.  The context value is a mutable list
# [trace_id, span_id, pending_flow_id, pending_flow_tid] shared by every
# shallow meta copy of the same logical frame.
META_KEY = "obs_span"
PARENTS_KEY = "obs_span_parents"

# record phases (Chrome trace-event letters where the mapping is 1:1)
PH_COMPLETE = "X"
PH_INSTANT = "i"
PH_COUNTER = "C"
PH_FLOW_START = "s"
PH_FLOW_END = "f"

# The fast-path gate for non-hook sites (query wire, sched, serving):
# one module-attribute load + truth test when span tracing is off.
enabled = False

_lock = threading.Lock()
_active = 0        # SpanTracer refcount
_epoch = 0         # bumped each time recording goes from off to on
_manual = False    # explicit enable() (serving surfaces without a pipeline)

_ids = itertools.count(1)
# trace ids start at a per-process random offset so two processes'
# traces (pipeline client + query server) stay distinct in a merged view
_trace_ids = itertools.count(
    (int.from_bytes(os.urandom(4), "little") << 20) | 1)
_flow_ids = itertools.count(1)

_recorder = FlightRecorder()
_tls = threading.local()

now_ns = time.perf_counter_ns  # the one clock (see obs/hooks.py)


def _tid() -> str:
    """The calling thread's logical identity: its name as it was at its
    first record (kept on the thread: ``current_thread().name`` costs a
    microsecond, three times a dispatch), or :func:`set_tid`'s override."""
    try:
        return _tls.tid
    except AttributeError:
        tid = _tls.tid = threading.current_thread().name
        return tid


def set_tid(name: Optional[str]) -> Optional[str]:
    """Override the calling thread's *logical* identity for span records
    (``None`` restores the OS thread name); returns the previous
    override so callers can nest.  The dispatcher lanes runtime
    (:mod:`nnstreamer_tpu.graph.lanes`) sets the executing task's name
    (``src:<name>``, ``queue:<name>``) around each slice, so records,
    flow pairing, and Perfetto rows from a lane run are byte-identical
    to the thread-per-element mode they replaced."""
    prev = getattr(_tls, "tid_override", None)
    _tls.tid_override = name
    tid = _tls.tid = name if name is not None \
        else threading.current_thread().name
    if getattr(_tls, "stacks_epoch", None) == _epoch:
        _tls.stack = _tls.stacks.setdefault(tid, [])
    return prev


def _rec(ph, ts, dur, name, cat, trace_id, span_id, parent_id, args) -> None:
    _recorder.append((ph, ts, dur, _tid(), name, cat,
                      trace_id, span_id, parent_id, args))


# -- activation --------------------------------------------------------------

def configured_flight_records() -> int:
    """Ring capacity per thread: ``NNSTPU_FLIGHT_RECORDS`` (short
    spelling) over ini ``[obs] flight_records`` over the default."""
    val = os.environ.get("NNSTPU_FLIGHT_RECORDS")
    if val is None:
        from ..conf import conf

        val = conf.get("obs", "flight_records", "")
    try:
        cap = int(val) if val not in (None, "") else DEFAULT_CAPACITY
    except ValueError:
        return DEFAULT_CAPACITY
    return cap if cap > 0 else DEFAULT_CAPACITY


def _activate(capacity: Optional[int] = None) -> None:
    global enabled, _active, _epoch, _recorder
    with _lock:
        if _active == 0 and not _manual and capacity \
                and capacity != _recorder.capacity:
            _recorder = FlightRecorder(capacity)
        if _active == 0:
            _epoch += 1
        _active += 1
        enabled = True


def _deactivate() -> None:
    global enabled, _active
    with _lock:
        _active = max(0, _active - 1)
        if _active == 0 and not _manual:
            enabled = False


def enable(capacity: Optional[int] = None) -> None:
    """Turn span recording on without a pipeline tracer (serving-side
    processes: a ``QueryServer`` that should attach to client traces)."""
    global enabled, _manual, _recorder
    with _lock:
        if _active == 0 and not _manual and capacity \
                and capacity != _recorder.capacity:
            _recorder = FlightRecorder(capacity)
        _manual = True
        enabled = True


def disable() -> None:
    global enabled, _manual
    with _lock:
        _manual = False
        if _active == 0:
            enabled = False


def reset() -> None:
    """Hard reset: disabled, fresh empty recorder (test isolation)."""
    global enabled, _manual, _active, _recorder
    with _lock:
        _active = 0
        _manual = False
        enabled = False
        _recorder = FlightRecorder(_recorder.capacity)


def snapshot() -> List[tuple]:
    """Drain the flight recorder: every retained record, time-ordered."""
    return _recorder.snapshot()


def clear() -> None:
    _recorder.clear()


def recorder_stats() -> dict:
    return _recorder.stats()


def records_for_trace(trace_id: int,
                      records: Optional[List[tuple]] = None) -> List[tuple]:
    """Every retained record stamped with ``trace_id`` (complete spans,
    instants, flow marks), time-ordered — the per-trace slice the tail-
    forensics engine (:mod:`.forensics`) attributes and captures."""
    if records is None:
        records = snapshot()
    return [r for r in records if r[6] == trace_id]


# -- trace context -----------------------------------------------------------

def new_trace_id() -> int:
    return next(_trace_ids)


def new_context() -> list:
    """Fresh [trace_id, span_id, flow_id, flow_tid] context (frame root)."""
    return [next(_trace_ids), next(_ids), 0, None]


def context_of(item) -> Optional[list]:
    meta = getattr(item, "meta", None)
    return meta.get(META_KEY) if meta is not None else None


def _consume_flow(ctx: list, ts: int) -> None:
    """Close the frame's pending flow here.  Only a hop that actually
    changed threads becomes a flow-finish record — same-thread pushes
    leave an unpaired start that export drops."""
    fid = ctx[2]
    if fid:
        tid = _tid()
        if ctx[3] != tid:
            _recorder.append((PH_FLOW_END, ts, 0, tid, "frame", "dataflow",
                              ctx[0], fid, 0, None))
        ctx[2] = 0
        ctx[3] = None


def merge_context(frames: Iterable, meta: dict, name: str, **args) -> None:
    """Stamp a coalesced frame (dynbatch batch, mux collection round) with
    a fresh span context carrying **parent links** to every constituent
    frame's span.  Constituents' pending cross-thread flows terminate at
    the coalesce point, so Perfetto draws each source stream's arrow into
    the batch.  ``args`` ride on the ``coalesce`` record beside its
    ``parents`` (a collector's ``ticket``)."""
    if not enabled:
        return
    ts = now_ns()
    parents: List[Tuple[int, int]] = []
    trace_id = 0
    for f in frames:
        ctx = context_of(f)
        if ctx is None:
            continue
        if not trace_id:
            trace_id = ctx[0]
        parents.append((ctx[0], ctx[1]))
        _consume_flow(ctx, ts)
    if not parents:
        return
    sid = next(_ids)
    meta[META_KEY] = [trace_id, sid, 0, None]
    meta[PARENTS_KEY] = tuple(parents)
    args["parents"] = [f"{t:x}/{s:x}" for t, s in parents]
    _rec(PH_INSTANT, ts, 0, name, "coalesce", trace_id, sid, parents[0][1],
         args)


def carry_context(frame, cuts: Iterable) -> None:
    """Hand ``frame``'s trace context to the frames an element cut out of
    it (``tensor_split``, ``tensor_demux``): the same trace and span, a
    flow state of their own, so the way back stays in the round's trace.
    Nothing where ``frame`` carries none."""
    ctx = frame.meta.get(META_KEY)
    if ctx is not None:
        for cut in cuts:
            cut.meta[META_KEY] = [ctx[0], ctx[1], 0, None]


# -- the wait of a frame an element holds ------------------------------------

class PadWaits:
    """Arrival stamps of the frames an element holds on one pad (a collect
    pad's deque, a queue's or a dynbatch's buffer), oldest first.  Frames
    leave such a buffer in arrival order, so :meth:`left` drops the
    stamps in front of the one it finds: they are of frames a sync policy
    or a leaky queue dropped.  Callers sit behind the ``enabled`` gate."""

    __slots__ = ("_held",)

    def __init__(self):
        self._held: collections.deque = collections.deque()

    def __len__(self) -> int:
        return len(self._held)

    def clear(self) -> None:
        self._held.clear()

    def arrived(self, item) -> None:
        self._held.append((id(item), now_ns()))

    def left(self, item, element: str, pad: str,
             t1_ns: Optional[int] = None, **args) -> None:
        """``item`` leaves the buffer now (``t1_ns``): one
        ``<element>.pad_wait`` record, cat ``wait``, from its arrival on
        ``pad`` until the round that took it (a collector's booking, a
        queue's pop, a dynbatch's flush), under the frame's own trace and
        span.  It starts on one thread and ends on another, so it is no
        dispatch or stage span and no annotation.  Nothing for an item
        that arrived unstamped (tracing was off) or is contributed again
        (``basepad``'s last frame)."""
        held, key = self._held, id(item)
        # newest first: a dropped frame's id may be a live frame's by now
        for i in range(len(held) - 1, -1, -1):
            if held[i][0] == key:
                t0_ns = held[i][1]
                for _ in range(i + 1):
                    held.popleft()
                break
        else:
            return
        if not enabled:
            return
        ctx = item.meta.get(META_KEY)
        trace, span = (ctx[0], ctx[1]) if ctx is not None else (0, 0)
        args["pad"] = pad
        # straight into the ring: a round writes one of these a frame, on
        # the thread that carries it
        _recorder.append((
            PH_COMPLETE, t0_ns, (t1_ns or now_ns()) - t0_ns, _tid(),
            element + ".pad_wait", "wait", trace, next(_ids), span, args))


# -- explicit spans (query wire, sched, serving) -----------------------------

def current() -> Optional[Tuple[int, int]]:
    """(trace_id, span_id) the calling thread is currently inside, if any."""
    return getattr(_tls, "cur", None)


def span_begin(trace_id: int = 0, parent_id: int = 0) -> tuple:
    """Open an explicit span and make it the thread's current context
    (children recorded via :func:`record_span` nest under it).  Returns
    an opaque token for :func:`span_end`."""
    sid = next(_ids)
    prev = getattr(_tls, "cur", None)
    _tls.cur = (trace_id, sid)
    return (sid, now_ns(), trace_id, parent_id, prev)


def span_end(token: tuple, name: str, cat: str = "span",
             args: Optional[dict] = None) -> int:
    sid, t0, trace_id, parent_id, prev = token
    _rec(PH_COMPLETE, t0, now_ns() - t0, name, cat,
         trace_id, sid, parent_id, args)
    _tls.cur = prev
    return sid


def record_span(name: str, t0_ns: int, dur_ns: int, cat: str = "span",
                trace: Optional[Tuple[int, int]] = None,
                args: Optional[dict] = None) -> int:
    """Record a completed span.  ``trace`` is (trace_id, parent_span_id);
    when omitted the thread's current context (an enclosing
    :func:`span_begin`) provides it."""
    if trace is None:
        trace = current() or (0, 0)
    sid = next(_ids)
    _rec(PH_COMPLETE, t0_ns, dur_ns, name, cat, trace[0], sid, trace[1], args)
    return sid


def record_instant(name: str, cat: str = "span",
                   trace: Optional[Tuple[int, int]] = None,
                   args: Optional[dict] = None) -> None:
    if trace is None:
        trace = current() or (0, 0)
    _rec(PH_INSTANT, now_ns(), 0, name, cat, trace[0], next(_ids), trace[1],
         args)


# -- stage spans (inside a traced dispatch) ----------------------------------

# jax.profiler.TraceAnnotation, bound when the first SpanTracer installs
# (importing this package does not import jax); a no-op outside a
# jax.profiler session
_TraceAnnotation = None

# element class -> the one args dict its dispatch spans share
_ELEMENT_ARGS: Dict[type, dict] = {}


def _element_args(cls: type) -> dict:
    args = _ELEMENT_ARGS.get(cls)
    if args is None:
        args = _ELEMENT_ARGS[cls] = {"element": cls.__name__}
    return args


def _annotate(name: str, **kwargs):
    """An entered ``jax.profiler.TraceAnnotation``: the span on the xplane's
    host plane, beside ``XLA Ops``."""
    ann = _TraceAnnotation(name, **kwargs)
    ann.__enter__()
    return ann


def _dispatch_stack() -> list:
    """The calling thread's open dispatch spans, innermost last: the
    stack of its *logical* tid, not of the OS thread — a lane running a
    helped drain slice inside a producer's chain must not nest the
    drained dispatches under the producer's spans (each task keeps the
    stack its dedicated thread would have had; :func:`set_tid` swaps)."""
    try:
        if _tls.stacks_epoch == _epoch:
            return _tls.stack
    except AttributeError:
        pass
    # a new recording: what a dispatch cut off by the last one's stop
    # left open on this thread is not this one's parent
    _tls.stacks = {}
    _tls.stacks_epoch = _epoch
    stack = _tls.stack = _tls.stacks.setdefault(_tid(), [])
    return stack


def stage_begin(name: str, **kwargs) -> Optional[tuple]:
    """Open a ``stage`` span inside the dispatch span the calling thread
    is in: work an element does that its dispatch span does not separate
    (a ticket wait, the backend invoke).  Returns the token for
    :func:`stage_end`, or None where no traced dispatch is open (span
    tracing off, or another pipeline's) — callers sit behind the hook
    bus's gate.  ``kwargs`` become the span's args and the arguments of
    its ``nns/<name>`` annotation on the profiler's clock; the span's own
    start rides along as ``t0_ns``, so that one annotation pairs the
    ring's clock with the xplane's."""
    if not enabled:
        return None
    stack = _dispatch_stack()
    if not stack:
        return None
    t0 = now_ns()
    return (name, t0, stack[-1], kwargs,
            _annotate("nns/" + name, t0_ns=t0, **kwargs))


def stage_end(token: Optional[tuple]) -> None:
    """Close a :func:`stage_begin` span (None: nothing was opened)."""
    if token is None:
        return
    name, t0, parent, kwargs, ann = token
    dur = now_ns() - t0
    ann.__exit__(None, None, None)
    ctx = parent[2]
    _rec(PH_COMPLETE, t0, dur, name, "stage", ctx[0] if ctx else 0,
         next(_ids), parent[0], kwargs or None)


# -- the tracer --------------------------------------------------------------

class SpanTracer(Tracer):
    """Hook-bus tracer feeding the flight recorder.

    Dispatch enter/exit become complete ("X") spans per element — nested
    naturally, because a pad push runs the downstream chain inside the
    upstream dispatch.  A per-thread stack supplies parent span ids; the
    frame's stamped context supplies the trace id.  Queue push/pop become
    counter tracks, queue drops and source pushes instants, and every pad
    push opens a flow that closes on whichever thread touches the frame
    next (``flows=False`` leaves the flows out: the lane a pipeline
    starts by itself when the hook bus has a listener, one complete span
    a dispatch and nothing else).  Each dispatch span is also a
    ``jax.profiler.TraceAnnotation`` ``nns/<element>``.
    """

    name = "spans"

    def __init__(self, registry=None, capacity: Optional[int] = None,
                 flows: bool = True):
        super().__init__(registry)
        self._capacity = capacity
        self._flows = flows

    def _install(self) -> None:
        global _TraceAnnotation
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation

            _TraceAnnotation = TraceAnnotation
        cap = self._capacity if self._capacity is not None \
            else configured_flight_records()
        _activate(cap)
        self._connect("source_push", self._on_source_push)
        if self._flows:
            self._connect("pad_push", self._on_pad_push)
        self._connect("dispatch_enter", self._on_dispatch_enter)
        self._connect("dispatch_exit", self._on_dispatch_exit)
        self._connect("queue_push", self._on_queue_push)
        self._connect("queue_pop", self._on_queue_pop)
        self._connect("queue_drop", self._on_queue_drop)
        self._connect("error", self._on_error)

    def stop(self) -> None:
        was_active = bool(self._conns)
        super().stop()
        if was_active:
            _deactivate()

    # -- hook callbacks ------------------------------------------------------

    def _on_source_push(self, pipeline, node, frame) -> None:
        if pipeline is not self._pipeline:
            return
        ctx = frame.meta.get(META_KEY)
        if ctx is None:
            ctx = frame.meta[META_KEY] = new_context()
        _rec(PH_INSTANT, now_ns(), 0, f"{node.name}.push", "source",
             ctx[0], ctx[1], 0, None)

    def _on_pad_push(self, pad, item) -> None:
        if pad.node.pipeline is not self._pipeline:
            return
        ctx = context_of(item)
        if ctx is None:
            return
        ts = now_ns()
        _consume_flow(ctx, ts)
        fid = next(_flow_ids)
        ctx[2] = fid
        ctx[3] = _tid()
        _recorder.append((PH_FLOW_START, ts, 0, ctx[3], "frame", "dataflow",
                          ctx[0], fid, 0, None))

    def _on_dispatch_enter(self, node, pad, item, t0) -> None:
        if node.pipeline is not self._pipeline:
            return
        meta = getattr(item, "meta", None)
        ctx = meta.get(META_KEY) if meta is not None else None
        if ctx is not None and ctx[2]:
            _consume_flow(ctx, t0)
        ann = _TraceAnnotation("nns/" + node.name)
        ann.__enter__()
        _dispatch_stack().append((next(_ids), t0, ctx, ann))

    def _on_dispatch_exit(self, node, pad, item, dur_ns) -> None:
        if node.pipeline is not self._pipeline:
            return
        stack = _dispatch_stack()
        if not stack:
            return  # tracer attached mid-dispatch: no matching enter
        sid, t0, ctx, ann = stack.pop()
        ann.__exit__(None, None, None)
        if stack:
            parent = stack[-1][0]
        else:
            parent = ctx[1] if ctx else 0
        # straight into the ring: this runs twice a dispatch on every
        # streaming thread, under one interpreter lock
        _recorder.append((PH_COMPLETE, t0, dur_ns, _tid(), node.name,
                          "dispatch", ctx[0] if ctx else 0, sid, parent,
                          _element_args(type(node))))

    def _on_queue_push(self, node, depth) -> None:
        if node.pipeline is self._pipeline:
            _rec(PH_COUNTER, now_ns(), 0, f"{node.name} depth", "queue",
                 0, 0, 0, depth)

    _on_queue_pop = _on_queue_push

    def _on_queue_drop(self, node, reason) -> None:
        if node.pipeline is self._pipeline:
            _rec(PH_INSTANT, now_ns(), 0, f"{node.name} drop", "queue",
                 0, next(_ids), 0, {"reason": reason})

    def _on_error(self, pipeline, node, exc) -> None:
        if pipeline is self._pipeline:
            _rec(PH_INSTANT, now_ns(), 0, "pipeline_error", "error",
                 0, next(_ids), 0,
                 {"node": node.name if node else "?", "error": repr(exc)})

    def summary(self) -> dict:
        return recorder_stats()


# -- exporters ---------------------------------------------------------------

def _flow_pairs(records) -> Dict[int, Tuple[tuple, tuple]]:
    """Flow ids whose start AND finish were retained on different threads."""
    starts: Dict[int, tuple] = {}
    ends: Dict[int, tuple] = {}
    for r in records:
        if r[0] == PH_FLOW_START:
            starts[r[7]] = r
        elif r[0] == PH_FLOW_END:
            ends[r[7]] = r
    return {fid: (s, ends[fid]) for fid, s in starts.items()
            if fid in ends and s[3] != ends[fid][3]}


def chrome_trace(records: Optional[List[tuple]] = None, pid: int = 0,
                 process_name: str = "nnstreamer_tpu") -> dict:
    """A snapshot as a Chrome trace-event JSON object (the ``traceEvents``
    array format): load the dumped file in Perfetto (ui.perfetto.dev) or
    ``chrome://tracing``.  One tid row per recorded thread, "X" spans
    with µs ts/dur, counter tracks for queue depth, and "s"/"f" flow
    arrows for every frame hop that crossed threads."""
    if records is None:
        records = snapshot()
    events: List[dict] = [{
        "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
        "args": {"name": process_name},
    }]
    tids: Dict[str, int] = {}

    def tid_for(name: str) -> int:
        t = tids.get(name)
        if t is None:
            t = tids[name] = len(tids) + 1
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": t, "args": {"name": name}})
        return t

    flows = _flow_pairs(records)
    for ph, ts, dur, tname, name, cat, trace_id, sid, parent, args in records:
        base = {"pid": pid, "tid": tid_for(tname), "ts": ts / 1e3,
                "name": name, "cat": cat}
        if ph == PH_COMPLETE:
            ev_args = {"trace_id": f"{trace_id:x}", "span_id": f"{sid:x}",
                       "parent_id": f"{parent:x}"}
            if args:
                ev_args.update(args)
            base.update(ph="X", dur=dur / 1e3, args=ev_args)
        elif ph == PH_INSTANT:
            ev_args = {"trace_id": f"{trace_id:x}"}
            if args:
                ev_args.update(args)
            base.update(ph="i", s="t", args=ev_args)
        elif ph == PH_COUNTER:
            base.update(ph="C", args={"depth": args})
        elif ph in (PH_FLOW_START, PH_FLOW_END):
            if sid not in flows:
                continue  # never crossed a thread (or half evicted)
            base.update(ph=ph, id=sid)
            if ph == PH_FLOW_END:
                base["bp"] = "e"
        else:  # pragma: no cover — unknown phase from a future producer
            continue
        events.append(base)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def waterfall(records: Optional[List[tuple]] = None, limit: int = 16) -> str:
    """Plain-text per-frame waterfall: one block per trace id, spans and
    instants indented by start time relative to the trace's first record
    (the terminal-friendly view of the same flight snapshot)."""
    if records is None:
        records = snapshot()
    by_trace: Dict[int, List[tuple]] = {}
    for r in records:
        if r[0] in (PH_COMPLETE, PH_INSTANT) and r[6]:
            by_trace.setdefault(r[6], []).append(r)
    lines: List[str] = []
    traces = sorted(by_trace.items(), key=lambda kv: kv[1][0][1])
    for trace_id, recs in traces[:limit]:
        t0 = min(r[1] for r in recs)
        span = max(r[1] + r[2] for r in recs) - t0
        lines.append(f"trace {trace_id:x}  ({len(recs)} records, "
                     f"{span / 1e6:.3f} ms)")
        for ph, ts, dur, tname, name, cat, _, _, _, args in recs:
            off = (ts - t0) / 1e6
            dur_s = f"{dur / 1e6:8.3f}ms" if ph == PH_COMPLETE else "        -"
            extra = ""
            if args and "parents" in args:
                extra = f"  <- {len(args['parents'])} parent span(s)"
            elif cat == "wait" and args:
                extra = f"  pad {args.get('pad')}"
            lines.append(f"  +{off:9.3f}ms {dur_s}  {name:<24} "
                         f"{cat:<9} [{tname}]{extra}")
    if len(traces) > limit:
        lines.append(f"... {len(traces) - limit} more trace(s) truncated")
    return "\n".join(lines)


# self-registration with the tracer registry (obs/__init__ imports this
# module, so ``NNSTPU_TRACERS=spans`` / attach_tracer("spans") always
# resolve)
from .tracers import TRACERS  # noqa: E402

TRACERS[SpanTracer.name] = SpanTracer
