"""Shared N-way fan-in collection with time synchronization.

The analog of ``GstCollectPads`` + the reference's tensor time-sync engine
(``tensor_common.h:59-107``, impl ``tensor_common.c:1150-1266+``) used by
both ``tensor_mux`` and ``tensor_merge``.  Three policies, matching
``tensor_time_sync_mode``:

- ``nosync``  — pop whatever is at each pad's head.
- ``slowest`` — sync point is the most-lagging pad's head timestamp; each
  pad contributes its buffer closest to that point (old buffers discarded).
- ``basepad`` — follow pad K's timestamps within a tolerance; option string
  ``"K:duration_ns"`` like the reference's ``sync-option``.

Arrival is serialized by the base ``Node`` lock; a collection round fires
whenever every non-EOS pad has a candidate buffer.

Hot-path discipline: queue bookkeeping and round selection happen under the
node lock, but **emission runs outside it** (ticket-ordered, so output order
still matches collection order).  The downstream chain — batch assembly,
filter dispatch — therefore never blocks the other source threads from
delivering their next frame (emission under the lock serializes every
source behind the device dispatch).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..buffer import Event, Frame, NONE_TS, is_valid_ts
from ..graph.node import Node, Pad
from ..obs import hooks as _hooks
from ..obs import spans as _spans


class CollectNode(Node):
    """Base for mux/merge: collects one frame per linked sink pad, time-
    synchronized, then calls :meth:`combine`."""

    REQUEST_SINK_PADS = True

    def __init__(
        self,
        name: Optional[str] = None,
        sync_mode: str = "slowest",
        sync_option: str = "",
    ):
        super().__init__(name)
        self.add_src_pad("src")
        self.sync_mode = str(sync_mode)
        if self.sync_mode not in ("nosync", "slowest", "basepad"):
            raise ValueError(f"unknown sync-mode {self.sync_mode!r}")
        self.sync_option = str(sync_option)
        self._base_pad_idx = 0
        self._base_tolerance = NONE_TS
        if self.sync_mode == "basepad" and self.sync_option:
            parts = self.sync_option.split(":")
            self._base_pad_idx = int(parts[0])
            if len(parts) > 1:
                self._base_tolerance = int(parts[1])
        self._queues: Dict[str, collections.deque] = {}
        # per-pad most-recent contributed/popped frame (the reference's
        # pad->buffer, tensor_common.c:1270+): basepad re-contributes it
        # when a pad's head is outside tolerance, keeping pad-count stable
        self._last: Dict[str, Frame] = {}
        # per-pad arrival stamps of the queued frames, kept while span
        # tracing is on: what a frame's ``pad_wait`` record is made of
        self._waits: Dict[str, _spans.PadWaits] = {}
        self._finished = False
        # ordered emission outside the node lock: tickets are taken under
        # the lock, honored under _emit_cv
        self._emit_cv = threading.Condition()
        self._ticket = 0
        self._emit_next = 0
        self._emitting = 0  # the ticket whose rounds combine() is called for

    # -- collection ---------------------------------------------------------

    def _pad_order(self) -> List[str]:
        return sorted(self._queues, key=lambda n: (len(n), n))  # sink_0 < sink_1 < sink_10

    def _linked_sinks(self) -> List[Pad]:
        return [p for p in self.sink_pads.values() if p.peer is not None]

    def _dispatch(self, pad: Pad, item) -> None:
        """The tracer hook points of :meth:`Node._dispatch` around this
        element's own dispatch (one flag test with no tracer attached)."""
        if _hooks.enabled:
            t0 = time.perf_counter_ns()
            _hooks.emit("dispatch_enter", self, pad, item, t0)
            try:
                self._dispatch_ordered(pad, item)
            finally:
                _hooks.emit("dispatch_exit", self, pad, item,
                            time.perf_counter_ns() - t0)
            return
        self._dispatch_ordered(pad, item)

    def _dispatch_ordered(self, pad: Pad, item) -> None:
        """Bookkeeping under the lock; emission outside it, ticket-ordered.

        Tickets are only booked when there is something to push downstream
        (rounds, EOS, caps) — an arrival that completes no round returns
        immediately, so source threads never queue up behind the downstream
        chain.  Caps/other events *defer all processing* to their ticket
        turn: spec mutation must not race an earlier ticket still pushing
        old-shape frames through the src pads.
        """
        outs: List = []
        caps_item = None
        finish = False
        with self._lock:
            if isinstance(item, Event):
                if item.kind == "eos":
                    pad.eos = True
                    # An EOS pad may unblock a pending collection round (a
                    # laggard waiting for newer data) before ending the stream
                    if not self._finished:
                        outs, finish = self._collect_rounds()
                    if not finish and all(
                        p.eos for p in self._linked_sinks()
                    ) and not self._finished:
                        finish = True
                    if finish:
                        self._finished = True
                else:
                    caps_item = item  # processed at our ticket turn
            else:
                if self._finished:
                    return  # stream already ended (a pad ran dry)
                self._queues.setdefault(pad.name, collections.deque()).append(item)
                if _spans.enabled:
                    waits = self._waits.get(pad.name)
                    if waits is None:
                        waits = self._waits[pad.name] = _spans.PadWaits()
                    waits.arrived(item)
                outs, finish = self._collect_rounds()
                if finish:
                    self._finished = True
            if not outs and not finish and caps_item is None:
                return  # nothing to emit: don't serialize behind the chain
            ticket = self._ticket
            self._ticket += 1
            if self._waits:
                # the booking thread writes what every frame of its rounds
                # waited, from its arrival on its pad until this booking;
                # a frame ``basepad`` contributes again has no stamp left
                booked = time.perf_counter_ns()
                for frames in outs:
                    for name, frame in frames.items():
                        waits = self._waits.get(name)
                        if waits:
                            waits.left(frame, self.name, name, booked,
                                       ticket=ticket)
        with self._emit_cv:
            if self._emit_next != ticket:
                # a collected round queues here behind the round before
                # it: a stage span of its own, so that the dispatch span
                # does not read the wait as this element's work
                tok = _spans.stage_begin(self.name + ".ticket_wait",
                                         ticket=ticket) \
                    if _hooks.enabled else None
                while self._emit_next != ticket:
                    self._emit_cv.wait()
                _spans.stage_end(tok)
        try:
            if caps_item is not None:
                if caps_item.kind == "caps":
                    # re-run the commit phase with ALL pad specs so
                    # downstream sees the new COMBINED spec — never the
                    # pad's verbatim.  Earlier tickets have drained, later
                    # ones wait: no frame is mid-push on our src pads.
                    with self._lock:
                        caps_events = self._recompute_caps(pad, caps_item.payload)
                    for spad, event in caps_events:
                        spad.peer.node._dispatch(spad.peer, event)
                else:
                    # the overridable hook (default: forward downstream)
                    self.on_event(pad, caps_item)
            self._emitting = ticket
            for frames in outs:
                out = self.combine(frames)
                if out is not None:
                    self._emit(out)
            if finish:
                for spad in self.src_pads.values():
                    spad.push(Event.eos())
                if self.pipeline is not None:
                    self.pipeline._node_eos(self)  # no-op unless we are a leaf
        finally:
            with self._emit_cv:
                self._emit_next += 1
                self._emit_cv.notify_all()

    def coalesce(self, frames: Dict[str, Frame], meta: dict) -> None:
        """Stamp ``meta`` (the round's output frame's) with a fresh span,
        parent-linked to every contributed frame's, under the ticket
        whose ``pad_wait`` records say how long each waited.  For
        ``combine()``, behind the ``_spans.enabled`` gate."""
        _spans.merge_context(frames.values(), meta, self.name,
                             ticket=self._emitting)

    def _ready(self) -> bool:
        for pad in self._linked_sinks():
            if not self._queues.get(pad.name):
                return False
        return True

    def _exhausted(self) -> bool:
        """A pad at EOS with an empty queue can never complete another set —
        the muxed stream ends (gst_tensor_mux_collected's NULL-buffer EOS)."""
        return any(
            pad.eos and not self._queues.get(pad.name)
            for pad in self._linked_sinks()
        )

    def _active_queues(self) -> List[Tuple[str, collections.deque]]:
        out = []
        for name in self._pad_order():
            q = self._queues[name]
            if q:
                out.append((name, q))
        return out

    def _sync_point(self, active) -> int:
        if self.sync_mode == "basepad":
            order = self._pad_order()
            if self._base_pad_idx < len(order):
                base_name = order[self._base_pad_idx]
                q = self._queues.get(base_name)
                if q:
                    return q[0].pts
            return NONE_TS
        # slowest: the max of head timestamps — wait for the laggard
        # (gst_tensor_time_sync_get_current_time, tensor_common.c).
        ts = NONE_TS
        for _, q in active:
            if is_valid_ts(q[0].pts):
                ts = max(ts, q[0].pts)
        return ts

    def _collect_rounds(self) -> Tuple[List, bool]:
        """Run collection rounds until no complete set remains.  Returns
        (synchronized pad→frame sets, stream-finished flag); combines and
        emits nothing itself — the caller runs combine() and pushes outside
        the node lock."""
        outs: List = []
        while True:
            if self._exhausted():
                return outs, True
            if not self._ready():
                return outs, False
            active = self._active_queues()
            if not active:
                return outs, False
            if self.sync_mode == "nosync":
                chosen = [(name, q.popleft()) for name, q in active]
            else:
                base_ts = self._sync_point(active)
                if base_ts == NONE_TS:
                    chosen = [(name, q.popleft()) for name, q in active]
                elif self.sync_mode == "basepad":
                    result = self._collect_basepad(active, base_ts)
                    if result is None:
                        return outs, False  # need newer data on some pad
                    if result == "retry":
                        continue  # stale head dropped: re-evaluate
                    chosen = result
                else:
                    chosen = []
                    need_buffer = False
                    for name, q in active:
                        pad = self.sink_pads[name]
                        # advance to the buffer closest to base_ts
                        while len(q) >= 2 and self._closer(q[1].pts, q[0].pts, base_ts):
                            q.popleft()
                        head = q[0]
                        if (
                            len(q) == 1
                            and not pad.eos
                            and is_valid_ts(head.pts)
                            and self._ends_before(head, base_ts)
                        ):
                            need_buffer = True  # laggard: wait for newer data
                            break
                        chosen.append((name, head))
                    if need_buffer:
                        return outs, False
                    for name, _ in chosen:
                        self._queues[name].popleft()
            if not chosen:
                return outs, False
            # defer combine() (concat/stack — the expensive part) to the
            # caller's ticket turn outside the lock
            outs.append(dict(chosen))

    def _collect_basepad(self, active, base_ts: int):
        """One basepad collection round (tensor_common.c:1281-1390 semantics):

        - a head strictly BEFORE the sync point is stale — pop it into the
          pad's ``last`` slot and retry/wait (the reference's need_buffer);
        - a head outside the tolerance window contributes the pad's LAST
          frame instead (head stays queued) — the pad still participates, so
          a combine round never has fewer pads than linked;
        - tolerance = min(option duration, the base pad's own inter-frame
          gap - 1) like the reference's dynamic ``base``.

        Returns the chosen list, "retry" (state changed, re-evaluate), or
        None (wait for newer data).
        """
        order = self._pad_order()
        base_name = (
            order[self._base_pad_idx] if self._base_pad_idx < len(order) else None
        )
        tol: Optional[int] = (
            self._base_tolerance if self._base_tolerance != NONE_TS else None
        )
        last_base = self._last.get(base_name) if base_name else None
        if last_base is not None:
            bq = self._queues.get(base_name)
            if bq and is_valid_ts(bq[0].pts) and is_valid_ts(last_base.pts):
                gap = abs(bq[0].pts - last_base.pts) - 1
                tol = gap if tol is None else min(tol, gap)
        chosen = []
        for name, q in active:
            pad = self.sink_pads[name]
            head = q[0]
            if (
                name != base_name
                and is_valid_ts(head.pts)
                and head.pts < base_ts
            ):
                self._last[name] = q.popleft()
                if q or pad.eos:
                    return "retry"  # newer head available / stream ending
                return None  # laggard: wait for newer data
            outside = (
                tol is not None
                and is_valid_ts(head.pts)
                and abs(head.pts - base_ts) > tol
            )
            if outside and name in self._last:
                chosen.append((name, self._last[name]))  # head stays queued
            else:
                self._last[name] = q.popleft()
                chosen.append((name, self._last[name]))
        return chosen

    @staticmethod
    def _closer(candidate_ts: int, current_ts: int, base_ts: int) -> bool:
        if not is_valid_ts(candidate_ts):
            return False
        if not is_valid_ts(current_ts):
            return True
        return abs(candidate_ts - base_ts) <= abs(current_ts - base_ts)

    @staticmethod
    def _ends_before(frame: Frame, ts: int) -> bool:
        end = frame.end_ts
        ref = end if is_valid_ts(end) else frame.pts
        return ref < ts

    def start(self) -> None:
        super().start()
        self._finished = False
        self._queues.clear()
        self._last.clear()
        self._waits.clear()
        with self._emit_cv:
            self._ticket = 0
            self._emit_next = 0

    # -- to be provided by subclasses ---------------------------------------

    def combine(self, frames: Dict[str, Frame]):
        """Merge one synchronized set (pad name → frame) into output frames."""
        raise NotImplementedError

    @staticmethod
    def output_timing(frames: Dict[str, Frame]) -> Tuple[int, int]:
        pts = min(
            (f.pts for f in frames.values() if is_valid_ts(f.pts)), default=NONE_TS
        )
        dur = min(
            (f.duration for f in frames.values() if is_valid_ts(f.duration)),
            default=NONE_TS,
        )
        return pts, dur
