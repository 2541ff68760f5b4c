"""``queue``: the thread-decoupling element.

In the reference, GStreamer ``queue`` elements give each pipeline segment its
own streaming thread — the core of its single-node pipeline parallelism
(``README.md:41-44``: converter/filter run while the sink consumes).  This
node reproduces that: ``_dispatch`` enqueues into a bounded buffer (returning
immediately to the upstream thread, or blocking when full = backpressure),
and a dedicated worker thread drains the buffer into the downstream chain.

The buffer itself is the native C++ frame queue
(:mod:`nnstreamer_tpu.native.queue`) when the runtime library is available —
blocking waits then happen outside the GIL — with a pure-Python twin as
fallback.  Leak modes mirror GStreamer's: ``no`` (backpressure),
``downstream`` (drop oldest queued frame), ``upstream`` (drop newest
incoming frame); in-band events are never dropped.
"""

from __future__ import annotations

import threading
from typing import List, Optional

from .. import faults as _faults
from ..buffer import Event
from ..graph.node import Node, Pad
from ..graph.registry import register_element
from ..native import DROPPED_INCOMING, OK, OK_DROPPED_OLDEST, SHUTDOWN
from ..native.queue import make_frame_queue
from ..obs import hooks as _hooks
from ..obs import spans as _spans

_POLL_MS = 100  # wake periodically so shutdown is never missed


@register_element("queue")
class Queue(Node):
    def __init__(
        self,
        name: Optional[str] = None,
        max_size_buffers: int = 200,
        leaky: str = "no",
    ):
        super().__init__(name)
        self.add_sink_pad("sink")
        self.add_src_pad("src")
        self.max_size = int(max_size_buffers)
        if leaky not in ("no", "downstream", "upstream"):
            raise ValueError(f"unknown leaky mode {leaky!r}")
        self.leaky = str(leaky)
        self._q = None
        self._worker_thread: Optional[threading.Thread] = None
        # dispatcher-lane mode (graph/lanes.py): the drain task replacing
        # the worker thread, and the runtime scheduling it
        self._lane_rt = None
        self._lane_task = None
        # cumulative leaky-mode drops; element-level (survives stop(),
        # unlike the backend queue's own counter) — feeds the drops tracer
        self.dropped = 0
        # push stamps of the queued frames, kept while span tracing is on
        self._waits = _spans.PadWaits()

    @property
    def backend_kind(self) -> str:
        """'native' or 'python' — which queue implementation is active."""
        from ..native.queue import NativeFrameQueue

        if self._q is None:
            self._ensure_queue()
        return "native" if isinstance(self._q, NativeFrameQueue) else "python"

    def _ensure_queue(self) -> None:
        if self._q is None:
            self._q = make_frame_queue(self.max_size)

    def _dispatch(self, pad: Pad, item) -> None:
        del pad
        self._ensure_queue()
        if _spans.enabled and not isinstance(item, Event):
            # before the push: once it is in, the consumer may pop it
            self._waits.arrived(item)
        rt, task = self._lane_rt, self._lane_task
        if rt is not None and task is not None and not task.promoted:
            # lane mode: a full queue is backpressure, never a parked
            # lane — on push timeout the producer helps drain inline
            status = rt.backpressure_push(self._q, item, self.leaky, task)
        else:
            status = self._q.push(item, leaky=self.leaky)
        if status in (OK_DROPPED_OLDEST, DROPPED_INCOMING):
            self.dropped += 1
            if _hooks.enabled:
                _hooks.emit(
                    "queue_drop", self,
                    "downstream" if status == OK_DROPPED_OLDEST
                    else "upstream",
                )
        if _hooks.enabled:
            _hooks.emit("queue_push", self, len(self._q))
        if rt is not None and task is not None:
            rt.arm(task)  # lane-to-lane handoff through the ready-ring

    def spawn_threads(self) -> List[threading.Thread]:
        self._ensure_queue()
        self._worker_thread = threading.Thread(
            target=self._worker, name=f"queue:{self.name}")
        return [self._worker_thread]

    def lane_task(self, rt):
        """Dispatcher-lane registration (``graph/lanes.py``): the drain
        task that replaces the worker thread."""
        from ..graph.lanes import DrainTask

        self._ensure_queue()
        self._lane_rt = rt
        self._lane_task = DrainTask(f"queue:{self.name}", self,
                                    rt._assign_lane())
        return self._lane_task

    def _lane_step(self, rt) -> Optional[str]:
        """One lane slice: drain up to ``rt.quantum`` items without
        blocking — the cooperative twin of :meth:`_worker`, same event,
        fault, and error semantics."""
        q = self._q
        if q is None:
            return "done"
        for _ in range(rt.quantum):
            if _faults.enabled:
                # chaos: queue_wedge sleeps HERE (the lane analog of the
                # worker-loop wedge) — pops stop while pushes pile up
                _faults.maybe_queue_wedge(self.name)
            status, item = q.pop(0)
            if status == SHUTDOWN:
                return "done"
            if status != OK:
                return None  # drained; re-armed by the next push
            if _hooks.enabled:
                _hooks.emit("queue_pop", self, len(q))
            if self._waits:
                self._waits.left(item, self.name, "sink")  # push -> pop
            try:
                if isinstance(item, Event):
                    if item.kind == "eos":
                        self.sink_pads["sink"].eos = True
                        self._on_eos()
                        return "done"
                    if item.kind == "caps":
                        self._handle_caps(self.sink_pads["sink"],
                                          item.payload)
                    else:
                        self.on_event(self.sink_pads["sink"], item)
                else:
                    self.push(item)
            except BaseException as exc:  # noqa: BLE001
                if self.pipeline is not None:
                    self.pipeline.post_error(self, exc)
                return "done"
        return None

    def _worker(self) -> None:
        q = self._q  # stop() may null the attribute while we drain
        while True:
            if _faults.enabled:
                # chaos: a queue_wedge fault sleeps HERE — pushes pile up
                # while pops stop, exactly the wedge the watchdog detects
                _faults.maybe_queue_wedge(self.name)
            status, item = q.pop(_POLL_MS)
            if status == SHUTDOWN:
                return
            if status != OK:
                continue  # timeout poll: retry
            if _hooks.enabled:
                _hooks.emit("queue_pop", self, len(q))
            if self._waits:
                self._waits.left(item, self.name, "sink")  # push -> pop
            try:
                if isinstance(item, Event):
                    if item.kind == "eos":
                        self.sink_pads["sink"].eos = True
                        self._on_eos()
                        return
                    if item.kind == "caps":
                        # renegotiate our pads + forward (a NegotiationError
                        # downstream must reach post_error, not kill the
                        # worker silently)
                        self._handle_caps(self.sink_pads["sink"], item.payload)
                    else:
                        self.on_event(self.sink_pads["sink"], item)
                else:
                    self.push(item)
            except BaseException as exc:  # noqa: BLE001
                if self.pipeline is not None:
                    self.pipeline.post_error(self, exc)
                return

    def stats(self) -> dict:
        """Occupancy + drop readout (the GStreamer ``current-level-buffers``
        / leaky accounting analog); safe to call while streaming."""
        q = self._q
        return {
            "backend": self.backend_kind if q is not None else None,
            "capacity": self.max_size,
            "depth": len(q) if q is not None else 0,
            "dropped": self.dropped,
            "leaky": self.leaky,
        }

    def recover(self):
        """Supervised recovery (``Pipeline.recover_queue``): shed the
        wedged backlog — frames drop with typed accounting, in-band
        events (EOS/caps) are re-queued in order — and hand back a fresh
        worker thread if the old one died.  Returns
        ``(frames_drained, new_threads)``."""
        q = self._q
        drained = 0
        if q is not None:
            events = []
            while True:
                status, item = q.pop(0)
                if status != OK:
                    break
                if isinstance(item, Event):
                    events.append(item)
                    continue
                drained += 1
                self.dropped += 1
                if _hooks.enabled:
                    _hooks.emit("queue_drop", self, "recovery")
            for ev in events:
                q.push(ev, leaky="no")
        threads: List[threading.Thread] = []
        rt, task = self._lane_rt, self._lane_task
        if rt is not None and task is not None and not task.promoted:
            # lane mode: no worker thread to respawn — re-create a dead
            # drain task (a faulted consumer) and re-arm it
            rt.ensure_armed(self)
            self._lane_task = rt._tasks.get(f"queue:{self.name}",
                                            self._lane_task)
            return drained, threads
        t = self._worker_thread
        if q is not None and (t is None or not t.is_alive()):
            self._worker_thread = threading.Thread(
                target=self._worker, name=f"queue:{self.name}")
            threads.append(self._worker_thread)
        return drained, threads

    def interrupt(self) -> None:
        if self._q is not None:
            self._q.shutdown()

    def stop(self) -> None:
        if self._q is not None:
            self._q.shutdown()
            self._q = None
        self._lane_rt = None
        self._lane_task = None
        self._waits.clear()
        super().stop()
