"""Dispatch-ahead at a device filter: an ordered completion worker.

A pad push runs the downstream chain in the pushing thread
(``graph/node.py``).  Behind a ``tensor_filter`` whose program runs on an
accelerator, that thread enqueues the program, waits for its results in the
chain's first host read, and carries the whole way back (tee, split,
unbatch, demux, decoders, sinks) before it can feed the next round: the chip
waits for the way back.  A :class:`Completion` takes the way back off that
thread.  While its :class:`AheadRule` says so, a round's output frame goes
into a FIFO that one worker thread a filter (``ahead:<filter>``, the tid of
its spans) drains: it waits for the round's results to be ready, then
pushes the frame downstream.  The dispatching thread returns at enqueue and
carries the next round to the device while this one runs.

- **Order and flow.**  Frames leave in the order they were dispatched.  An
  event the filter receives (caps, EOS, any other) first waits until the
  worker has delivered every frame before it (:meth:`Completion.flush`),
  then runs as it always did, so ``Pipeline.wait`` reports a drained
  pipeline only once the FIFO is empty.  The FIFO holds :data:`DEPTH`
  rounds; a dispatching thread that finds it full waits, the backpressure
  of the inline path.
- **Errors.**  A fault downstream meets the faulting element's restart
  policy where it is raised, as inline; one that no policy absorbs reaches
  ``Pipeline.post_error`` from the worker, which then sheds what it holds.
- **Stop.**  :meth:`Completion.close` lets the worker deliver what it holds
  and joins it; frames dispatched after it go downstream inline.
- **The round's trace context** rides in the frame's ``meta``, so the
  worker's spans join the round's trace as the inline chain's did.

Both ways time each round on the filter's own clock (:meth:`AheadRule
.observe`): the device step, from the end of the enqueue (or the previous
result's readiness, if later) to this result's readiness, and the host
return, from readiness until the downstream push returns.  Each round
counts once in ``nnstpu_filter_rounds_total{filter, way}``.
"""

from __future__ import annotations

import collections
import threading
import time
import warnings
from typing import Optional

# Dispatching ahead puts one more round in flight: a frame that its own push
# would have carried to the device at once waits behind the round that runs,
# and a client with a free permit spends it.  It pays back the host's share
# of a round and no more.  So a filter goes ahead where the host return is
# at least ENGAGE of the device step, and comes back inline under LEAVE;
# between the two the way stays as it is.  On a TPU v5e two token streams
# return in 0.07-0.09 % of a 1.0-1.3 s step, 8 to 48 streams in 0.94-3.8 %.
ENGAGE = 0.005
LEAVE = 0.0025
# The least share of this many rounds decides, so that one round that meets
# a host stall (~100 ms) flips the way neither in nor out.
WINDOW = 2
# The first round's step holds the program's first run: it is not read.
SKIP = 1
# Rounds handed to the worker and not yet delivered.
DEPTH = 2


def count_round(filt: str, way: str) -> None:
    """One round of filter ``filt``, delivered ``way`` (ahead | inline)."""
    from ..obs.metrics import REGISTRY

    REGISTRY.counter(
        "nnstpu_filter_rounds_total",
        "rounds a tensor_filter dispatched, by the way their results went "
        "downstream: ahead (handed to the filter's completion worker, the "
        "dispatching thread back at enqueue) or inline (in the dispatching "
        "thread)", labelnames=("filter", "way"),
    ).inc(filter=filt, way=way)


class AheadRule:
    """Whether a filter's next round goes ahead, from its own clock: the
    device step and the host return of each round it delivered.  ``fixed``
    holds ``ahead`` as it is given (the tests' seam)."""

    __slots__ = ("ahead", "fixed", "_skip", "_shares")

    def __init__(self, ahead: bool = False, fixed: bool = False):
        self.ahead = ahead
        self.fixed = fixed
        self._skip = SKIP
        self._shares: collections.deque = collections.deque(maxlen=WINDOW)

    def observe(self, step_ns: int, return_ns: int) -> bool:
        if self.fixed:
            return self.ahead
        if self._skip:
            self._skip -= 1
        elif step_ns > 0:
            self._shares.append(return_ns / step_ns)
            if len(self._shares) == WINDOW:
                self.ahead = min(self._shares) >= (
                    LEAVE if self.ahead else ENGAGE)
        return self.ahead


class Completion:
    """The ordered completion worker of one filter's run."""

    def __init__(self, node, rule: AheadRule):
        self.node = node
        self.rule = rule
        self._cv = threading.Condition()
        # (frame, end of its enqueue in ns | None), oldest first; a frame
        # stays here until it is delivered
        self._held: collections.deque = collections.deque()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._failed = False
        self._ready_ns = 0  # the last round's readiness

    def emit(self, frame, enq_ns: Optional[int]) -> None:
        """Send ``frame`` downstream: handed to the worker while the rule
        says ahead, else from this thread once the worker has delivered
        what it holds.  ``enq_ns`` is the end of the round's enqueue; None
        for a frame that is no round (a quarantined filter's passthrough)."""
        name = self.node.name
        with self._cv:
            while (self.rule.ahead and not self._closed and not self._failed
                   and len(self._held) >= DEPTH):
                self._cv.wait()
            if self._failed or (self.rule.ahead and not self._closed):
                if enq_ns is not None:
                    count_round(name, "ahead")
                if self._failed:
                    return  # the pipeline has its error: shed
                self._held.append((frame, enq_ns))
                self._cv.notify_all()
                if self._thread is None:
                    self._start()
                return
        self.flush()
        if enq_ns is not None:
            count_round(name, "inline")
        self._deliver(frame, enq_ns)

    def _start(self) -> None:
        t = self._thread = threading.Thread(
            target=self._run, name=f"ahead:{self.node.name}", daemon=True)
        pl = self.node.pipeline
        if pl is not None:
            pl.threads.append(t)  # joined by Pipeline.stop with the sources
        t.start()

    def _deliver(self, frame, enq_ns: Optional[int]) -> None:
        for t in frame.tensors:
            wait = getattr(t, "block_until_ready", None)
            if wait is not None:
                wait()
        ready = time.perf_counter_ns()
        self.node.push(frame)
        if enq_ns is not None:
            self.rule.observe(ready - max(enq_ns, self._ready_ns),
                              time.perf_counter_ns() - ready)
            self._ready_ns = ready

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._held and not self._closed:
                    self._cv.wait()
                if not self._held:
                    return
                frame, enq_ns = self._held[0]
                failed = self._failed
            try:
                if not failed:
                    self._deliver(frame, enq_ns)
            except BaseException as exc:  # noqa: BLE001 - any node failure
                self._failed = True
                if self.node.pipeline is not None:
                    self.node.pipeline.post_error(self.node, exc)
            finally:
                with self._cv:
                    self._held.popleft()
                    self._cv.notify_all()

    def flush(self) -> None:
        """Wait until the worker has delivered (or shed) what it holds."""
        t = self._thread
        if t is None or t is threading.current_thread():
            return
        with self._cv:
            while self._held and t.is_alive():
                self._cv.wait(0.5)

    def close(self, wait: bool = True, timeout: float = 5.0) -> None:
        """No more hand-offs: the worker delivers what it holds and ends.
        ``wait`` joins it (``Pipeline.stop`` joins it with its other
        threads)."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        t = self._thread
        if not wait or t is None or t is threading.current_thread():
            return
        t.join(timeout)
        if t.is_alive():
            warnings.warn(
                f"{t.name}: the completion worker did not finish within "
                f"{timeout}s (a wedged downstream element?)",
                RuntimeWarning, stacklevel=2)
