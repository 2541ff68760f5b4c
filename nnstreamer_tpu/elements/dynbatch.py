"""``tensor_dynbatch`` / ``tensor_dynunbatch``: adaptive micro-batching.

``tensor_mux → tensor_batch`` batches a *fixed* number of parallel streams
(survey §2.6's north star).  This pair batches adaptively **within one
stream**: whatever frames have queued up behind a slow consumer coalesce
into a single batched invoke — the serving-framework "dynamic batching"
discipline (and the TPU-native answer to a slow or erratic host↔device
wire: transfer + dispatch costs amortize over the pile-up, automatically,
while a lightly-loaded stream stays at batch 1 for latency).

Mechanics:

- ``tensor_dynbatch`` is queue-like (own worker thread, bounded buffer).
  Each round it pops one frame then drains everything else pending, up to
  ``max_batch``; the set is stacked into one ``(bucket, *shape)`` frame.
- Batch sizes round up to power-of-2 **buckets** (padding repeats the
  last frame) so the downstream XLA filter compiles one executable per
  bucket — the backend's bounded LRU executable cache makes bucket flips
  cheap after first sight, and per-frame signature checks are skipped via
  the polymorphic (batch=None) negotiated spec, exactly the drift path
  the jax backend already handles.  Under mesh-sharded dispatch
  (``NNSTPU_MESH`` — ``residency.consumer_mesh_devices``) ``max_batch``
  is the PER-SHARD cap: up to ``max_batch × ndev`` rows coalesce and
  buckets are ``ndev × pow-2`` (:func:`mesh_bucket`), so every emitted
  batch divides the mesh and one invoke spans all chips.
- Frame timing/meta ride in ``meta["dynbatch"]``; ``tensor_dynunbatch``
  splits the batched result back into the original frames (padding rows
  dropped), preserving per-frame pts/duration.

The model under the filter must accept a polymorphic leading batch dim
(``input_spec`` shape ``(None, ...)``).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..buffer import Event, Frame
from ..graph.node import NegotiationError, Node, Pad
from ..graph.registry import register_element
from ..native import OK, SHUTDOWN
from ..native.queue import make_frame_queue
from ..obs import hooks as _hooks
from ..obs import spans as _spans
from ..spec import TensorSpec, TensorsSpec

_POLL_MS = 100


def _bucket(n: int, max_batch: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return min(b, max_batch)


def mesh_bucket(n: int, max_batch: int, ndev: int = 1) -> int:
    """Batch-size bucket for ``n`` queued rows dispatching over an
    ``ndev``-device mesh: the power-of-2 ladder applies PER SHARD, so the
    emitted batch is ``ndev × bucket(ceil(n/ndev))`` — always divisible by
    the mesh, and the executable set stays bounded to {ndev × pow-2
    buckets ≤ ndev × max_batch}.  ``ndev=1`` is the classic ladder."""
    if ndev <= 1:
        return _bucket(n, max_batch)
    return ndev * _bucket(-(-n // ndev), max_batch)


@register_element("tensor_dynbatch")
class DynBatch(Node):
    def __init__(
        self,
        name: Optional[str] = None,
        max_batch: int = 8,
        max_size_buffers: int = 64,
    ):
        super().__init__(name)
        self.add_sink_pad("sink")
        self.add_src_pad("src")
        self.max_batch = int(max_batch)
        if self.max_batch < 1 or (self.max_batch & (self.max_batch - 1)):
            # the bucket set {1, 2, 4, ..., max_batch} bounds the filter's
            # per-bucket executable cache; a non-power-of-2 cap would emit
            # an extra odd bucket and silently break that reasoning
            raise ValueError(
                f"max_batch must be a power of two, got {self.max_batch}"
            )
        self.max_size = int(max_size_buffers)
        self._q = None
        # dispatcher-lane mode (graph/lanes.py)
        self._lane_rt = None
        self._lane_task = None
        self.batches_emitted = 0  # observability: how often we coalesced
        self.frames_in = 0
        self._pool = None  # shared staging pool, resolved lazily
        self._mesh_dev = 1  # downstream dispatch-mesh width (configure)
        # push stamps of the queued frames, kept while span tracing is on
        self._waits = _spans.PadWaits()

    def configure(self, in_specs: Dict[str, TensorsSpec]) -> Dict[str, TensorsSpec]:
        spec = in_specs["sink"]
        if not spec.tensors_fixed:
            raise NegotiationError(
                f"{self.name}: dynbatch needs fixed upstream tensors, got {spec}"
            )
        out = tuple(
            TensorSpec(dtype=t.dtype, shape=(None,) + tuple(t.shape))
            for t in spec.tensors
        )
        from ..graph.residency import consumer_mesh_devices

        # mesh-sharded consumer: buckets grow in per-shard multiples so one
        # invoke spreads the pile-up across every chip
        self._mesh_dev = consumer_mesh_devices(self)
        # batch dim None → downstream pads skip per-frame sig checks and the
        # jax backend treats each new bucket as spec drift (LRU-cached)
        return {"src": TensorsSpec(tensors=out, rate=spec.rate)}

    def warmup_plan(self):
        """Compile-ahead: one thunk per ``ndev × pow-2`` bucket this
        element can emit, aimed at the downstream filter (hopping
        queue/upload plumbing).  With warmup on, every bucket executable
        exists before PLAYING — a pile-up's first flip to a new bucket
        never pays a compile on the request path."""
        from ..graph.residency import downstream_filter_node

        spec = self.sink_pads["sink"].spec
        if spec is None or not spec.tensors_fixed:
            return []
        filt = downstream_filter_node(self)
        warm = getattr(filt, "warm_spec", None)
        if warm is None:
            return []
        ndev = max(1, self._mesh_dev)
        buckets = []
        b = 1
        while b <= self.max_batch:
            buckets.append(b * ndev)
            b <<= 1
        ensure = getattr(filt.backend, "ensure_cache_capacity", None)
        if ensure is not None:
            # the ladder plus the negotiated entry must coexist in the
            # backend LRU, or warmup would evict its own work
            ensure(len(buckets) + 1)
        items = []
        for bb in buckets:
            bspec = TensorsSpec(
                tensors=tuple(
                    TensorSpec(dtype=t.dtype, shape=(bb,) + tuple(t.shape))
                    for t in spec.tensors
                ),
                rate=spec.rate,
            )
            items.append((f"bucket{bb}", lambda s=bspec: warm(s)))
        return items

    def _ensure_queue(self):
        if self._q is None:
            self._q = make_frame_queue(self.max_size)

    def _dispatch(self, pad: Pad, item) -> None:
        """The tracer hook points of :meth:`Node._dispatch` around this
        element's own dispatch, as ``CollectNode._dispatch`` has them
        (one flag test with no tracer attached)."""
        if _hooks.enabled:
            t0 = time.perf_counter_ns()
            _hooks.emit("dispatch_enter", self, pad, item, t0)
            try:
                self._enqueue(item)
            finally:
                _hooks.emit("dispatch_exit", self, pad, item,
                            time.perf_counter_ns() - t0)
            return
        self._enqueue(item)

    def _enqueue(self, item) -> None:
        self._ensure_queue()
        if _spans.enabled and not isinstance(item, Event):
            # before the push: once it is in, the worker may flush it
            self._waits.arrived(item)
        rt, task = self._lane_rt, self._lane_task
        if rt is not None and task is not None and not task.promoted:
            rt.backpressure_push(self._q, item, "no", task)
            rt.arm(task)
            return
        self._q.push(item, leaky="no")

    def spawn_threads(self) -> List[threading.Thread]:
        self._ensure_queue()
        return [threading.Thread(target=self._worker, name=f"dynbatch:{self.name}")]

    def lane_task(self, rt):
        """Dispatcher-lane registration (``graph/lanes.py``): the
        coalescing drain task that replaces the worker thread."""
        from ..graph.lanes import DrainTask

        self._ensure_queue()
        self._lane_rt = rt
        self._lane_task = DrainTask(f"dynbatch:{self.name}", self,
                                    rt._assign_lane())
        return self._lane_task

    def _lane_step(self, rt) -> Optional[str]:
        """One lane slice: the cooperative twin of :meth:`_worker` — pop
        one frame, greedily coalesce whatever else is already queued
        (never blocking), emit the batch."""
        q = self._q
        if q is None:
            return "done"
        max_pending = self.max_batch * max(1, self._mesh_dev)
        for _ in range(rt.quantum):
            status, item = q.pop(0)
            if status == SHUTDOWN:
                return "done"
            if status != OK:
                return None  # drained; re-armed by the next push
            pending: List[Frame] = []
            try:
                if isinstance(item, Event):
                    if self._event(item):
                        return "done"
                    continue
                pending.append(item)
                while len(pending) < max_pending:
                    status, nxt = q.pop(0)
                    if status != OK:
                        break
                    if isinstance(nxt, Event):
                        self._emit_batch(pending)
                        pending = []
                        if self._event(nxt):
                            return "done"
                        break
                    pending.append(nxt)
                if pending:
                    self._emit_batch(pending)
            except BaseException as exc:  # noqa: BLE001
                if self.pipeline is not None:
                    self.pipeline.post_error(self, exc)
                return "done"
        return None

    def _pool_or_default(self):
        if self._pool is None:
            from ..pool import default_pool

            self._pool = default_pool()
        return self._pool

    def _emit_batch(self, frames: List[Frame]) -> None:
        if self._waits:
            # a flush: every frame of it waited from its push until now
            flushed = time.perf_counter_ns()
            for f in frames:
                self._waits.left(f, self.name, "sink", flushed)
        n = len(frames)
        b = mesh_bucket(n, self.max_batch, self._mesh_dev)
        pad_rows = b - n
        stacked = []
        copied = 0
        allocs = 0
        for ti in range(frames[0].num_tensors):
            rows = [np.asarray(f.tensors[ti]) for f in frames]
            # slot-wise assembly into a recycled pooled buffer: each row
            # (and each padding repeat of the last row) copied exactly once
            # into its slot — no fresh np.stack allocation per flush
            buf = self._pool_or_default().lease(
                (b,) + rows[0].shape, rows[0].dtype
            )
            for i, r in enumerate(rows):
                np.copyto(buf[i], r)
            for i in range(n, b):  # pad: repeat last frame
                np.copyto(buf[i], rows[-1])
            stacked.append(buf)
            copied += buf.nbytes
            allocs += 1 if buf.pool_fresh else 0
        if _hooks.enabled:
            _hooks.emit("copy", self, copied, allocs)
        meta = {
            "dynbatch": {
                "n": n,
                "pts": [f.pts for f in frames],
                "duration": [f.duration for f in frames],
                "meta": [f.meta for f in frames],
            }
        }
        if _spans.enabled:
            # the batched frame gets its own span with parent links to
            # every constituent frame's span (their per-frame contexts
            # survive inside meta["dynbatch"]["meta"] and are restored by
            # tensor_dynunbatch)
            _spans.merge_context(frames, meta, self.name)
        self.frames_in += n
        self.batches_emitted += 1
        if _hooks.enabled:
            _hooks.emit("dynbatch_flush", self, n, b)
        self.push(Frame(tensors=tuple(stacked), pts=frames[0].pts,
                        duration=frames[0].duration, meta=meta))

    def _worker(self) -> None:
        q = self._q
        pending: List[Frame] = []
        # per-mesh dispatch sizing: max_batch is the PER-SHARD cap, so an
        # ndev-wide consumer coalesces up to max_batch × ndev rows per
        # invoke (the whole point of serving the pool from all chips)
        max_pending = self.max_batch * max(1, self._mesh_dev)
        while True:
            status, item = q.pop(_POLL_MS)
            if status == SHUTDOWN:
                return
            if status != OK:
                continue
            try:
                if isinstance(item, Event):
                    if pending:  # events never reorder past queued frames
                        self._emit_batch(pending)
                        pending = []
                    if self._event(item):
                        return
                    continue
                pending.append(item)
                # coalesce whatever else is already waiting (never block)
                while len(pending) < max_pending:
                    status, nxt = q.pop(0)
                    if status != OK:
                        break
                    if isinstance(nxt, Event):
                        self._emit_batch(pending)
                        pending = []
                        if self._event(nxt):
                            return
                        break
                    pending.append(nxt)
                if pending:
                    self._emit_batch(pending)
                    pending = []
            except BaseException as exc:  # noqa: BLE001
                if self.pipeline is not None:
                    self.pipeline.post_error(self, exc)
                return

    def _event(self, event: Event) -> bool:
        """Handle an in-band event on the worker thread; True = stream over.
        Caps events renegotiate THIS node (the batched spec downstream must
        track the new per-frame spec — same discipline as queue.py)."""
        if event.kind == "eos":
            self.sink_pads["sink"].eos = True
            self._on_eos()
            return True
        if event.kind == "caps":
            self._handle_caps(self.sink_pads["sink"], event.payload)
        else:
            self.on_event(self.sink_pads["sink"], event)
        return False

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


@register_element("tensor_dynunbatch")
class DynUnbatch(Node):
    """Inverse of :class:`DynBatch`: split a batched frame back into its
    original per-frame stream using the ``dynbatch`` meta (padding rows
    dropped, per-frame timing restored)."""

    def __init__(self, name: Optional[str] = None):
        super().__init__(name)
        self.add_sink_pad("sink")
        self.add_src_pad("src")

    def configure(self, in_specs: Dict[str, TensorsSpec]) -> Dict[str, TensorsSpec]:
        spec = in_specs["sink"]
        out = []
        for t in spec.tensors:
            if t.rank < 1:
                raise NegotiationError(
                    f"{self.name}: expected batched tensors, got {t}"
                )
            out.append(TensorSpec(dtype=t.dtype, shape=tuple(t.shape[1:])))
        return {"src": TensorsSpec(tensors=tuple(out), rate=spec.rate)}

    def process(self, pad: Pad, frame: Frame):
        del pad
        info = frame.meta.get("dynbatch")
        n = info["n"] if info else frame.tensors[0].shape[0]
        # one host materialization per batched tensor (numpy row views after)
        mats = [np.asarray(t) for t in frame.tensors]
        out = []
        metas = info.get("meta") if info else None
        for i in range(n):
            pts = info["pts"][i] if info else frame.pts
            dur = info["duration"][i] if info else frame.duration
            out.append(Frame(
                tensors=tuple(m[i] for m in mats), pts=pts, duration=dur,
                meta=metas[i] if metas else {},
            ))
        return out
