"""Device lane: true device timing, compile accounting, memory gauges.

Every other tracer measures **host** wall time — but JAX dispatch is
asynchronous: ``backend.invoke`` returns when the XLA call is *enqueued*,
not when the executable finishes, so the ``dispatch_exit`` hook and the
nested dispatch span systematically misattribute device compute to
whichever downstream element first blocks on the result (exactly the
blind spot device-side TPU tracing exists to close — PAPERS.md).  This
module is the device lane of the obs subsystem:

- :class:`DeviceTracer` (``NNSTPU_TRACERS=device``) stamps each filter
  dispatch with a **completion probe**: the ``device_dispatch`` hook
  hands the returned arrays to a bounded queue drained by a background
  *reaper* thread that blocks on readiness (``jax.block_until_ready`` —
  duck-typed, so host-backend outputs complete instantly) and emits a
  real ``device_exec`` span with enqueue→done timing into the flight
  recorder on a dedicated device track (the reaper thread's row in
  Perfetto), with a flow arrow from the host dispatch span.  The queue
  is bounded so a wedged device can never grow host memory without
  bound — overflow drops the probe and counts it.  Each span carries the
  filter's dispatch count as ``round`` (the join with that dispatch's
  ``<filter>.invoke`` stage span), and with profiling enabled
  (:mod:`nnstreamer_tpu.utils.profiling`) its duration is the per-node
  latency of ``Pipeline.stats()``.  A pipeline starts this lane by itself,
  on a registry of its own, when it starts while the hook bus has a
  listener or with profiling on (``graph/pipeline.py``).
- :func:`record_compile` is the sink for backend executable-cache
  events (``backends/jax_backend.py`` calls it on every hit/miss/evict):
  ``nnstpu_compile_total{result=...}`` counters, a compile wall-time
  histogram, flops/bytes from ``cost_analysis()`` when the runtime
  exposes them, a ``compile`` span when span tracing is active, and the
  ``compile`` hook for per-pipeline tracers.  Counters are fed
  unconditionally (compiles are rare and expensive; one counter inc is
  noise) so compile churn is visible in any scrape, tracer or not.
- :func:`register_memory_gauges` / :func:`device_memory_snapshot` sample
  per-device ``memory_stats()`` (bytes in use, peak, pool limit) as
  ``nnstpu_device_memory_bytes`` gauges at scrape time and as a dict for
  error flight dumps.  Host platforms without allocator stats simply
  contribute nothing.
- the **utilization lane** (:mod:`.util`): every reaped dispatch is
  joined with its executable's registered ``cost_analysis()`` profile
  (the backend stamps a cost fingerprint per compiled entry) to compute
  per-dispatch achieved-TFLOPs / achieved-GB/s / MFU
  (``nnstpu_mfu{device,node,bucket}``) and a roofline classification
  (``compute_bound``/``bandwidth_bound`` on the span args and
  ``nnstpu_roofline_dispatches_total``); ``device_exec`` span coverage
  feeds the windowed ``nnstpu_device_busy_fraction{device}`` gauge, and
  idle gaps ≥ ``[obs] device_idle_gap_ms`` become ``device_idle``
  flight spans on the device track (reason: ``wire`` under a sick
  probe regime, ``host_dispatch`` when nothing was enqueued,
  ``queue_wait`` otherwise) — see ``docs/observability.md``
  "Utilization lane".

The watchdog (:mod:`.watchdog`) reads :func:`oldest_inflight` to flag
dispatches whose device completion exceeds its deadline.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..utils import profiling as _profiling
from . import hooks as _hooks
from . import spans
from . import util as _util
from .metrics import REGISTRY, MetricsRegistry
from .tracers import Tracer

now_ns = time.perf_counter_ns

# Seconds-unit buckets for device execution / compile time: the latency
# bucket ladder shifted into seconds (50 µs – 2.5 s) plus a long tail for
# cold compiles.
DEVICE_EXEC_BUCKETS_S = (
    5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2,
    0.1, 0.25, 0.5, 1.0, 2.5,
)
COMPILE_BUCKETS_S = DEVICE_EXEC_BUCKETS_S + (5.0, 10.0, 30.0, 60.0)

DEFAULT_PROBE_CAPACITY = 1024

# In-flight dispatch registry (probe id -> (t0_ns, element name)), shared
# by every active DeviceTracer so the watchdog can ask "how old is the
# oldest dispatch still executing on device" without touching jax.
_inflight_lock = threading.Lock()
_inflight: Dict[int, Tuple[int, str]] = {}


def oldest_inflight() -> Optional[Tuple[int, str]]:
    """(enqueue ts_ns, element name) of the oldest dispatch whose device
    completion has not been observed yet, or None.  Only meaningful while
    a :class:`DeviceTracer` is attached (otherwise nothing registers)."""
    with _inflight_lock:
        if not _inflight:
            return None
        return min(_inflight.values())


def configured_probe_capacity() -> int:
    """Completion-probe queue bound: ``NNSTPU_OBS_DEVICE_PROBE_QUEUE`` /
    ini ``[obs] device_probe_queue`` over the default."""
    from ..conf import conf

    try:
        cap = conf.get_int("obs", "device_probe_queue",
                           DEFAULT_PROBE_CAPACITY)
    except ValueError:
        return DEFAULT_PROBE_CAPACITY
    return cap if cap > 0 else DEFAULT_PROBE_CAPACITY


# -- compile accounting ------------------------------------------------------

# Compile-phase attribution (thread-local): the warmup phase marks its
# threads so compile spans land on the dedicated "warmup" Perfetto track
# (not inside the first frame's trace) and nnstpu_compile_seconds splits
# by phase={warmup,serving}.
_phase_tls = threading.local()


def set_compile_phase(phase: Optional[str]) -> None:
    """Mark the calling thread's compiles as ``phase`` ("warmup") or
    restore the default ("serving") with None."""
    _phase_tls.phase = phase


def compile_phase() -> str:
    return getattr(_phase_tls, "phase", None) or "serving"


def _compile_metrics(registry: MetricsRegistry):
    return (
        registry.counter(
            "nnstpu_compile_total",
            "Backend executable-cache events (hit/miss/persist_hit/evict)",
            labelnames=("result",),
        ),
        registry.histogram(
            "nnstpu_compile_seconds",
            "Wall time spent building backend executables (seconds; "
            "persist_hit reconstructs included), split by compile phase",
            labelnames=("phase",),
            buckets=COMPILE_BUCKETS_S,
        ),
        registry.counter(
            "nnstpu_compile_flops_total",
            "Sum of cost_analysis() flops over compiled executables",
        ),
        registry.counter(
            "nnstpu_compile_bytes_total",
            "Sum of cost_analysis() bytes accessed over compiled executables",
        ),
    )


def cost_info(compiled) -> dict:
    """flops/bytes out of an AOT ``Compiled.cost_analysis()`` (a dict
    keyed ``"flops"`` / ``"bytes accessed"``); {} when the backend
    doesn't implement it."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — optional on many backends
        return {}
    if not isinstance(ca, dict):
        return {}
    info = {}
    if ca.get("flops"):
        info["flops"] = float(ca["flops"])
    if ca.get("bytes accessed"):
        info["bytes"] = float(ca["bytes accessed"])
    return info


def memory_info(compiled) -> dict:
    """Per-executable HBM footprint out of an AOT
    ``Compiled.memory_analysis()`` (``CompiledMemoryStats``): argument/
    output/temp/alias/generated-code bytes, as
    ``{"argument_bytes": ..., "output_bytes": ..., ...}``; {} when the
    runtime doesn't expose it.  Recorded alongside the cost registry at
    compile time — the feed behind ``nnstpu_executable_hbm_bytes`` and
    the OOM flight dump's HBM ledger (obs/profiler.py)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — optional on many backends
        return {}
    if ma is None:
        return {}
    info = {}
    for kind in ("argument", "output", "temp", "alias", "generated_code"):
        val = getattr(ma, f"{kind}_size_in_bytes", None)
        if isinstance(val, (int, float)) and val >= 0:
            info[f"{kind}_bytes"] = int(val)
    return info


def record_compile(backend, key, result: str, dur_ns: int = 0,
                   info: Optional[dict] = None,
                   registry: Optional[MetricsRegistry] = None) -> None:
    """Account one executable-cache event (called by filter backends).

    Feeds the ``nnstpu_compile_*`` metrics unconditionally, records a
    ``compile`` span when span tracing is active, and emits the
    ``compile`` hook for attached tracers.  Never raises — compile
    accounting must not take a compile down."""
    try:
        phase = compile_phase()
        counters, hist, flops_c, bytes_c = _compile_metrics(
            registry if registry is not None else REGISTRY)
        counters.inc(1, result=result)
        if result in ("miss", "persist_hit"):
            hist.observe(dur_ns / 1e9, phase=phase)
            if info:
                # cost_analysis() reports negative sentinels for ops it
                # cannot cost (custom calls / host callbacks) — a counter
                # rejects those, so only true positives accumulate
                if (info.get("flops") or 0) > 0:
                    flops_c.inc(info["flops"])
                if (info.get("bytes") or 0) > 0:
                    bytes_c.inc(info["bytes"])
        if spans.enabled and result in ("miss", "persist_hit"):
            args = {"key": repr(key), "backend": type(backend).__name__,
                    "result": result, "phase": phase}
            if info:
                args.update(info)
            if phase == "warmup":
                # warmup-phase compiles land on the dedicated "warmup"
                # Perfetto track, never inside the first frame's trace
                # (the recorder keys rows by tid string, not OS thread)
                spans._recorder.append((
                    spans.PH_COMPLETE, now_ns() - dur_ns, dur_ns, "warmup",
                    "compile", "compile", 0, next(spans._ids), 0, args))
            else:
                spans.record_span("compile", now_ns() - dur_ns, dur_ns,
                                  cat="compile", trace=(0, 0), args=args)
        if _hooks.enabled:
            _hooks.emit("compile", backend, key, result, dur_ns, info or {})
    except Exception:  # noqa: BLE001
        import logging

        logging.getLogger("nnstreamer_tpu.obs").exception(
            "compile accounting failed")


def record_weights_upload(backend, placed, dur_ns: int,
                          registry: Optional[MetricsRegistry] = None) -> None:
    """Account one upload of a model's weights (a backend's ``open``): the
    ``nnstpu_weights_upload_seconds`` / ``nnstpu_weights_device_bytes``
    metrics whatever the hook gate, and a ``weights_upload`` span when span
    tracing is active.  Never raises."""
    try:
        reg = registry if registry is not None else REGISTRY
        model = str(getattr(backend.model, "name", "") or "")
        nbytes = sum(int(a.nbytes) for a in placed)
        reg.histogram(
            "nnstpu_weights_upload_seconds",
            "Wall time spent putting a model's weights on its devices "
            "(seconds, device_put to ready), one observation an upload",
            labelnames=("model",), buckets=COMPILE_BUCKETS_S,
        ).observe(dur_ns / 1e9, model=model)
        reg.gauge(
            "nnstpu_weights_device_bytes",
            "Bytes of a model's weights as its programs' arguments hold "
            "them (one replica)", labelnames=("model",),
        ).set(nbytes, model=model)
        if spans.enabled:
            spans.record_span(
                "weights_upload", now_ns() - dur_ns, dur_ns, cat="compile",
                trace=(0, 0), args={"model": model, "bytes": nbytes,
                                    "arrays": len(placed)})
    except Exception:  # noqa: BLE001
        import logging

        logging.getLogger("nnstreamer_tpu.obs").exception(
            "weights upload accounting failed")


# -- device memory gauges ----------------------------------------------------

# memory_stats() keys worth exposing (allocator implementations differ;
# anything absent is skipped)
_MEMORY_KEYS = (
    "bytes_in_use",
    "peak_bytes_in_use",
    "bytes_limit",
    "bytes_reservable_limit",
    "pool_bytes",
    "largest_alloc_size",
)


def _device_label(d) -> str:
    plat = getattr(d, "platform", None) or "device"
    return f"{plat}:{getattr(d, 'id', 0)}"


def _head_device_label(head) -> str:
    """``platform:ordinal`` of a single-device array's placement ("host"
    for numpy and other non-device outputs)."""
    try:
        devs = head.devices()
        for d in devs:
            return _device_label(d)
    except Exception:  # noqa: BLE001 — not a device array
        pass
    return "host"


def _mesh_shards(head):
    """``[(device_label, ordinal, per-shard array)]`` for a mesh-sharded
    output (ordinal-sorted), or None for single-device / non-jax heads.
    Duck-typed on ``sharding.device_set`` + ``addressable_shards`` so the
    CPU-mesh test harness exercises the same path as a real v5e-8."""
    try:
        if len(head.sharding.device_set) <= 1:
            return None
        shards = head.addressable_shards
        out = [
            (_device_label(s.device), getattr(s.device, "id", i), s.data)
            for i, s in enumerate(shards)
        ]
    except Exception:  # noqa: BLE001 — not a sharded device array
        return None
    if len(out) <= 1:
        return None
    out.sort(key=lambda e: e[1])
    return out


# Peak-watermark deltas: the instantaneous gauges miss transient spikes
# between scrapes, so every snapshot folds the observed high-water mark
# into a per-device watermark that the peak gauge drains at scrape time.
_peak_lock = threading.Lock()
_peak_watermarks: Dict[str, int] = {}

# allocator peak-reset spellings, probed in order (most allocators have
# none — the watermark then carries the since-start peak, still honest)
_PEAK_RESET_METHODS = ("reset_memory_stats", "clear_memory_stats",
                       "reset_peak_memory_stats")


def _observe_peaks(snapshot: Dict[str, Dict[str, int]]) -> None:
    with _peak_lock:
        for dev, stats in snapshot.items():
            seen = max(stats.get("peak_bytes_in_use", 0),
                       stats.get("bytes_in_use", 0))
            if seen > _peak_watermarks.get(dev, 0):
                _peak_watermarks[dev] = seen


def reset_peak_watermarks() -> None:
    """Drop every tracked watermark (test isolation)."""
    with _peak_lock:
        _peak_watermarks.clear()


def device_memory_snapshot(devices=None) -> Dict[str, Dict[str, int]]:
    """Per-device ``memory_stats()`` snapshot ({"tpu:0": {bytes_in_use:
    ...}}), for /metrics collectors and error flight dumps.  Devices
    without allocator stats (CPU) are omitted.  Every snapshot also
    feeds the peak watermarks behind
    ``nnstpu_device_memory_peak_bytes``."""
    if devices is None:
        try:
            import jax

            devices = jax.devices()
        except Exception:  # noqa: BLE001 — no backend at all
            return {}
    out: Dict[str, Dict[str, int]] = {}
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — unimplemented on this platform
            continue
        if not stats:
            continue
        kept = {k: int(stats[k]) for k in _MEMORY_KEYS
                if isinstance(stats.get(k), (int, float))}
        if kept:
            out[_device_label(d)] = kept
    _observe_peaks(out)
    return out


def register_memory_gauges(registry: Optional[MetricsRegistry] = None,
                           devices=None):
    """Sample per-device memory into ``nnstpu_device_memory_bytes``
    gauges at every scrape (a registry collector — pull-style, no
    poller).  Returns the collector handle for ``remove_collector``.

    Also exports ``nnstpu_device_memory_peak_bytes{device}``: the
    highest ``peak_bytes_in_use`` observed since the LAST scrape (any
    snapshot between scrapes feeds the watermark).  After each read the
    tracked watermark resets to zero and, where the allocator supports a
    peak reset (probed: ``reset_memory_stats`` /
    ``clear_memory_stats`` / ``reset_peak_memory_stats``), the
    device-side peak resets too — making the series a true
    between-scrapes high-water mark instead of a since-start maximum."""
    registry = registry if registry is not None else REGISTRY
    gauge = registry.gauge(
        "nnstpu_device_memory_bytes",
        "Per-device allocator stats (bytes), sampled at scrape time",
        labelnames=("device", "kind"),
    )
    peak_gauge = registry.gauge(
        "nnstpu_device_memory_peak_bytes",
        "Per-device peak bytes in use observed since the last scrape "
        "(watermark drained at read; allocator peak reset where supported)",
        labelnames=("device",),
    )

    def collect():
        snapshot = device_memory_snapshot(devices)
        for dev, stats in snapshot.items():
            for kind, val in stats.items():
                gauge.set(val, device=dev, kind=kind)
        with _peak_lock:
            drained = {dev: _peak_watermarks.pop(dev, 0)
                       for dev in snapshot}
        for dev, peak in drained.items():
            peak_gauge.set(peak, device=dev)
        devs = devices
        if devs is None:
            try:
                import jax

                devs = jax.devices()
            except Exception:  # noqa: BLE001
                devs = ()
        for d in devs:
            if _device_label(d) not in drained:
                continue
            for meth in _PEAK_RESET_METHODS:
                fn = getattr(d, meth, None)
                if callable(fn):
                    try:
                        fn()
                    except Exception:  # noqa: BLE001 — reset is best-effort
                        pass
                    break

    return registry.add_collector(collect)


# -- the tracer --------------------------------------------------------------

class DeviceTracer(Tracer):
    """True device timing via completion probes.

    ``device_dispatch`` (emitted by ``tensor_filter`` right after the
    backend invoke returns) hands the output arrays to a bounded probe
    queue; a background reaper thread blocks on their readiness and
    records a ``device_exec`` span (ts = enqueue, dur = enqueue→done) on
    its own thread — a dedicated device track in the Perfetto export —
    linked to the host dispatch span by a flow arrow.  Histograms and
    counters land on the metrics registry; the queue bound plus overflow
    accounting keep a wedged device from backing memory up into the
    pipeline.
    """

    name = "device"

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 capacity: Optional[int] = None):
        super().__init__(registry)
        self._capacity = capacity
        self._q: collections.deque = collections.deque()
        self._cv = threading.Condition()
        self._reaper: Optional[threading.Thread] = None
        self._running = False
        self._lock = threading.Lock()
        self._by_element: Dict[str, List[int]] = {}  # name -> [count, ns]
        # label -> [count, ns, flops_sum, cost_missing_count]: the
        # utilization view keeps EVERY dispatch (cost-less ones count in
        # the missing column and read mfu=None — never silently omitted)
        self._by_device: Dict[str, List] = {}
        # label -> (last completion ts_ns, probe queue empty then): the
        # dead-time tracker behind device_idle gap spans
        self._last_end: Dict[str, tuple] = {}
        self._usage = _util.DeviceUsage()
        self._sent = 0
        self._completed = 0
        self._dropped = 0
        self._compiles: Dict[str, int] = {
            "hit": 0, "miss": 0, "persist_hit": 0, "evict": 0}
        self._last_compile: Optional[dict] = None
        self._mem_handle = None
        self._busy_decay_handle = None

    def _install(self) -> None:
        cap = self._capacity if self._capacity is not None \
            else configured_probe_capacity()
        self._cap = max(1, int(cap))
        # the device lane records into the span flight recorder even when
        # no SpanTracer is attached: NNSTPU_TRACERS=device alone must
        # still yield a chrome trace with device_exec spans
        spans._activate(spans.configured_flight_records())
        self._hist = self._registry.histogram(
            "nnstpu_device_exec_seconds",
            "True device execution time per dispatch, enqueue to "
            "completion (seconds; one series per mesh device when the "
            "dispatch spans a sharded output)",
            labelnames=("pipeline", "element", "device"),
            buckets=DEVICE_EXEC_BUCKETS_S,
        )
        self._dispatches = self._registry.counter(
            "nnstpu_device_dispatches_total",
            "Dispatches handed to the device completion reaper",
            labelnames=("pipeline", "element"),
        )
        self._drop_counter = self._registry.counter(
            "nnstpu_device_probe_dropped_total",
            "Completion probes dropped on reaper-queue overflow",
            labelnames=("pipeline",),
        )
        # utilization lane: per-dispatch MFU (cost_analysis flops over
        # measured enqueue->done time vs the configured peak), roofline
        # classification counts, and the windowed busy fraction
        self._mfu_gauge = self._registry.gauge(
            "nnstpu_mfu",
            "Model FLOPs utilization of the last observed dispatch "
            "(cost_analysis flops / device time / the device_kind's peak "
            "in obs.util.DEVICE_PEAKS; absent for an unknown device)",
            labelnames=("device", "node", "bucket"),
        )
        self._bound_counter = self._registry.counter(
            "nnstpu_roofline_dispatches_total",
            "Observed dispatches by roofline classification (arithmetic "
            "intensity vs the device's peak ridge point)",
            labelnames=("pipeline", "device", "bound"),
        )
        self._busy_gauge = self._registry.gauge(
            "nnstpu_device_busy_fraction",
            "Fraction of the trailing [obs] busy_window_s each device "
            "spent executing observed dispatches (device_exec coverage)",
            labelnames=("device",),
        )
        self._peak_tf = _util.peak_tflops()
        self._peak_gb = _util.peak_gbs()
        self._idle_gap_ns = int(_util.configured_idle_gap_ms() * 1e6)
        if self._busy_decay_handle is not None:
            # a restart while the previous stop()'s decay collector is
            # still draining: the live collector takes over
            self._registry.remove_collector(self._busy_decay_handle)
            self._busy_decay_handle = None
        self._busy_handle = self._registry.add_collector(self._collect_busy)
        self._mem_handle = register_memory_gauges(self._registry)
        self._running = True
        try:
            import jax

            platform = jax.default_backend()
        except Exception:  # noqa: BLE001
            platform = "device"
        self._reaper = threading.Thread(
            target=self._reap, name=f"device:{platform}", daemon=True)
        self._reaper.start()
        self._connect("device_dispatch", self._on_device_dispatch)
        self._connect("compile", self._on_compile)

    def stop(self) -> None:
        was_active = bool(self._conns)
        super().stop()
        if not was_active:
            return
        with self._cv:
            self._running = False
            self._cv.notify_all()
        if self._reaper is not None:
            # a reaper blocked on a wedged device is abandoned (daemon);
            # its probes stay registered as in-flight for the watchdog
            self._reaper.join(timeout=5)
            self._reaper = None
        if self._mem_handle is not None:
            self._registry.remove_collector(self._mem_handle)
            self._mem_handle = None
        if getattr(self, "_busy_handle", None) is not None:
            self._registry.remove_collector(self._busy_handle)
            self._busy_handle = None
            self._install_busy_decay()
        spans._deactivate()

    def _install_busy_decay(self) -> None:
        """Replace the live busy collector with a self-removing decaying
        one: the gauge must keep tracking the (shrinking) windowed busy
        fraction after stop() and read 0 once the window has fully
        passed with no reaps — a frozen last-value gauge misleads any
        idle/healthy read taken between runs (the benchmark sentinel,
        the autoscaler's busy band).  The series stays present (CI
        scrapes after the run), it just decays honestly."""
        gauge = getattr(self, "_busy_gauge", None)
        if gauge is None:
            return
        window_ns = int(_util.configured_busy_window_s() * 1e9)
        deadline = now_ns() + window_ns
        usage = self._usage
        registry = self._registry

        def decay() -> None:
            done = now_ns() >= deadline
            fracs = {} if done else usage.busy_fractions()
            for device in usage.devices():
                gauge.set(round(fracs.get(device, 0.0), 6), device=device)
            if done:
                registry.remove_collector(decay)
                if self._busy_decay_handle is decay:
                    self._busy_decay_handle = None

        self._busy_decay_handle = registry.add_collector(decay)

    # -- hook callbacks ------------------------------------------------------

    def _on_device_dispatch(self, node, frame, outs, t0_ns) -> None:
        if node.pipeline is not self._pipeline:
            return
        ctx = spans.context_of(frame)
        trace_id, parent = (ctx[0], ctx[1]) if ctx is not None else (0, 0)
        head = outs[0] if isinstance(outs, (tuple, list)) and outs else outs
        # the executable's cost fingerprint, read on the dispatching
        # thread so it matches the geometry just invoked (a renegotiation
        # between enqueue and reap must not mislabel this dispatch)
        cost_key = None
        backend = getattr(node, "backend", None)
        ck_fn = getattr(backend, "cost_key", None)
        if ck_fn is not None:
            try:
                cost_key = ck_fn()
            except Exception:  # noqa: BLE001 — attribution is best-effort
                cost_key = None
        pid = next(spans._ids)
        fid = next(spans._flow_ids)
        # flow START on the dispatching (host) thread, inside the host
        # dispatch span: Perfetto draws the arrow host span -> device span
        spans._recorder.append((
            spans.PH_FLOW_START, now_ns(), 0,
            threading.current_thread().name, "device", "device",
            trace_id, fid, 0, None))
        with self._cv:
            if len(self._q) >= self._cap:
                self._dropped += 1
                self._drop_counter.inc(1, pipeline=self._pipeline.name)
                return
            self._sent += 1
            with _inflight_lock:
                _inflight[pid] = (t0_ns, node.name)
            # the filter's dispatch count: what joins this completion to
            # the round's <filter>.invoke span without guessing from order
            self._q.append((pid, node.name, head, t0_ns, trace_id, parent,
                            fid, cost_key, getattr(node, "dispatches", 0)))
            self._cv.notify()

    def _on_compile(self, backend, key, result, dur_ns, info) -> None:
        del backend, key, dur_ns
        with self._lock:
            self._compiles[result] = self._compiles.get(result, 0) + 1
            if result == "miss" and info:
                self._last_compile = dict(info)

    # -- the reaper ----------------------------------------------------------

    def _reap(self) -> None:
        pipeline_name = self._pipeline.name
        while True:
            with self._cv:
                while self._running and not self._q:
                    self._cv.wait(0.5)
                if not self._running and not self._q:
                    return
                (pid, name, head, t0, trace_id, parent, fid,
                 cost_key, round_id) = self._q.popleft()
            try:
                shards = _mesh_shards(head)
                if shards is not None:
                    dur = self._reap_sharded(
                        shards, name, t0, trace_id, parent, fid,
                        pipeline_name, cost_key, round_id)
                else:
                    try:
                        import jax

                        jax.block_until_ready(head)
                    except ImportError:  # pragma: no cover
                        bur = getattr(head, "block_until_ready", None)
                        if bur is not None:
                            bur()
                    t_done = now_ns()
                    dur = max(0, t_done - t0)
                    label = _head_device_label(head)
                    track = threading.current_thread().name
                    sid = next(spans._ids)
                    args = {"element": name, "device": label,
                            "round": round_id}
                    args.update(self._utilization(
                        label, track, name, t0, dur, trace_id, parent,
                        cost_key, pipeline_name))
                    # both records land on THIS thread: the device track
                    spans._recorder.append((
                        spans.PH_FLOW_END, t0, 0, track, "device", "device",
                        trace_id, fid, 0, None))
                    spans._recorder.append((
                        spans.PH_COMPLETE, t0, dur, track, "device_exec",
                        "device", trace_id, sid, parent, args))
                    self._hist.observe(dur / 1e9, pipeline=pipeline_name,
                                       element=name, device=label)
                self._dispatches.inc(1, pipeline=pipeline_name, element=name)
                if _profiling.enabled():
                    # the per-node latency of Pipeline.stats(): enqueue ->
                    # done, observed here and never waited for in the
                    # dispatching thread
                    _profiling.record(name, dur)
                with self._lock:
                    self._completed += 1
                    c = self._by_element.setdefault(name, [0, 0])
                    c[0] += 1
                    c[1] += dur
            except Exception:  # noqa: BLE001 — a poison probe must not
                import logging  # kill the reaper

                logging.getLogger("nnstreamer_tpu.obs").exception(
                    "device completion probe failed for %s", name)
            finally:
                with _inflight_lock:
                    _inflight.pop(pid, None)
                # dispatcher lanes: a device completion is a lane wakeup
                # (idle lanes and backpressured producers re-poll now,
                # not on the next timeout tick) — never a blocked thread
                try:
                    from ..graph import lanes as _lanes

                    _lanes.device_wakeup()
                except Exception:  # noqa: BLE001 — observability only
                    pass

    def _reap_sharded(self, shards, name, t0, trace_id, parent, fid,
                      pipeline_name, cost_key=None, round_id=0) -> int:
        """Per-mesh-device completion for a sharded dispatch: each shard's
        readiness is observed individually and recorded on its OWN
        ``device:<platform>:<ordinal>`` Perfetto track (the recorder keys
        rows by the tid string, not the OS thread, so one reaper thread
        fans out to ndev rows) with a per-device
        ``nnstpu_device_exec_seconds{device=...}`` observation — shard
        skew shows up as differing span lengths side by side.  The
        executable's cost_analysis() covers the WHOLE mesh program, so
        each shard is attributed flops/ndev for its MFU.  Returns the
        whole-dispatch duration (= the slowest shard observed)."""
        flow_done = False
        dur = 0
        nshards = max(1, len(shards))
        for label, _ordinal, data in shards:
            wait = getattr(data, "block_until_ready", None)
            if wait is not None:
                wait()
            t_done = now_ns()
            shard_dur = max(0, t_done - t0)
            dur = max(dur, shard_dur)
            track = f"device:{label}"
            if not flow_done:
                # the host dispatch span's flow arrow lands on the first
                # shard's track (one arrow per dispatch, ndev spans)
                spans._recorder.append((
                    spans.PH_FLOW_END, t0, 0, track, "device", "device",
                    trace_id, fid, 0, None))
                flow_done = True
            sid = next(spans._ids)
            args = {"element": name, "device": label, "round": round_id}
            args.update(self._utilization(
                label, track, name, t0, shard_dur, trace_id, parent,
                cost_key, pipeline_name, nshards=nshards))
            spans._recorder.append((
                spans.PH_COMPLETE, t0, shard_dur, track, "device_exec",
                "device", trace_id, sid, parent, args))
            self._hist.observe(shard_dur / 1e9, pipeline=pipeline_name,
                               element=name, device=label)
        return dur

    # -- utilization attribution ---------------------------------------------

    def _utilization(self, label, track, name, t0, dur, trace_id, parent,
                     cost_key, pipeline_name, nshards: int = 1) -> dict:
        """Per-dispatch efficiency attribution for one device: roofline
        args for the ``device_exec`` span, the ``nnstpu_mfu`` gauge and
        roofline counter, the busy-interval feed, the ``device_idle``
        gap span when the device sat starved since its last observed
        completion, and the by-device aggregates.  Cost-less dispatches
        (no registered flops) still count everywhere, with ``mfu: None``
        — throughput accounting stays exact.  Never raises."""
        extra: dict = {}
        try:
            t_done = t0 + dur
            info = _util.cost_of(cost_key)
            flops = bytes_ = None
            bucket = 0
            if info is not None:
                bucket = int(info.get("bucket") or 0)
                flops = info.get("flops")
                bytes_ = info.get("bytes")
                if flops:
                    flops = flops / nshards
                if bytes_:
                    bytes_ = bytes_ / nshards
                extra["cost_key"] = cost_key
                if flops:
                    extra["flops"] = flops
                if bytes_:
                    extra["bytes"] = bytes_
            rl = _util.roofline(flops, bytes_, dur / 1e9,
                                self._peak_tf, self._peak_gb)
            sig = lambda v: float(f"{v:.4g}")  # noqa: E731 — 4 significant
            extra["mfu"] = sig(rl["mfu"]) if rl["mfu"] is not None else None
            extra["roofline"] = rl["bound"]
            if rl["achieved_tflops"] is not None:
                extra["achieved_tflops"] = sig(rl["achieved_tflops"])
            if rl["achieved_gbs"] is not None:
                extra["achieved_gbs"] = sig(rl["achieved_gbs"])
            if rl["intensity"] is not None:
                extra["intensity"] = sig(rl["intensity"])
            if rl["mfu"] is not None:
                self._mfu_gauge.set(rl["mfu"], device=label, node=name,
                                    bucket=str(bucket))
            self._bound_counter.inc(1, pipeline=pipeline_name, device=label,
                                    bound=rl["bound"])
            # dead-time accounting: a gap since this device's last
            # observed completion >= [obs] device_idle_gap_ms becomes a
            # device_idle span on its track, attributed to the waiting
            # dispatch's trace so Perfetto shows WHY the chip starved
            prev = self._last_end.get(label)
            if prev is not None and t0 - prev[0] >= self._idle_gap_ns:
                gap = t0 - prev[0]
                wire = _util.last_wire_health()
                if wire is not None and wire.get("regime") == "slow":
                    reason = "wire"
                elif prev[1]:
                    # nothing was enqueued when the device went idle: the
                    # host (dispatch path / upstream queue) starved it
                    reason = "host_dispatch"
                else:
                    reason = "queue_wait"
                spans._recorder.append((
                    spans.PH_COMPLETE, prev[0], gap, track, "device_idle",
                    "device", trace_id, next(spans._ids), parent,
                    {"device": label, "gap_ms": round(gap / 1e6, 3),
                     "reason": reason}))
            with self._cv:
                q_empty = not self._q
            self._last_end[label] = (t_done, q_empty)
            self._usage.add(label, t0, t_done)
            # set the busy gauge here too (windowed up to this
            # completion): the scrape-time collector keeps it fresh while
            # the tracer is live, this keeps the series present after
            # stop() removed the collector (CI scrapes after the run)
            frac = self._usage.busy_fractions(now_ns=t_done).get(label)
            if frac is not None:
                self._busy_gauge.set(round(frac, 6), device=label)
            with self._lock:
                d = self._by_device.setdefault(label, [0, 0, 0.0, 0])
                d[0] += 1
                d[1] += dur
                if flops:
                    d[2] += flops
                else:
                    d[3] += 1
            if _hooks.enabled:
                # the cost-model feed: one emission per observed shard
                # completion, carrying the same duration the device_exec
                # span records (so downstream aggregates reconcile with
                # the Perfetto trace by construction)
                info = {"bucket": bucket, "mesh": nshards}
                if cost_key:
                    # the join key the deep-profiling lane (fingerprint
                    # watch, DegradeDetector) keys its baselines by
                    info["cost_key"] = cost_key
                if flops:
                    info["flops"] = flops
                if bytes_:
                    info["bytes"] = bytes_
                if extra.get("mfu") is not None:
                    info["mfu"] = extra["mfu"]
                _hooks.emit("device_exec", pipeline_name, name, label,
                            t0, dur, info)
        except Exception:  # noqa: BLE001 — attribution must never kill a probe
            import logging

            logging.getLogger("nnstreamer_tpu.obs").exception(
                "utilization attribution failed for %s", name)
        return extra

    def _collect_busy(self) -> None:
        """Scrape-time collector: windowed busy fraction per device from
        observed device_exec coverage ([obs] busy_window_s)."""
        for device, frac in self._usage.busy_fractions().items():
            self._busy_gauge.set(round(frac, 6), device=device)

    def summary(self) -> dict:
        with self._cv:
            inflight = len(self._q)
        busy = self._usage.busy_fractions()
        peak_tf = getattr(self, "_peak_tf", None) or _util.peak_tflops()
        with self._lock:
            per = {name: {"count": c[0], "device_ns": c[1]}
                   for name, c in self._by_element.items()}
            per_dev = {}
            for label, c in self._by_device.items():
                count, ns, flops_sum, missing = c[0], c[1], c[2], c[3]
                # aggregate MFU over the device's observed busy time;
                # None (not omission) when no dispatch carried cost info —
                # count/device_ns stay exact either way
                mfu = None
                if flops_sum and ns > 0 and peak_tf:
                    mfu = float(
                        f"{flops_sum / (ns / 1e9) / (peak_tf * 1e12):.4g}")
                entry = {"count": count, "device_ns": ns, "mfu": mfu,
                         "cost_missing": missing}
                frac = busy.get(label)
                if frac is not None:
                    entry["busy_fraction"] = round(frac, 4)
                per_dev[label] = entry
            total_ns = sum(c[1] for c in self._by_element.values())
            out = {
                "dispatches": self._sent,
                "completed": self._completed,
                "dropped": self._dropped,
                "inflight": inflight,
                "device_ns": total_ns,
                "by_element": per,
                "by_device": per_dev,
                "compiles": dict(self._compiles),
            }
            if self._last_compile:
                out["last_compile"] = dict(self._last_compile)
        return out


# self-registration (obs/__init__ imports this module, so
# NNSTPU_TRACERS=device / attach_tracer("device") always resolve)
from .tracers import TRACERS  # noqa: E402

TRACERS[DeviceTracer.name] = DeviceTracer
