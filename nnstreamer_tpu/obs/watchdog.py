"""Pipeline health watchdog: stalled sources, wedged queues, overdue
device dispatches.

A streaming pipeline fails silent more often than it fails loud: a
source that blocks in its own iterator, a queue whose consumer wedged (a
deadlocked downstream, a backend stuck on a sick device link), a device
dispatch that never completes.  None of those post an error — the graph
just stops moving.  The watchdog (``NNSTPU_TRACERS=watchdog`` or
``pipeline.attach_tracer("watchdog")``) turns "stopped moving" into a
first-class, observable state:

- a monitor thread ticks every ``[obs] watchdog_interval`` seconds and
  checks, per pipeline: **stalled sources** (streaming thread alive but
  no ``source_push`` within ``watchdog_stall_s``), **wedged queues**
  (depth at/above ``watchdog_queue_depth`` with no pop for the stall
  window), and **overdue device work** (a dispatch whose completion the
  :class:`~.device.DeviceTracer` has not observed within
  ``watchdog_device_deadline_s``);
- an unhealthy verdict flips the pipeline's health state: the
  ``nnstpu_health`` gauge drops to 0, ``/healthz`` on the metrics server
  turns 503 with the reason (:func:`~.export.register_health`), a
  ``health`` hook event fires for other tracers, a span instant lands in
  the flight recorder, and the pipeline writes an automatic flight dump
  (``{name}.stall.trace.json`` in ``[obs] flight_dump_dir``) — the same
  black-box readout ``post_error`` produces, for hangs instead of
  crashes;
- recovery (frames moving again) flips everything back and fires the
  hook again, so flapping is visible too;
- with ``recover=True`` (conf ``[obs] watchdog_recover``) detection
  escalates to **self-healing**: restart the stalled source, drain the
  wedged queue (+ respawn a dead worker), trip the circuit breakers for
  an overdue device — each attempt budget-capped per target and counted
  in ``nnstpu_recovery_total{action,result}`` (see
  ``docs/robustness.md``).

A posted pipeline error also marks the pipeline unhealthy — a crashed
graph should never answer ``/healthz`` with 200.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from . import spans
from .export import register_health, unregister_health
from .metrics import MetricsRegistry
from .tracers import Tracer

now_ns = time.perf_counter_ns

DEFAULT_INTERVAL_S = 1.0
DEFAULT_STALL_S = 5.0
DEFAULT_QUEUE_DEPTH = 1
DEFAULT_DEVICE_DEADLINE_S = 30.0


class PipelineWatchdog(Tracer):
    name = "watchdog"

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 interval_s: Optional[float] = None,
                 stall_s: Optional[float] = None,
                 queue_depth: Optional[int] = None,
                 device_deadline_s: Optional[float] = None,
                 recover: Optional[bool] = None,
                 recover_budget: Optional[int] = None):
        """``recover=True`` (or conf ``[obs] watchdog_recover``) escalates
        detection into recovery: a stalled source is restarted
        (:meth:`Pipeline.restart_source`), a wedged queue is drained +
        its worker respawned (:meth:`Pipeline.recover_queue`), and an
        overdue device dispatch trips every live circuit breaker
        (:func:`nnstreamer_tpu.sched.breaker.trip_all`) so the serving
        edge sheds typed errors instead of queueing behind the wedge.
        At most ``recover_budget`` attempts per (kind, target) while
        unhealthy — budgets reset when health recovers, so a flapping
        target can be rescued again but never restart-stormed."""
        super().__init__(registry)
        self._interval = interval_s
        self._stall = stall_s
        self._depth_threshold = queue_depth
        self._device_deadline = device_deadline_s
        self._recover = recover
        self._recover_budget = recover_budget
        self._recover_attempts: Dict[tuple, int] = {}
        self._recoveries = 0
        self._lock = threading.Lock()
        self._src_last: Dict[str, int] = {}     # source -> last push ts_ns
        self._q_state: Dict[str, List[int]] = {}  # queue -> [depth, last_pop]
        self._healthy = True
        self._reasons: List[str] = []
        self._checks = 0
        self._transitions = 0
        self._stop_evt = threading.Event()
        self._monitor: Optional[threading.Thread] = None
        self._health_fn = None
        # deep-profiling auto-capture: armed at install when [obs]
        # profile_auto is on (the conf read happens there, not here, so
        # attach-then-start picks up late env changes)
        self._profile_auto = False
        self._profile_detector = None
        self._auto_captures = 0

    # -- lifecycle -----------------------------------------------------------

    def _conf_float(self, key: str, default: float) -> float:
        from ..conf import conf

        try:
            return conf.get_float("obs", key, default)
        except ValueError:
            return default

    def _install(self) -> None:
        from ..conf import conf

        if self._interval is None:
            self._interval = self._conf_float(
                "watchdog_interval", DEFAULT_INTERVAL_S)
        if self._stall is None:
            self._stall = self._conf_float("watchdog_stall_s",
                                           DEFAULT_STALL_S)
        if self._depth_threshold is None:
            try:
                self._depth_threshold = conf.get_int(
                    "obs", "watchdog_queue_depth", DEFAULT_QUEUE_DEPTH)
            except ValueError:
                self._depth_threshold = DEFAULT_QUEUE_DEPTH
        if self._device_deadline is None:
            self._device_deadline = self._conf_float(
                "watchdog_device_deadline_s", DEFAULT_DEVICE_DEADLINE_S)
        if self._recover is None:
            try:
                self._recover = conf.get_bool("obs", "watchdog_recover",
                                              False)
            except ValueError:
                self._recover = False
        if self._recover_budget is None:
            try:
                self._recover_budget = conf.get_int(
                    "obs", "watchdog_recover_budget", 3)
            except ValueError:
                self._recover_budget = 3
        # >0: spot-check the host->device wire every this many seconds
        # and publish it live (obs/util.py nnstpu_wire_* gauges + the
        # wire_health stats provider), so a slow wire is visible on
        # /metrics DURING serving
        self._wire_probe_s = self._conf_float("watchdog_wire_probe_s", 0.0)
        self._last_wire_probe = 0.0
        # [obs] profile_auto: when a dispatch's device time degrades
        # beyond the perfdiff noise band, auto-trigger a deep-profiling
        # capture (obs/profiler.py) so the regression's op-level evidence
        # is banked while the regression is still happening — at most
        # one capture per profile_auto_cooldown_s
        self._profile_auto = False
        self._profile_detector = None
        self._profile_auto_s = self._conf_float("profile_auto_seconds", 1.0)
        self._profile_cooldown_s = self._conf_float(
            "profile_auto_cooldown_s", 120.0)
        self._last_auto_profile = 0.0
        self._auto_captures = 0
        try:
            self._profile_auto = conf.get_bool("obs", "profile_auto", False)
        except ValueError:
            self._profile_auto = False
        if self._profile_auto:
            from .profiler import DegradeDetector

            self._profile_detector = DegradeDetector()
            self._connect("device_exec",
                          self._profile_detector.on_device_exec)
        self._gauge = self._registry.gauge(
            "nnstpu_health",
            "Pipeline health as judged by the watchdog (1 healthy, "
            "0 unhealthy)",
            labelnames=("pipeline",),
        )
        self._stall_counter = self._registry.counter(
            "nnstpu_watchdog_stalls_total",
            "Health flips to unhealthy, by reason kind",
            labelnames=("pipeline", "kind"),
        )
        self._gauge.set(1, pipeline=self._pipeline.name)
        # health instants / stall dumps need the flight recorder live even
        # when the watchdog is the only tracer attached
        spans._activate(spans.configured_flight_records())
        self._connect("source_spawn", self._on_source_spawn)
        self._connect("source_push", self._on_source_push)
        self._connect("queue_push", self._on_queue_push)
        self._connect("queue_pop", self._on_queue_pop)
        self._connect("error", self._on_error)
        # hold ONE bound-method object: unregister compares by identity,
        # and every `self.health` attribute access creates a fresh one
        self._health_fn = self.health
        register_health(self._pipeline.name, self._health_fn)
        self._stop_evt.clear()
        self._monitor = threading.Thread(
            target=self._run, name=f"watchdog:{self._pipeline.name}",
            daemon=True)
        self._monitor.start()

    def stop(self) -> None:
        was_active = bool(self._conns)
        super().stop()
        if not was_active:
            return
        self._stop_evt.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5)
            self._monitor = None
        unregister_health(self._pipeline.name, self._health_fn)
        spans._deactivate()

    # -- hook callbacks ------------------------------------------------------

    def _on_source_spawn(self, pipeline, node) -> None:
        if pipeline is self._pipeline:
            with self._lock:
                self._src_last[node.name] = now_ns()

    def _on_source_push(self, pipeline, node, frame) -> None:
        del frame
        if pipeline is self._pipeline:
            with self._lock:
                self._src_last[node.name] = now_ns()

    def _on_queue_push(self, node, depth) -> None:
        if node.pipeline is self._pipeline:
            with self._lock:
                st = self._q_state.setdefault(node.name, [0, now_ns()])
                st[0] = depth

    def _on_queue_pop(self, node, depth) -> None:
        if node.pipeline is self._pipeline:
            with self._lock:
                self._q_state[node.name] = [depth, now_ns()]

    def _on_error(self, pipeline, node, exc) -> None:
        if pipeline is self._pipeline:
            self._flip(
                [f"error:{node.name if node else '?'}: {exc!r}"],
                dump=False)  # post_error already wrote its own flight dump

    # -- the monitor ---------------------------------------------------------

    def _source_thread_alive(self, name: str) -> bool:
        # the pipeline knows the execution substrate (streaming thread
        # vs dispatcher-lane task); older pipeline objects without the
        # helper fall back to the thread-name check
        alive = getattr(self._pipeline, "source_alive", None)
        if alive is not None:
            return alive(name)
        return any(t.name == f"src:{name}" and t.is_alive()
                   for t in self._pipeline.threads)

    def _evaluate(self) -> List[str]:
        now = now_ns()
        stall_ns = int(self._stall * 1e9)
        reasons: List[str] = []
        with self._lock:
            src = dict(self._src_last)
            queues = {k: list(v) for k, v in self._q_state.items()}
        for name, last in src.items():
            if now - last > stall_ns and self._source_thread_alive(name):
                reasons.append(
                    f"stalled_source:{name}: no frame for "
                    f"{(now - last) / 1e9:.1f}s")
        for name, (depth, last_pop) in queues.items():
            if depth >= self._depth_threshold and now - last_pop > stall_ns:
                reasons.append(
                    f"wedged_queue:{name}: depth {depth}, no pop for "
                    f"{(now - last_pop) / 1e9:.1f}s")
        from .device import oldest_inflight

        oldest = oldest_inflight()
        if oldest is not None:
            t0, element = oldest
            age = (now - t0) / 1e9
            if age > self._device_deadline:
                reasons.append(
                    f"overdue_device:{element}: dispatch executing for "
                    f"{age:.1f}s")
        return reasons

    def _run(self) -> None:
        while not self._stop_evt.wait(self._interval):
            if self._pipeline.state != "PLAYING":
                continue
            with self._lock:
                self._checks += 1
            if (self._wire_probe_s > 0
                    and time.monotonic() - self._last_wire_probe
                    >= self._wire_probe_s):
                self._last_wire_probe = time.monotonic()
                from . import util as _util

                try:
                    _util.publish_wire_health(
                        _util.probe_wire_health(n=4), self._registry)
                except Exception:  # noqa: BLE001 — a failed probe must
                    pass           # never flag health or kill the monitor
                # partition edges re-probe on the same cadence: a remote
                # link's regime flip is what triggers repartitioning, so
                # it must be observed, not polled by the planner
                for addr, prober in _util.wire_edges().items():
                    try:
                        _util.publish_wire_health(
                            prober(), self._registry, addr=addr)
                    except Exception:  # noqa: BLE001 — a dead edge is
                        pass           # the deployer's problem, not ours
            if self._profile_detector is not None:
                verdict = self._profile_detector.degraded()
                if (verdict
                        and time.monotonic() - self._last_auto_profile
                        >= self._profile_cooldown_s):
                    self._last_auto_profile = time.monotonic()
                    self._auto_capture(verdict)
            try:
                reasons = self._evaluate()
            except Exception:  # noqa: BLE001 — the monitor must survive
                import logging

                logging.getLogger("nnstreamer_tpu.obs").exception(
                    "watchdog evaluation failed")
                continue
            if reasons:
                self._flip(reasons)
                if self._recover:
                    try:
                        self._attempt_recovery(reasons)
                    except Exception:  # noqa: BLE001 — the monitor survives
                        import logging

                        logging.getLogger("nnstreamer_tpu.obs").exception(
                            "watchdog recovery failed")
            else:
                self._recovered()

    def _auto_capture(self, verdict: str) -> None:
        """Spawn one watchdog-triggered deep-profiling window in the
        background (the monitor tick must not block for the capture);
        a capture already in flight (typed busy) simply skips — the
        cooldown clock has been stamped either way."""
        import logging

        logging.getLogger("nnstreamer_tpu.obs").warning(
            "watchdog: device-time degradation (%s) — auto-triggering "
            "profile capture", verdict)

        def run():
            from . import profiler

            try:
                profiler.capture_profile(
                    seconds=self._profile_auto_s, pipeline=self._pipeline,
                    trigger="watchdog", registry=self._registry)
                with self._lock:
                    self._auto_captures += 1
            except profiler.ProfileBusyError:
                pass
            except Exception:  # noqa: BLE001 — the capture is best-effort
                logging.getLogger("nnstreamer_tpu.obs").exception(
                    "watchdog auto-capture failed")

        threading.Thread(target=run, daemon=True,
                         name=f"wd-profile:{self._pipeline.name}").start()

    def _flip(self, reasons: List[str], dump: bool = True) -> None:
        with self._lock:
            first = self._healthy
            self._healthy = False
            self._reasons = list(reasons)
            if first:
                self._transitions += 1
        if not first:
            return
        import logging

        from . import hooks as _hooks

        name = self._pipeline.name
        logging.getLogger("nnstreamer_tpu.obs").warning(
            "watchdog: pipeline %r unhealthy: %s", name, "; ".join(reasons))
        self._gauge.set(0, pipeline=name)
        for r in reasons:
            self._stall_counter.inc(
                1, pipeline=name, kind=r.split(":", 1)[0])
        spans.record_instant("watchdog_unhealthy", cat="health",
                             trace=(0, 0), args={"reasons": reasons})
        if _hooks.enabled:
            _hooks.emit("health", self._pipeline, False, "; ".join(reasons))
        if dump:
            # same black-box readout post_error writes, for hangs
            self._pipeline._dump_flight("stall")

    def _attempt_recovery(self, reasons: List[str]) -> None:
        """Escalation: one recovery action per unhealthy reason, budget-
        capped per (kind, target).  Outcomes land on the shared
        ``nnstpu_recovery_total`` counter via the pipeline's recovery
        methods; the breaker-trip path records its own."""
        from . import recovery as _recovery

        for r in reasons:
            kind, _, rest = r.partition(":")
            target = rest.partition(":")[0]
            key = (kind, target)
            with self._lock:
                attempts = self._recover_attempts.get(key, 0)
                if attempts >= self._recover_budget:
                    continue
                self._recover_attempts[key] = attempts + 1
                self._recoveries += 1
            if kind == "stalled_source":
                self._pipeline.restart_source(target)
            elif kind == "wedged_queue":
                self._pipeline.recover_queue(target)
            elif kind == "overdue_device":
                from ..sched.breaker import trip_all

                n = trip_all(reason=r)
                _recovery.record(self._pipeline.name, "breaker_trip",
                                 "ok" if n else "error", target,
                                 f"tripped={n}")

    def _recovered(self) -> None:
        with self._lock:
            if self._healthy:
                return
            self._healthy = True
            self._reasons = []
            self._transitions += 1
            # fresh budgets: a later re-wedge of the same target may be
            # rescued again (flap accounting stays in _transitions)
            self._recover_attempts.clear()
        from . import hooks as _hooks

        self._gauge.set(1, pipeline=self._pipeline.name)
        spans.record_instant("watchdog_recovered", cat="health",
                             trace=(0, 0), args=None)
        if _hooks.enabled:
            _hooks.emit("health", self._pipeline, True, "")

    # -- readouts ------------------------------------------------------------

    def health(self):
        """(healthy, reason) — the /healthz provider contract."""
        with self._lock:
            return self._healthy, "; ".join(self._reasons)

    def summary(self) -> dict:
        from .export import degraded_snapshot

        with self._lock:
            out = {
                "healthy": self._healthy,
                "reasons": list(self._reasons),
                "checks": self._checks,
                "transitions": self._transitions,
                "sources": len(self._src_last),
                "queues": len(self._q_state),
                "recover": bool(self._recover),
                "recoveries": self._recoveries,
            }
            if self._profile_auto:
                out["profile_auto"] = {
                    "captures": self._auto_captures,
                    "verdicts": (self._profile_detector.verdicts
                                 if self._profile_detector else 0),
                }
        # degraded-but-serving reasons (e.g. a cpu-fallback backend) ride
        # the watchdog's summary too: stats.json readers see WHY a worker
        # is deprioritized without scraping /healthz separately
        degraded = degraded_snapshot()
        if degraded:
            out["degraded"] = degraded
        # last published wire-health probe: the wire regime next to the
        # health verdict it often explains
        from .util import last_wire_health

        wire = last_wire_health()
        if wire is not None:
            out["wire"] = wire
        return out


from .tracers import TRACERS  # noqa: E402

TRACERS[PipelineWatchdog.name] = PipelineWatchdog
