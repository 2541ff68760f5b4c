"""Per-node timing + jax.profiler integration.

The reference documents external tracing tools (gst-instruments/HawkTracer,
``tools/profiling/README.md``) and per-element GST debug categories; here
profiling is built in: a process-global registry of per-node invoke
latencies, toggled at runtime, plus helpers to bracket regions with
``jax.profiler`` traces.

Nothing here waits for the device: a pipeline that starts with profiling
enabled starts the device lane (:mod:`nnstreamer_tpu.obs.device`), whose
reaper thread feeds :func:`record` with each filter dispatch's enqueue →
completion time; ``Pipeline.stop()`` drains it.

Recorded invoke latencies are additionally folded into the observability
metrics registry (:mod:`nnstreamer_tpu.obs.metrics`) as the
``nnstpu_node_invoke_latency_ms`` histogram, so enabling profiling makes
per-node latencies scrapeable from the Prometheus endpoint alongside the
tracer metrics.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Sequence

_enabled = False
_lock = threading.Lock()
_records: Dict[str, List[int]] = {}


def enable(on: bool = True) -> None:
    global _enabled
    _enabled = on


def enabled() -> bool:
    return _enabled


def record(node_name: str, duration_ns: int) -> None:
    with _lock:
        _records.setdefault(node_name, []).append(duration_ns)
    # re-home onto the obs registry: get-or-create is idempotent, so this
    # survives registry resets between test runs
    from ..obs.metrics import REGISTRY

    REGISTRY.histogram(
        "nnstpu_node_invoke_latency_ms",
        "Per-node invoke latency (milliseconds), recorded while profiling "
        "is enabled",
        labelnames=("node",),
    ).observe(duration_ns / 1e6, node=node_name)


def summarize_ns(ns: Sequence[int]) -> Dict[str, float]:
    """Latency summary (ms) of a sample of nanosecond durations.

    Percentiles use **ceil-based nearest rank** — ``s[ceil(q*n) - 1]`` —
    so p99 is the smallest value ≥ 99% of the sample.  The previous
    ``s[min(n-1, int(n*0.99))]`` floor-rank returned the MAX for every
    n ≤ 100, biasing small-sample p99 upward by the full tail.
    """
    # the shared ceil-rank implementation (imported lazily: obs.tracers
    # imports this module at its own import time, same as record())
    from ..obs.metrics import quantile_rank

    s = sorted(ns)
    n = len(s)
    return {
        "count": n,
        "mean_ms": sum(s) / n / 1e6,
        "p50_ms": quantile_rank(s, 0.50) / 1e6,
        "p90_ms": quantile_rank(s, 0.90) / 1e6,
        "p99_ms": quantile_rank(s, 0.99) / 1e6,
        "min_ms": s[0] / 1e6,
        "max_ms": s[-1] / 1e6,
    }


def stats() -> Dict[str, Dict[str, float]]:
    """Per-node latency summary in milliseconds."""
    with _lock:
        snap = {name: list(ns) for name, ns in _records.items() if ns}
    return {name: summarize_ns(ns) for name, ns in snap.items()}


def reset() -> None:
    with _lock:
        _records.clear()


@contextlib.contextmanager
def profiled():
    """Context manager: enable, yield, restore."""
    prev = _enabled
    enable(True)
    try:
        yield
    finally:
        enable(prev)


@contextlib.contextmanager
def device_trace(logdir: str):
    """Capture an XLA/TPU xplane trace (jax.profiler) around a region.

    Routed through the deep-profiling lane's process-wide capture lock
    (obs/profiler.py) so a concurrent capture raises its typed
    ``ProfileBusyError`` instead of jax's opaque double-start crash; the
    raw artifacts land under the caller's ``logdir`` as before."""
    from ..obs.profiler import profiled_window

    with profiled_window(label="device_trace", logdir=logdir,
                         trigger="manual", parse=False):
        yield
