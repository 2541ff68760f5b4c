"""Device *utilization* lane: live MFU / roofline attribution.

The device lane (:mod:`.device`) answers "how long did each dispatch
execute"; this module turns those durations into *efficiency*: was the
chip busy, idle, compute-bound or wire-starved — the instrument panel
the on-chip performance campaign (ROADMAP item 1, TVM's measure→search→
cache→serve discipline) steers by.

- **Per-executable cost registry** — ``backends/jax_backend.py`` calls
  :func:`register_cost` once per compiled entry with the executable's
  ``cost_analysis()`` flops/bytes (keyed by a per-process executable
  fingerprint); the :class:`~.device.DeviceTracer` reaper looks the key
  back up per dispatch and computes achieved-TFLOPs / achieved-GB/s /
  MFU for the ``nnstpu_mfu{device,node,bucket}`` gauge and the
  ``device_exec`` span args.
- **Roofline math** — :func:`roofline` classifies an executable by
  arithmetic intensity against the device's ridge point
  (``compute_bound`` / ``bandwidth_bound``); peaks come from one table
  keyed by ``device_kind`` (:data:`DEVICE_PEAKS`).  A device that is not
  in the table — every CPU host included — has no peak, so its
  dispatches carry ``mfu=None`` + ``bound="unknown"``, as do
  synthetic/partial payloads (zero or missing flops) — never an
  exception, never a silent drop, never an assumed default.
- **Dead-time accounting** — :func:`merge_intervals` /
  :func:`busy_fraction` / :func:`idle_gaps` compute windowed busy/idle
  coverage from ``device_exec`` span intervals (overlapping multi-device
  spans merge per device); :class:`DeviceUsage` is the bounded
  per-device interval store behind
  ``nnstpu_device_busy_fraction{device}``.
- **Wire health as live metrics** — :func:`probe_wire_health` is the
  single implementation of the 150 KB host→device put spot-check;
  :func:`publish_wire_health` republishes any probe (the local wire or a
  partition edge) as ``nnstpu_wire_put_ms`` / ``nnstpu_wire_dispatch_ms``
  / ``nnstpu_wire_regime`` gauges plus a ``wire_health`` stats provider,
  so a slow wire is visible on ``/metrics`` during serving (the watchdog
  can probe on an interval — ``[obs] watchdog_wire_probe_s``).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from .metrics import REGISTRY, MetricsRegistry

# -- peaks --------------------------------------------------------------------

# Published per-chip peaks, the denominators of MFU and the ridge point,
# keyed by ``jax.devices()[0].device_kind``.  A kind without a row has no
# MFU and no roofline share — never a default.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    # TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16
    # (393 TOP/s int8), 16 GB HBM at 819 GB/s per chip
    "TPU v5 lite": {"tflops": 197.0, "gbs": 819.0},
}

WIRE_SLOW_PUT_MS = 5.0  # >5 ms per 150 KB put = the slow wire regime


def _peak(field: str, kind: Optional[str]) -> Optional[float]:
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    return DEVICE_PEAKS.get(kind, {}).get(field)


def peak_tflops(kind: Optional[str] = None) -> Optional[float]:
    """Peak bf16 compute in TFLOP/s of ``kind`` (default: this process's
    first device), or None for a device the table does not know."""
    return _peak("tflops", kind)


def peak_gbs(kind: Optional[str] = None) -> Optional[float]:
    """Peak HBM bandwidth in GB/s of ``kind``, or None (see
    :func:`peak_tflops`)."""
    return _peak("gbs", kind)


# -- per-executable cost registry ---------------------------------------------

_COST_CAP = 256  # executables are LRU-bounded per backend; this bounds all

_cost_lock = threading.Lock()
_costs: "OrderedDict[str, dict]" = OrderedDict()


def register_cost(key: str, flops: Optional[float] = None,
                  bytes: Optional[float] = None, **meta) -> str:
    """Record one compiled executable's cost profile under ``key`` (the
    backend's executable fingerprint).  ``flops``/``bytes`` may be None
    or 0 — CPU hosts and fused wrappers sometimes expose neither; the
    entry still registers so every dispatch resolves to *something* and
    cost-less executables show up as ``mfu=None`` instead of vanishing
    from the efficiency view.  Returns ``key``."""
    entry = dict(meta)
    entry["flops"] = float(flops) if flops else None
    entry["bytes"] = float(bytes) if bytes else None
    with _cost_lock:
        _costs[key] = entry
        _costs.move_to_end(key)
        while len(_costs) > _COST_CAP:
            _costs.popitem(last=False)
    return key


def cost_of(key: Optional[str]) -> Optional[dict]:
    """The registered cost profile for ``key``, or None."""
    if not key:
        return None
    with _cost_lock:
        entry = _costs.get(key)
        return dict(entry) if entry is not None else None


def clear_costs() -> None:
    """Drop every registered cost profile (test isolation)."""
    with _cost_lock:
        _costs.clear()


def cost_entries() -> Dict[str, dict]:
    """Every registered cost profile, keyed by executable fingerprint
    (entries are copies).  The deep-profiling lane reads this to join
    XPlane op tables and build the per-executable HBM ledger."""
    with _cost_lock:
        return {k: dict(v) for k, v in _costs.items()}


# -- roofline math ------------------------------------------------------------

def roofline(flops: Optional[float], bytes_: Optional[float], dur_s: float,
             peak_tf: Optional[float] = None,
             peak_gb: Optional[float] = None) -> dict:
    """One dispatch on the roofline.

    Returns ``{achieved_tflops, achieved_gbs, mfu, intensity, ridge,
    bound}`` where ``bound`` is ``"compute_bound"`` / ``"bandwidth_bound"``
    / ``"unknown"``.  Peaks default to this device's :data:`DEVICE_PEAKS`
    row; without one (an unknown ``device_kind``) ``mfu`` and ``ridge``
    stay None and the bound ``"unknown"``.  Degenerate inputs (no
    duration, zero/missing flops) fill None + ``"unknown"`` instead of
    raising — the reaper calls this per dispatch and must never die on a
    flaky ``cost_analysis()``.  A bytes-only entry (flops absent, bytes known)
    is pure data movement and classifies ``bandwidth_bound``."""
    peak_tf = peak_tf if peak_tf is not None else peak_tflops()
    peak_gb = peak_gb if peak_gb is not None else peak_gbs()
    out: dict = {
        "achieved_tflops": None,
        "achieved_gbs": None,
        "mfu": None,
        "intensity": None,
        "ridge": round(peak_tf * 1e12 / (peak_gb * 1e9), 3)
        if peak_tf and peak_gb else None,
        "bound": "unknown",
    }
    try:
        dur_s = float(dur_s)
        flops = float(flops) if flops else None
        bytes_ = float(bytes_) if bytes_ else None
    except (TypeError, ValueError):
        return out
    if dur_s <= 0.0:
        return out
    if flops:
        out["achieved_tflops"] = flops / dur_s / 1e12
        if peak_tf:
            out["mfu"] = flops / dur_s / (peak_tf * 1e12)
    if bytes_:
        out["achieved_gbs"] = bytes_ / dur_s / 1e9
    if flops and bytes_:
        out["intensity"] = flops / bytes_
        if out["ridge"] is not None:
            out["bound"] = ("compute_bound"
                            if out["intensity"] >= out["ridge"]
                            else "bandwidth_bound")
    elif bytes_ and not flops:
        out["bound"] = "bandwidth_bound"
    return out


# -- busy/idle interval accounting --------------------------------------------

def merge_intervals(intervals: Iterable[Tuple[int, int]]
                    ) -> List[Tuple[int, int]]:
    """Union of ``(start, end)`` intervals, sorted and coalesced —
    overlapping spans (a mesh dispatch observed per shard, concurrent
    streams on one device) count their covered time once."""
    ivs = sorted((int(s), int(e)) for s, e in intervals if e > s)
    out: List[Tuple[int, int]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_fraction(intervals: Iterable[Tuple[int, int]], t0: int,
                  t1: int) -> Optional[float]:
    """Fraction of the window ``[t0, t1)`` covered by the (possibly
    overlapping) intervals; None for an empty window."""
    if t1 <= t0:
        return None
    covered = 0
    for s, e in merge_intervals(intervals):
        s, e = max(s, t0), min(e, t1)
        if e > s:
            covered += e - s
    return covered / (t1 - t0)


def idle_gaps(intervals: Iterable[Tuple[int, int]], min_gap: int,
              t0: Optional[int] = None, t1: Optional[int] = None
              ) -> List[Tuple[int, int]]:
    """``(start, duration)`` of every idle gap ≥ ``min_gap`` between the
    merged busy intervals (window edges included when ``t0``/``t1`` are
    given)."""
    merged = merge_intervals(intervals)
    gaps: List[Tuple[int, int]] = []
    if not merged:
        if t0 is not None and t1 is not None and t1 - t0 >= min_gap:
            gaps.append((t0, t1 - t0))
        return gaps
    if t0 is not None and merged[0][0] - t0 >= min_gap:
        gaps.append((t0, merged[0][0] - t0))
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        if s1 - e0 >= min_gap:
            gaps.append((e0, s1 - e0))
    if t1 is not None and t1 - merged[-1][1] >= min_gap:
        gaps.append((merged[-1][1], t1 - merged[-1][1]))
    return gaps


DEFAULT_BUSY_WINDOW_S = 10.0
DEFAULT_IDLE_GAP_MS = 5.0
DEFAULT_USAGE_CAP = 512


def configured_busy_window_s() -> float:
    """Sliding window for the busy-fraction gauge: ini ``[obs]
    busy_window_s`` (env ``NNSTPU_OBS_BUSY_WINDOW_S``)."""
    from ..conf import conf

    try:
        w = conf.get_float("obs", "busy_window_s", DEFAULT_BUSY_WINDOW_S)
    except ValueError:
        return DEFAULT_BUSY_WINDOW_S
    return w if w > 0 else DEFAULT_BUSY_WINDOW_S


def configured_idle_gap_ms() -> float:
    """Minimum device idle gap that becomes a ``device_idle`` flight
    span: ini ``[obs] device_idle_gap_ms``."""
    from ..conf import conf

    try:
        g = conf.get_float("obs", "device_idle_gap_ms", DEFAULT_IDLE_GAP_MS)
    except ValueError:
        return DEFAULT_IDLE_GAP_MS
    return g if g >= 0 else DEFAULT_IDLE_GAP_MS


class DeviceUsage:
    """Bounded per-device store of observed busy intervals.

    The :class:`~.device.DeviceTracer` reaper feeds one ``(enqueue,
    done)`` interval per observed dispatch (per shard under mesh
    dispatch); :meth:`busy_fractions` computes the sliding-window busy
    fraction per device at scrape time.  Intervals are host perf-counter
    nanoseconds — the same clock as every span.
    """

    def __init__(self, cap: int = DEFAULT_USAGE_CAP):
        self._cap = max(8, int(cap))
        self._lock = threading.Lock()
        self._by_device: Dict[str, deque] = {}

    def add(self, device: str, start_ns: int, end_ns: int) -> None:
        if end_ns <= start_ns:
            end_ns = start_ns + 1  # instantaneous completions still count
        with self._lock:
            dq = self._by_device.get(device)
            if dq is None:
                dq = self._by_device[device] = deque(maxlen=self._cap)
            dq.append((int(start_ns), int(end_ns)))

    def devices(self) -> List[str]:
        with self._lock:
            return sorted(self._by_device)

    def intervals(self, device: str) -> List[Tuple[int, int]]:
        with self._lock:
            return list(self._by_device.get(device, ()))

    def busy_fractions(self, window_ns: Optional[int] = None,
                       now_ns: Optional[int] = None) -> Dict[str, float]:
        """{device: busy fraction over the trailing window}.  The window
        is clipped to start no earlier than the oldest retained interval
        so a bounded ring never reads as idle time it simply forgot."""
        if window_ns is None:
            window_ns = int(configured_busy_window_s() * 1e9)
        now = now_ns if now_ns is not None else time.perf_counter_ns()
        out: Dict[str, float] = {}
        with self._lock:
            snap = {d: list(dq) for d, dq in self._by_device.items()}
        for device, ivs in snap.items():
            if not ivs:
                continue
            t0 = max(now - window_ns, min(s for s, _ in ivs))
            frac = busy_fraction(ivs, t0, now)
            if frac is not None:
                out[device] = frac
        return out

    def clear(self) -> None:
        with self._lock:
            self._by_device.clear()


# -- wire health: probes keyed per address, published live --------------------
#
# "local" is the host→device wire this process drives (the original
# single-probe surface); partition edges add remote addresses — the
# planner prices each cut at ITS edge's put rate, not a global regime.

LOCAL_WIRE_ADDR = "local"

_wire_lock = threading.Lock()
_wire_by_addr: Dict[str, dict] = {}
_wire_registered = False
# addr -> zero-arg prober (returns a probe_wire_health-shaped dict);
# the watchdog's re-probe loop walks these alongside the local probe
_wire_edges: Dict[str, Callable[[], dict]] = {}


def wire_regime(put_ms: Optional[float]) -> str:
    """``"fast"`` / ``"slow"`` classification of a 150 KB put time."""
    if put_ms is None:
        return "unknown"
    return "slow" if put_ms > WIRE_SLOW_PUT_MS else "fast"


def probe_wire_health(n: int = 20, nbytes: int = 150_528) -> dict:
    """Spot-check the host→device wire (150 KB flat put + dispatch
    rate) — the watchdog's optional serving-time probe."""
    import numpy as np

    import jax

    rng = np.random.default_rng(1)
    arrs = [rng.integers(0, 256, nbytes).astype(np.uint8) for _ in range(n)]
    t0 = time.perf_counter()
    ds = [jax.device_put(a) for a in arrs]
    jax.block_until_ready(ds)
    put_ms = (time.perf_counter() - t0) / n * 1e3
    t0 = time.perf_counter()
    for d in ds:
        out = d + 1
    out.block_until_ready()
    disp_ms = (time.perf_counter() - t0) / n * 1e3
    return {"put_150k_ms": round(put_ms, 3), "dispatch_ms": round(disp_ms, 3)}


def last_wire_health(addr: str = LOCAL_WIRE_ADDR) -> Optional[dict]:
    """The most recently published wire-health probe for ``addr`` (with
    its regime and timestamp), or None if that address was never probed
    this process.  Default: the local host→device wire — the shape every
    pre-partition caller relies on."""
    with _wire_lock:
        record = _wire_by_addr.get(addr)
        return dict(record) if record is not None else None


def wire_health_by_addr() -> Dict[str, dict]:
    """Every published probe keyed by address (``"local"`` plus any
    partition edges) — the planner's per-edge put-rate input."""
    with _wire_lock:
        return {addr: dict(rec) for addr, rec in _wire_by_addr.items()}


def register_wire_edge(addr: str, prober: Callable[[], dict]) -> None:
    """Register a remote edge's prober: the watchdog's wire re-probe
    walks every registered edge next to the local probe, so a flipping
    edge regime is observed without the planner polling."""
    with _wire_lock:
        _wire_edges[addr] = prober


def unregister_wire_edge(addr: str) -> None:
    with _wire_lock:
        _wire_edges.pop(addr, None)


def wire_edges() -> Dict[str, Callable[[], dict]]:
    """Snapshot of registered edge probers by address."""
    with _wire_lock:
        return dict(_wire_edges)


def _wire_stats() -> dict:
    """The ``wire_health`` stats provider: the local record's flat shape
    (unchanged from the single-probe era) plus an ``edges`` map when any
    remote edge has been probed."""
    by_addr = wire_health_by_addr()
    out = dict(by_addr.get(LOCAL_WIRE_ADDR) or {})
    edges = {a: r for a, r in by_addr.items() if a != LOCAL_WIRE_ADDR}
    if edges:
        out["edges"] = edges
    return out


def publish_wire_health(health: dict,
                        registry: Optional[MetricsRegistry] = None,
                        addr: str = LOCAL_WIRE_ADDR) -> dict:
    """Republish one wire-health probe as live gauges + stats provider.

    Sets ``nnstpu_wire_put_ms`` / ``nnstpu_wire_dispatch_ms`` /
    ``nnstpu_wire_regime`` (0 fast, 1 slow), all labeled by ``addr``
    (``"local"`` = the host→device wire; partition edges publish under
    their remote ``host:port``), and registers a ``wire_health``
    provider in ``/stats.json`` on first publish, so a slow wire is
    visible on any scrape.  Returns the stamped record."""
    global _wire_registered
    registry = registry if registry is not None else REGISTRY
    put_ms = health.get("put_150k_ms")
    regime = wire_regime(put_ms)
    record = dict(health)
    record["regime"] = regime
    record["probed_at"] = time.time()
    with _wire_lock:
        _wire_by_addr[addr] = record
        first = not _wire_registered
        _wire_registered = True
    if put_ms is not None:
        registry.gauge(
            "nnstpu_wire_put_ms",
            "Wire spot-check: ms per 150 KB flat put (addr: local = "
            "host-to-device, else a partition edge's host:port)",
            labelnames=("addr",),
        ).set(float(put_ms), addr=addr)
    if health.get("dispatch_ms") is not None:
        registry.gauge(
            "nnstpu_wire_dispatch_ms",
            "Wire spot-check: ms per trivial dispatch (by addr)",
            labelnames=("addr",),
        ).set(float(health["dispatch_ms"]), addr=addr)
    registry.gauge(
        "nnstpu_wire_regime",
        "Wire regime from the last spot-check (0 fast, 1 slow/sick), "
        "by addr",
        labelnames=("addr",),
    ).set(1.0 if regime == "slow" else 0.0, addr=addr)
    if first:
        from .export import register_stats

        register_stats("wire_health", _wire_stats)
    return dict(record)


def reset_wire_health() -> None:
    """Forget every probe, edge prober, and the provider registration
    (test isolation)."""
    global _wire_registered
    from .export import unregister_stats

    with _wire_lock:
        _wire_by_addr.clear()
        _wire_edges.clear()
        _wire_registered = False
    unregister_stats("wire_health")
