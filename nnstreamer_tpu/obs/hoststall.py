"""Host-stall lane: what the process was doing when a beat came late.

Every other record of the flight ring is written by a thread that works;
a stall of the whole host (a cgroup's CPU quota spent, a run queue that
does not let the process on, a page fault, the interpreter lock held in a
long C call) shows only as spans that are longer than they should be.
This lane is the witness:

- a **beat** thread sleeps ``BEAT_NS`` and, when it wakes more than
  ``LATE_NS`` late, writes one ``host_stall`` record (cat ``host``) into
  the ring: start = when it should have woken, duration = the lateness,
  args what the kernel knows of the beat: ``cpu_ms`` (``process_time_ns``
  over it: did any thread of the process run?), ``nivcsw``, ``majflt`` and
  ``minflt`` (``getrusage(RUSAGE_SELF)`` over it), ``run_delay_ms`` (the beat
  thread's own run-queue delay, ``/proc/thread-self/schedstat``'s second
  field; the main thread's where there is no ``thread-self``),
  ``throttled_ms`` and ``nr_throttled`` (the process's cgroup
  ``cpu.stat``, v2 or v1), ``steal_ms`` (``/proc/stat``) and
  ``psi_cpu_ms`` / ``psi_memory_ms`` / ``psi_io_ms`` (the machine's
  ``/proc/pressure``), each ``None`` where its file is not there, and
  ``cause``.  The files are read once a second for a baseline and at a
  late beat, never every beat;
- a garbage collection over ``GC_PAUSE_NS`` (``gc.callbacks``) writes a
  ``gc_pause`` record of the same cat with its ``generation``;
- both count into ``nnstpu_host_stalls_total{cause}`` and
  ``nnstpu_host_stall_seconds{cause}``: ``throttled`` where
  ``throttled_ms`` covers at least half the lateness, ``runqueue`` where
  ``run_delay_ms`` does, ``gc`` where a collection does, ``fault`` where
  ``majflt`` rose, else ``unknown`` (with ``cpu_ms`` near the lateness:
  a thread of the process held the interpreter lock).

No conf key and no tracer name: :class:`HostBeat` is a lane that
``Pipeline._attach_observability`` starts when the hook bus has a
listener, beside the device lane's reaper, and that dies with it.  One
beat serves every pipeline of the process.
"""

from __future__ import annotations

import gc
import os
import resource
import threading
import time
from typing import Optional, Tuple

from . import spans
from .metrics import REGISTRY

now_ns = time.perf_counter_ns

BEAT_NS = 20_000_000       # the beat's sleep
LATE_NS = 20_000_000       # a wake-up later than this is a stall
BASELINE_NS = 1_000_000_000  # how often the kernel's files are re-read
GC_PAUSE_NS = 2_000_000    # a collection longer than this is a record

_TICKS_PER_S = os.sysconf("SC_CLK_TCK")

STALL_BUCKETS_S = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)

_lock = threading.Lock()
_users = 0
_thread: Optional[threading.Thread] = None
_stop: Optional[threading.Event] = None
_gc_t0 = 0
_last_gc: Tuple[int, int] = (0, 0)  # the last recorded collection


def _metrics():
    return (
        REGISTRY.counter(
            "nnstpu_host_stalls_total",
            "Late beats of the host-stall lane and long garbage "
            "collections, by what the kernel's counters say caused them",
            labelnames=("cause",)),
        REGISTRY.histogram(
            "nnstpu_host_stall_seconds",
            "Length of a host stall (a beat's lateness, a collection's "
            "pause), seconds",
            labelnames=("cause",), buckets=STALL_BUCKETS_S),
    )


def _count(cause: str, dur_ns: int) -> None:
    total, seconds = _metrics()
    total.inc(1, cause=cause)
    seconds.observe(dur_ns / 1e9, cause=cause)


# -- what the kernel knows ---------------------------------------------------

def _read(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def _run_delay_ns() -> Optional[int]:
    """Nanoseconds the calling thread has waited on a run queue."""
    for path in ("/proc/thread-self/schedstat", "/proc/self/schedstat"):
        text = _read(path)
        try:
            return int(text.split()[1])
        except (AttributeError, IndexError, ValueError):
            continue
    return None


def _throttle(path: str) -> Optional[Tuple[int, int]]:
    """``(nr_throttled, throttled ns)`` of one ``cpu.stat``; None for a
    file that is not there or counts no throttling (a v2 group whose
    ``cpu`` controller is off)."""
    text = _read(path)
    if text is None:
        return None
    stat = dict(line.split()[:2] for line in text.splitlines()
                if len(line.split()) >= 2)
    try:
        if "throttled_usec" in stat:  # v2
            return int(stat["nr_throttled"]), int(stat["throttled_usec"]) * 1000
        return int(stat["nr_throttled"]), int(stat["throttled_time"])  # v1
    except (KeyError, ValueError):
        return None


def cpu_stat_path() -> Optional[str]:
    """The ``cpu.stat`` of the process's own cgroup, v2 or v1, as
    ``/proc/self/cgroup`` names it (inside a cgroup namespace the group
    is the mount's root)."""
    text = _read("/proc/self/cgroup")
    if text is None:
        return None
    found = []
    for line in text.splitlines():
        _, _, rest = line.partition(":")
        controllers, _, group = rest.partition(":")
        group = group.rstrip("/")
        if not controllers:
            roots = ("/sys/fs/cgroup", "/sys/fs/cgroup/unified")
        elif "cpu" in controllers.split(","):
            roots = ("/sys/fs/cgroup/" + controllers, "/sys/fs/cgroup/cpu")
        else:
            continue
        for root in roots:
            found += [f"{root}{group}/cpu.stat", f"{root}/cpu.stat"]
    for path in found:
        if _throttle(path) is not None:
            return path
    return None


def _steal_ms() -> Optional[float]:
    """Milliseconds the hypervisor ran something else on this machine's
    CPUs while they had work (``/proc/stat``'s ``cpu`` line, 8th value)."""
    text = _read("/proc/stat")
    try:
        return int(text.split("\n", 1)[0].split()[8]) * 1e3 / _TICKS_PER_S
    except (AttributeError, IndexError, ValueError):
        return None


def _pressure_ms(resource_: str) -> Optional[float]:
    """Milliseconds some task of the machine stood still for want of
    ``resource_`` (``/proc/pressure/<resource>``'s ``some … total=`` µs)."""
    text = _read("/proc/pressure/" + resource_)
    try:
        some = text.split("\n", 1)[0]
        return int(some[some.index("total=") + len("total="):]) / 1e3
    except (AttributeError, ValueError):
        return None


class _Kernel:
    """The slow counters (files), read for a baseline and at a late beat."""

    def __init__(self):
        self.cpu_stat = cpu_stat_path()
        self.at_ns = 0
        self.last: dict = {}
        self.read()

    def read(self) -> dict:
        """Re-read; returns the deltas since the last reading, in the
        record's units (``None`` where a file is not there)."""
        delay = _run_delay_ns()
        throttle = (_throttle(self.cpu_stat) if self.cpu_stat else None) \
            or (None, None)
        now = {"run_delay_ms": None if delay is None else delay / 1e6,
               "throttled_ms": None if throttle[1] is None
               else throttle[1] / 1e6,
               "nr_throttled": throttle[0],
               "steal_ms": _steal_ms(),
               "psi_cpu_ms": _pressure_ms("cpu"),
               "psi_memory_ms": _pressure_ms("memory"),
               "psi_io_ms": _pressure_ms("io")}
        out = {key: None if value is None or self.last.get(key) is None
               else value - self.last[key] for key, value in now.items()}
        self.last, self.at_ns = now, now_ns()
        return out


def cause_of(late_ns: int, args: dict, gc_ns: int = 0) -> str:
    """What a late beat is counted under (the module's docstring)."""
    half_ms = late_ns / 2e6
    if (args.get("throttled_ms") or 0) >= half_ms:
        return "throttled"
    if (args.get("run_delay_ms") or 0) >= half_ms:
        return "runqueue"
    if gc_ns >= late_ns / 2:
        return "gc"
    if args.get("majflt"):
        return "fault"
    return "unknown"


# -- the beat ----------------------------------------------------------------

def _beat(stop: threading.Event) -> None:
    kernel = _Kernel()
    while True:
        t0, cpu0 = now_ns(), time.process_time_ns()
        ru0 = resource.getrusage(resource.RUSAGE_SELF)
        if stop.wait(BEAT_NS / 1e9):
            return
        woke = now_ns()
        due = t0 + BEAT_NS
        late = woke - due
        if late <= LATE_NS:
            if woke - kernel.at_ns >= BASELINE_NS:
                kernel.read()
            continue
        ru = resource.getrusage(resource.RUSAGE_SELF)
        args = {"cpu_ms": (time.process_time_ns() - cpu0) / 1e6,
                "nivcsw": ru.ru_nivcsw - ru0.ru_nivcsw,
                "majflt": ru.ru_majflt - ru0.ru_majflt,
                "minflt": ru.ru_minflt - ru0.ru_minflt}
        args.update(kernel.read())
        g0, g1 = _last_gc
        args["cause"] = cause_of(late, args,
                                 max(0, min(g1, woke) - max(g0, due)))
        if spans.enabled:
            spans.record_span("host_stall", due, late, "host", (0, 0), args)
        _count(args["cause"], late)


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0, _last_gc
    if phase == "start":
        _gc_t0 = now_ns()
        return
    t0, dur, _gc_t0 = _gc_t0, now_ns() - _gc_t0, 0
    if t0 and dur > GC_PAUSE_NS:
        _last_gc = (t0, t0 + dur)
        if spans.enabled:
            spans.record_span("gc_pause", t0, dur, "host", (0, 0),
                              {"generation": info.get("generation"),
                               "collected": info.get("collected")})
        _count("gc", dur)


class HostBeat:
    """The lane: ``start(pipeline)`` / ``stop()`` as a tracer has them.
    The first user starts the beat thread and the collection callback,
    the last one's ``stop`` ends both."""

    def __init__(self):
        self._started = False

    def start(self, pipeline=None) -> None:
        del pipeline
        global _users, _thread, _stop
        if self._started:
            return
        self._started = True
        with _lock:
            _users += 1
            if _users > 1:
                return
            _metrics()  # the series exist from the first listener on
            _stop = threading.Event()
            _thread = threading.Thread(target=_beat, args=(_stop,),
                                       name="host:beat", daemon=True)
            _thread.start()
            gc.callbacks.append(_on_gc)

    def stop(self) -> None:
        global _users, _thread, _stop
        if not self._started:
            return
        self._started = False
        with _lock:
            _users -= 1
            if _users > 0:
                return
            thread, stop = _thread, _stop
            _thread = _stop = None
            if _on_gc in gc.callbacks:
                gc.callbacks.remove(_on_gc)
        stop.set()
        thread.join(timeout=5)
