"""Deep profiling lane: on-demand XPlane capture, per-op device
attribution, and HBM forensics.

The utilization lane (obs/util.py) and cost observatory (obs/costmodel.py)
can say a dispatch is compute- or bandwidth-bound — but not **which fused
op** is responsible.  This module closes the loop from fleet metric to
individual XLA op (the TVM discipline from PAPERS.md 1802.04799 needs
op-granularity measurements to search on, and whole-program compilation —
1810.09868 — makes the compiled *executable* the unit that must be
profiled):

- **Windowed capture** — :func:`capture_profile` wraps ``jax.profiler``
  start/stop around a bounded serving window and writes the XPlane
  artifacts into a bounded on-disk gallery (:class:`ProfileGallery`,
  the forensics newest-K/byte-cap discipline).  Exactly ONE capture runs
  at a time, process-wide: concurrent callers get a typed
  :class:`ProfileBusyError` (HTTP 409 on the ``GET /profile?seconds=N``
  endpoint — ``obs/export.py``).  The watchdog auto-triggers a capture
  when a dispatch's device time degrades beyond the perfdiff noise band
  (:class:`DegradeDetector`, ``[obs] profile_auto``).
- **Per-op attribution** — :func:`parse_capture_dir` decodes the
  captured ``*.xplane.pb`` protos with a schema-free protobuf
  wire-format walker (:func:`parse_xspace` — no tensorflow/tensorboard
  install needed; a printable-string *text-event fallback* yields a
  counts-only table when the wire walk finds no event planes) into
  per-op device time.  Ops are joined to the cost registry's executable
  fingerprints via the ``device_exec`` emissions observed DURING the
  window, rolled up by category (matmul/conv/elementwise/copy/infeed),
  exported as ``nnstpu_op_time_us{executable,op_category}``, and
  :func:`annotate_chrome_trace` links ``device_exec`` spans in the
  merged Perfetto doc to the capture's drill-down table.
- **HBM forensics** — the backend records ``compiled.memory_analysis()``
  per executable at compile time alongside the cost registry
  (``obs/device.py memory_info``); :func:`register_hbm_gauges` exposes
  ``nnstpu_executable_hbm_bytes{executable,kind}``,
  :func:`check_hbm_capacity` compares the per-pipeline resident-set
  estimate against device capacity before PLAYING (a typed
  :class:`HbmCapacityWarning` + degraded reason, never a start
  failure), and :func:`hbm_ledger` is what the OOM flight dump embeds
  so the verdict names the offending executable.

The orphaned ``[common] xplane_trace_dir`` whole-run path in
``graph/pipeline.py`` folds onto this machinery too
(:func:`start_whole_run` / :func:`stop_whole_run`): one start/stop
implementation, gallery-managed summaries, failures surfaced through the
``health`` hook + degraded registry instead of bare ``warnings.warn`` —
and a whole-run trace holds the capture lock, so ``/profile`` during it
answers the same typed 409 as capture-while-capturing.

See docs/observability.md "Deep profiling lane".
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import tempfile
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from . import hooks as _hooks
from .metrics import REGISTRY, MetricsRegistry

XPLANE_SUFFIX = ".xplane.pb"
SUMMARY_SUFFIX = ".profile.json"

# frames-bounded captures still need a wall-clock ceiling (a stalled
# pipeline must not hold the capture lock forever)
FRAMES_TIMEOUT_S = 30.0
_TICK_S = 0.05


class ProfileBusyError(RuntimeError):
    """A capture is already running (one at a time, process-wide).  The
    ``/profile`` endpoint maps this to HTTP 409."""

    status = 409

    def __init__(self, active: Optional[dict] = None):
        self.active = dict(active or {})
        detail = self.active.get("capture_id") or "capture in progress"
        super().__init__(f"profile capture busy: {detail}")


class HbmCapacityWarning(RuntimeWarning):
    """The per-pipeline HBM resident-set estimate exceeds device
    capacity: warmup surfaces this as a typed warning (serving may still
    work — buffer donation and allocator pooling are not modeled), never
    a start failure."""


# -- conf ---------------------------------------------------------------------

def _conf_float(key: str, default: float) -> float:
    from ..conf import conf

    try:
        return conf.get_float("obs", key, default)
    except ValueError:
        return default


def _conf_int(key: str, default: int) -> int:
    return int(_conf_float(key, float(default)))


def configured_dir() -> str:
    """``[obs] profile_dir`` ("" = a per-process temp gallery)."""
    from ..conf import conf

    return conf.get_path("obs", "profile_dir", "") or ""


def configured_default_seconds() -> float:
    return max(0.05, _conf_float("profile_default_seconds", 2.0))


def configured_top_k() -> int:
    return max(1, _conf_int("profile_top_k", 20))


# -- the capture gallery ------------------------------------------------------

def _dir_bytes(path: str) -> int:
    total = 0
    for root, _dirs, files in os.walk(path):
        for fn in files:
            try:
                total += os.path.getsize(os.path.join(root, fn))
            except OSError:
                continue
    return total


class ProfileGallery:
    """Bounded on-disk capture gallery: newest-K retained, byte-capped.

    Each capture owns ``<dir>/<capture_id>/`` (the raw jax.profiler
    output tree) plus ``<dir>/<capture_id>.profile.json`` (the parsed
    summary).  Unlike the forensics gallery (slowest-K — captures there
    are *evidence ranked by badness*), profiles rank by recency: the
    newest captures answer "what is the device doing NOW".  The
    directory is rescanned at init so a restarted process keeps honoring
    the bound across its predecessor's captures."""

    def __init__(self, dirpath: str, keep: int, max_bytes: int):
        self.dir = dirpath
        self.keep = max(1, int(keep))
        self.max_bytes = max(0, int(max_bytes))
        self.evicted = 0
        self._lock = threading.Lock()
        # (sort key, capture_id, bytes) — sort key orders by recency
        self._entries: List[Tuple[float, str, int]] = []
        os.makedirs(dirpath, exist_ok=True)
        for fname in sorted(os.listdir(dirpath)):
            if not fname.endswith(SUMMARY_SUFFIX):
                continue
            cid = fname[:-len(SUMMARY_SUFFIX)]
            path = os.path.join(dirpath, fname)
            try:
                with open(path) as f:
                    when = float(json.load(f).get("started_unix") or 0.0)
            except Exception:  # noqa: BLE001 — a corrupt summary is not load-bearing
                when = 0.0
            self._entries.append((when, cid, self._entry_bytes(cid)))
        self._entries.sort()

    def capture_dir(self, capture_id: str) -> str:
        return os.path.join(self.dir, capture_id)

    def summary_path(self, capture_id: str) -> str:
        return os.path.join(self.dir, capture_id + SUMMARY_SUFFIX)

    def _entry_bytes(self, capture_id: str) -> int:
        total = 0
        try:
            total += os.path.getsize(self.summary_path(capture_id))
        except OSError:
            pass
        cdir = self.capture_dir(capture_id)
        if os.path.isdir(cdir):
            total += _dir_bytes(cdir)
        return total

    def add(self, capture_id: str, summary: dict) -> Optional[str]:
        """Write one capture's summary; evict oldest entries until the
        bounds hold.  Returns the summary path, or None when the write
        failed or the capture itself fell straight out."""
        path = self.summary_path(capture_id)
        data = json.dumps(summary, indent=1, sort_keys=True,
                          default=str).encode("utf-8")
        with self._lock:
            tmp = f"{path}.tmp.{os.getpid()}"
            try:
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)
            except OSError:
                return None
            self._entries = [e for e in self._entries if e[1] != capture_id]
            self._entries.append((float(summary.get("started_unix") or 0.0),
                                  capture_id, self._entry_bytes(capture_id)))
            self._entries.sort()
            kept: Optional[str] = path
            while len(self._entries) > self.keep or (
                    self.max_bytes and
                    sum(e[2] for e in self._entries) > self.max_bytes
                    and len(self._entries) > 1):
                victim = self._entries.pop(0)  # oldest first
                self.evicted += 1
                self._remove_entry(victim[1])
                if victim[1] == capture_id:
                    kept = None
            return kept

    def _remove_entry(self, capture_id: str) -> None:
        try:
            os.remove(self.summary_path(capture_id))
        except OSError:
            pass
        cdir = self.capture_dir(capture_id)
        if os.path.isdir(cdir):
            import shutil

            shutil.rmtree(cdir, ignore_errors=True)

    def entries(self) -> List[str]:
        with self._lock:
            return [cid for _w, cid, _b in self._entries]

    def summary(self) -> dict:
        with self._lock:
            return {
                "dir": self.dir,
                "entries": len(self._entries),
                "bytes": sum(e[2] for e in self._entries),
                "evicted": self.evicted,
            }


_gallery_lock = threading.Lock()
_gallery: Optional[ProfileGallery] = None
_tmp_gallery_dir: Optional[str] = None


def gallery() -> ProfileGallery:
    """The process gallery for the conf'd ``[obs] profile_dir``
    (re-resolved when the conf changes; "" falls back to one per-process
    temp dir, so ``/profile`` works out of the box)."""
    global _gallery, _tmp_gallery_dir
    root = configured_dir()
    with _gallery_lock:
        if not root:
            if _tmp_gallery_dir is None:
                _tmp_gallery_dir = tempfile.mkdtemp(prefix="nnstpu-profile-")
            root = _tmp_gallery_dir
        if _gallery is None or _gallery.dir != root:
            _gallery = ProfileGallery(
                root,
                keep=_conf_int("profile_keep", 4),
                max_bytes=_conf_int("profile_max_bytes", 64 * 1024 * 1024))
        return _gallery


def reset_gallery() -> None:
    """Drop the cached gallery object (test isolation; files stay)."""
    global _gallery
    with _gallery_lock:
        _gallery = None


# -- XPlane wire-format parsing -----------------------------------------------
#
# The XPlane proto schema ships with tensorflow/tensorboard, neither of
# which is a dependency here; host-only installs have only jaxlib.  The
# wire format, however, is stable and tiny: a generic protobuf walker
# plus the (frozen) XPlane field numbers decodes everything the op table
# needs.  Field map (tsl/profiler/protobuf/xplane.proto):
#   XSpace.planes=1; XPlane.name=2 .lines=3 .event_metadata=4(map);
#   XLine.name=2 .events=4; XEvent.metadata_id=1 .duration_ps=3
#   .num_occurrences=5; XEventMetadata.id=1 .name=2 .display_name=4.

def _pb_fields(buf: bytes):
    """Yield ``(field_number, wire_type, value)`` over one message's
    bytes: varints as ints, length-delimited as bytes.  Raises on
    malformed input (callers treat that as "not a proto")."""
    i, n = 0, len(buf)
    while i < n:
        tag = 0
        shift = 0
        while True:
            b = buf[i]
            i += 1
            tag |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        fno, wt = tag >> 3, tag & 7
        if wt == 0:  # varint
            v = 0
            shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            yield fno, wt, v
        elif wt == 1:  # fixed64
            yield fno, wt, buf[i:i + 8]
            i += 8
        elif wt == 2:  # length-delimited
            ln = 0
            shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            if i + ln > n:
                raise ValueError("truncated length-delimited field")
            yield fno, wt, buf[i:i + ln]
            i += ln
        elif wt == 5:  # fixed32
            yield fno, wt, buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")


def parse_xspace(data: bytes) -> List[dict]:
    """Decode one ``.xplane.pb`` (an XSpace) into
    ``[{"name": plane, "ops": {event_name: [total_dur_ps, count]}}]``."""
    planes: List[dict] = []
    for fno, wt, v in _pb_fields(data):
        if fno != 1 or wt != 2:
            continue
        name = ""
        meta: Dict[int, str] = {}
        lines: List[bytes] = []
        for f2, w2, v2 in _pb_fields(v):
            if f2 == 2 and w2 == 2:
                name = v2.decode("utf-8", "replace")
            elif f2 == 3 and w2 == 2:
                lines.append(v2)
            elif f2 == 4 and w2 == 2:  # event_metadata map entry
                mid, em = 0, None
                for f3, w3, v3 in _pb_fields(v2):
                    if f3 == 1 and w3 == 0:
                        mid = v3
                    elif f3 == 2 and w3 == 2:
                        em = v3
                if em is None:
                    continue
                mname = ""
                for f4, w4, v4 in _pb_fields(em):
                    if f4 == 1 and w4 == 0:
                        mid = v4
                    elif f4 == 2 and w4 == 2 and not mname:
                        mname = v4.decode("utf-8", "replace")
                    elif f4 == 4 and w4 == 2:
                        mname = v4.decode("utf-8", "replace")
                meta[mid] = mname
        ops: Dict[str, List[int]] = {}
        for line in lines:
            for f2, w2, v2 in _pb_fields(line):
                if f2 != 4 or w2 != 2:  # XEvent
                    continue
                mid = dur = 0
                occ = 1
                for f3, w3, v3 in _pb_fields(v2):
                    if w3 != 0:
                        continue
                    if f3 == 1:
                        mid = v3
                    elif f3 == 3:
                        dur = v3
                    elif f3 == 5:
                        occ = max(1, v3)
                ename = meta.get(mid, f"#{mid}")
                entry = ops.setdefault(ename, [0, 0])
                entry[0] += dur
                entry[1] += occ
        planes.append({"name": name, "ops": ops})
    return planes


_TEXT_RUN = re.compile(rb"[\x20-\x7e]{6,}")


def parse_text_events(data: bytes, limit: int = 512) -> Dict[str, List[int]]:
    """The documented text-event fallback: when the wire walk yields no
    event planes (a host-only install writing an artifact this walker
    cannot decode), scan the raw bytes for printable op-name-looking
    runs and return a **counts-only** table (``dur_ps`` stays 0 — the
    summary marks ``parser: "text"`` so readers never mistake counts
    for time)."""
    counts: Dict[str, List[int]] = {}
    for m in _TEXT_RUN.finditer(data):
        s = m.group().decode("ascii", "replace").strip()
        if not re.match(r"^[A-Za-z_$/][\w$./:\- ]*(\.\d+)?$", s):
            continue
        entry = counts.setdefault(s, [0, 0])
        entry[1] += 1
        if len(counts) >= limit:
            break
    return counts


# op-category rollup: name heuristics over XLA/HLO (and host python)
# event names — intentionally coarse, for the matmul/conv/elementwise/
# copy/infeed split the roofline verdicts need
_CATEGORY_RULES = (
    ("matmul", ("dot", "gemm", "matmul", "einsum", "mha", "attention")),
    ("conv", ("conv",)),
    ("infeed", ("infeed", "outfeed", "transfer", "h2d", "d2h",
                "device_put", "copy-start", "copy-done", "send", "recv")),
    ("copy", ("copy", "transpose", "reshape", "broadcast", "concatenate",
              "slice", "pad", "gather", "scatter", "bitcast", "tuple")),
    ("elementwise", ("add", "sub", "mul", "div", "tanh", "exp", "log",
                     "max", "min", "relu", "select", "compare", "rsqrt",
                     "sqrt", "sigmoid", "convert", "clamp", "reduce",
                     "softmax", "power", "negate", "abs")),
)


def categorize_op(name: str) -> str:
    low = name.lower()
    if "fusion" in low:
        return "fusion"
    for cat, needles in _CATEGORY_RULES:
        for needle in needles:
            if needle in low:
                return cat
    return "other"


def find_xplane_files(capture_dir: str) -> List[str]:
    out: List[str] = []
    for root, _dirs, files in os.walk(capture_dir):
        for fn in files:
            if fn.endswith(XPLANE_SUFFIX):
                out.append(os.path.join(root, fn))
    return sorted(out)


def parse_capture_dir(capture_dir: str,
                      top_k: Optional[int] = None) -> dict:
    """Parse every XPlane artifact under ``capture_dir`` into the op
    table.  Device planes (``/device:...``) are preferred when present
    (TPU/GPU); host-only artifacts (CPU backend) fall back to the host
    plane — gate TPU-specific assertions on ``device_planes > 0``."""
    top_k = top_k if top_k is not None else configured_top_k()
    files = find_xplane_files(capture_dir)
    device_ops: Dict[str, List[int]] = {}
    host_ops: Dict[str, List[int]] = {}
    plane_names: List[str] = []
    parser = "wire"
    for path in files:
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            continue
        try:
            planes = parse_xspace(data)
        except Exception:  # noqa: BLE001 — fall back, never fail the capture
            planes = []
        if not any(p["ops"] for p in planes):
            parser = "text"
            for name, entry in parse_text_events(data).items():
                agg = host_ops.setdefault(name, [0, 0])
                agg[0] += entry[0]
                agg[1] += entry[1]
            continue
        for plane in planes:
            if not plane["ops"]:
                continue
            plane_names.append(plane["name"])
            target = device_ops if "/device:" in plane["name"] else host_ops
            for name, entry in plane["ops"].items():
                agg = target.setdefault(name, [0, 0])
                agg[0] += entry[0]
                agg[1] += entry[1]
    device_planes = sum(1 for n in plane_names if "/device:" in n)
    ops = device_ops if device_ops else host_ops
    rows = [
        {"name": name, "category": categorize_op(name),
         "dur_us": round(entry[0] / 1e6, 3), "count": entry[1]}
        for name, entry in ops.items()
    ]
    rows.sort(key=lambda r: (-r["dur_us"], -r["count"], r["name"]))
    categories: Dict[str, float] = {}
    for r in rows:
        categories[r["category"]] = round(
            categories.get(r["category"], 0.0) + r["dur_us"], 3)
    return {
        "parser": parser,
        "artifacts": [os.path.relpath(p, capture_dir) for p in files],
        "planes": plane_names,
        "device_planes": device_planes,
        "ops_total": len(rows),
        "ops": rows[:top_k],
        "op_categories": categories,
    }


# -- the capture state machine ------------------------------------------------

_capture_lock = threading.Lock()
_active_lock = threading.Lock()
_active: Optional[dict] = None  # {"capture_id", "trigger", "whole_run"}

_last_lock = threading.Lock()
_recent: "deque[dict]" = deque(maxlen=8)

_seq_lock = threading.Lock()
_seq = 0


def _next_capture_id(trigger: str) -> str:
    global _seq
    with _seq_lock:
        _seq += 1
        n = _seq
    return f"{time.strftime('%Y%m%d-%H%M%S')}.{os.getpid()}.{n:03d}.{trigger}"


def _acquire(trigger: str, capture_id: str, whole_run: bool = False) -> None:
    global _active
    if not _capture_lock.acquire(blocking=False):
        with _active_lock:
            raise ProfileBusyError(_active)
    with _active_lock:
        _active = {"capture_id": capture_id, "trigger": trigger,
                   "whole_run": whole_run}


def _release() -> None:
    global _active
    with _active_lock:
        _active = None
    _capture_lock.release()


def active_capture() -> Optional[dict]:
    """The in-flight capture's descriptor, or None."""
    with _active_lock:
        return dict(_active) if _active is not None else None


def last_capture() -> Optional[dict]:
    """The most recent completed capture summary (newest first)."""
    with _last_lock:
        return dict(_recent[-1]) if _recent else None


def recent_captures() -> List[dict]:
    with _last_lock:
        return [dict(s) for s in _recent]


def _remember(summary: dict) -> None:
    with _last_lock:
        _recent.append(dict(summary))


def _captures_counter(registry: MetricsRegistry):
    return registry.counter(
        "nnstpu_profile_captures_total",
        "Deep-profiling XPlane captures, by trigger "
        "(manual/http/watchdog/fleet/whole_run) and outcome",
        labelnames=("trigger", "outcome"),
    )


def _export_op_gauges(summary: dict,
                      registry: Optional[MetricsRegistry] = None) -> None:
    """``nnstpu_op_time_us{executable,op_category}``: the last capture's
    per-category device time, attributed to the executable fingerprints
    observed during the window."""
    registry = registry if registry is not None else REGISTRY
    gauge = registry.gauge(
        "nnstpu_op_time_us",
        "Per-op-category device time (µs) from the most recent deep-"
        "profiling capture, keyed to the cost registry's executable "
        "fingerprint (see docs/observability.md 'Deep profiling lane')",
        labelnames=("executable", "op_category"),
    )
    per: Dict[Tuple[str, str], float] = {}
    for row in summary.get("ops") or ():
        key = (row.get("executable") or "", row["category"])
        per[key] = per.get(key, 0.0) + float(row["dur_us"])
    for (executable, category), dur in per.items():
        gauge.set(round(dur, 3), executable=executable, op_category=category)


class _FingerprintWatch:
    """Collect the executable fingerprints whose ``device_exec``
    completions landed inside the capture window — the join key between
    XPlane op rows and the cost registry."""

    def __init__(self):
        self.lock = threading.Lock()
        self.by_key: Dict[str, List[float]] = {}  # fp -> [dur_us_sum, n]
        self.frames = 0

    def on_device_exec(self, pipeline_name, node_name, device, t0_ns,
                       dur_ns, info) -> None:
        del pipeline_name, node_name, device, t0_ns
        fp = (info or {}).get("cost_key")
        with self.lock:
            self.frames += 1
            if fp:
                entry = self.by_key.setdefault(fp, [0.0, 0])
                entry[0] += dur_ns / 1e3
                entry[1] += 1

    def connect(self) -> None:
        _hooks.connect("device_exec", self.on_device_exec)

    def disconnect(self) -> None:
        _hooks.disconnect("device_exec", self.on_device_exec)

    def snapshot(self) -> Dict[str, dict]:
        with self.lock:
            return {fp: {"dur_us": round(e[0], 3), "dispatches": e[1]}
                    for fp, e in self.by_key.items()}


def _attribute_executables(parsed: dict, observed: Dict[str, dict]) -> None:
    """Stamp each op row's ``executable``: with exactly one fingerprint
    observed during the window every device op joins it; with several,
    a model-name substring match wins, else the dominant (most device
    time) fingerprint — deterministic and honest (the summary carries
    the full observed table alongside, so nothing is hidden)."""
    if not observed:
        return
    dominant = max(observed, key=lambda fp: observed[fp]["dur_us"])
    single = list(observed)[0] if len(observed) == 1 else None
    names = {fp: fp.split(":", 1)[0].lower() for fp in observed}
    for row in parsed.get("ops") or ():
        if single is not None:
            row["executable"] = single
            continue
        low = row["name"].lower()
        row["executable"] = next(
            (fp for fp, model in names.items() if model and model in low),
            dominant)


def _emit(action: str, detail: str, pipeline=None) -> None:
    if _hooks.enabled:
        pname = getattr(pipeline, "name", "") or ""
        _hooks.emit("profile", pname, action, detail)


def capture_profile(seconds: Optional[float] = None,
                    frames: Optional[int] = None,
                    pipeline=None,
                    trigger: str = "manual",
                    registry: Optional[MetricsRegistry] = None) -> dict:
    """One bounded profiling window: start ``jax.profiler``, serve for
    ``seconds`` (or until ``frames`` device completions, capped at
    ``FRAMES_TIMEOUT_S``), stop, parse, bank into the gallery, export
    the op gauges.  Raises :class:`ProfileBusyError` when a capture (or
    a whole-run trace) already holds the window.  A ``pipeline`` that
    leaves PLAYING mid-window (stop, renegotiation) ends the window
    early and the summary records the abandonment — never an error.
    The returned summary is also what ``GET /profile`` serves."""
    registry = registry if registry is not None else REGISTRY
    if seconds is None and frames is None:
        seconds = configured_default_seconds()
    capture_id = _next_capture_id(trigger)
    _acquire(trigger, capture_id)
    try:
        gal = gallery()
        capture_dir = gal.capture_dir(capture_id)
        os.makedirs(capture_dir, exist_ok=True)
        watch = _FingerprintWatch()
        summary = {
            "kind": "profile_capture",
            "capture_id": capture_id,
            "trigger": trigger,
            "pipeline": getattr(pipeline, "name", "") or "",
            "started_unix": time.time(),
            "requested_seconds": seconds,
            "requested_frames": frames,
            "aborted": "",
            "artifact_dir": capture_dir,
        }
        _emit("start", capture_id, pipeline)
        import jax

        watch.connect()
        t0 = time.monotonic()
        try:
            jax.profiler.start_trace(capture_dir)
            try:
                deadline = t0 + (seconds if seconds is not None
                                 else FRAMES_TIMEOUT_S)
                while time.monotonic() < deadline:
                    if frames is not None and watch.frames >= frames:
                        break
                    if (pipeline is not None
                            and pipeline.state != "PLAYING"):
                        summary["aborted"] = (
                            f"pipeline left PLAYING "
                            f"(state={pipeline.state})")
                        break
                    time.sleep(_TICK_S)
            finally:
                jax.profiler.stop_trace()
        finally:
            watch.disconnect()
        summary["seconds"] = round(time.monotonic() - t0, 3)
        summary["frames_observed"] = watch.frames
        observed = watch.snapshot()
        summary["executables"] = observed
        parsed = parse_capture_dir(capture_dir)
        _attribute_executables(parsed, observed)
        summary.update(parsed)
        summary["summary_path"] = gal.add(capture_id, summary)
        _export_op_gauges(summary, registry)
        _remember(summary)
        outcome = "aborted" if summary["aborted"] else "ok"
        _captures_counter(registry).inc(1, trigger=trigger, outcome=outcome)
        _emit("end" if outcome == "ok" else "abort",
              f"{capture_id}: {summary['ops_total']} ops, "
              f"{summary['frames_observed']} frames"
              + (f"; {summary['aborted']}" if summary["aborted"] else ""),
              pipeline)
        return summary
    finally:
        _release()


@contextlib.contextmanager
def profiled_window(label: str = "window", logdir: Optional[str] = None,
                    trigger: str = "manual", parse: bool = True):
    """Low-level capture bracket for code that drives its own workload
    (``utils.profiling.device_trace``): serialized
    on the same process-wide capture lock (typed busy, never a
    concurrent ``start_trace`` crash), artifacts in the gallery (or the
    caller's ``logdir``).  Yields a dict that carries ``summary`` after
    the block exits."""
    capture_id = _next_capture_id(trigger)
    _acquire(trigger, capture_id)
    holder: dict = {"capture_id": capture_id, "label": label}
    try:
        gal = gallery() if logdir is None else None
        capture_dir = logdir or gal.capture_dir(capture_id)
        os.makedirs(capture_dir, exist_ok=True)
        _emit("start", f"{capture_id} ({label})")
        import jax

        t0 = time.monotonic()
        jax.profiler.start_trace(capture_dir)
        try:
            yield holder
        finally:
            jax.profiler.stop_trace()
            if parse:
                summary = {
                    "kind": "profile_capture",
                    "capture_id": capture_id,
                    "trigger": trigger,
                    "label": label,
                    "pipeline": "",
                    "started_unix": time.time(),
                    "seconds": round(time.monotonic() - t0, 3),
                    "aborted": "",
                    "artifact_dir": capture_dir,
                    "executables": {},
                }
                summary.update(parse_capture_dir(capture_dir))
                if gal is not None:
                    summary["summary_path"] = gal.add(capture_id, summary)
                _remember(summary)
                _captures_counter(REGISTRY).inc(
                    1, trigger=trigger, outcome="ok")
                holder["summary"] = summary
            _emit("end", f"{capture_id} ({label})")
    finally:
        _release()


# -- the whole-run fold (``[common] xplane_trace_dir``) ----------------------

_whole_run_lock = threading.Lock()
_whole_run: Dict[int, dict] = {}  # id(pipeline) -> state


def start_whole_run(pipeline, trace_dir: str) -> bool:
    """The ``Pipeline._post_negotiate_hooks`` entry point: start one
    whole-PLAYING-interval trace into the user's ``trace_dir`` (raw
    artifacts land there, exactly the pre-fold contract), holding the
    capture lock so ``/profile`` answers 409 for the duration.  Returns
    True when tracing started; failures surface through the ``health``
    hook + degraded registry (see :func:`_surface_failure`), never an
    exception."""
    capture_id = _next_capture_id("whole_run")
    try:
        _acquire("whole_run", capture_id, whole_run=True)
    except ProfileBusyError as exc:
        _surface_failure(pipeline, f"xplane whole-run trace skipped: {exc}")
        return False
    try:
        os.makedirs(trace_dir, exist_ok=True)
        import jax

        jax.profiler.start_trace(trace_dir)
    except Exception as exc:  # noqa: BLE001 — obs must not take start down
        _release()
        _surface_failure(pipeline,
                         f"xplane whole-run trace start failed: {exc!r}")
        return False
    with _whole_run_lock:
        _whole_run[id(pipeline)] = {
            "capture_id": capture_id,
            "trace_dir": trace_dir,
            "started_unix": time.time(),
            "t0": time.monotonic(),
        }
    _emit("start", f"{capture_id} (whole_run -> {trace_dir})", pipeline)
    return True


def stop_whole_run(pipeline) -> Optional[dict]:
    """The ``Pipeline.stop`` half: stop the trace, parse the artifacts
    in place, bank the summary (summary only — the raw artifacts belong
    to the user's dir and are never evicted).  Never raises."""
    with _whole_run_lock:
        state = _whole_run.pop(id(pipeline), None)
    if state is None:
        return None
    summary: Optional[dict] = None
    try:
        import jax

        jax.profiler.stop_trace()
        summary = {
            "kind": "profile_capture",
            "capture_id": state["capture_id"],
            "trigger": "whole_run",
            "pipeline": getattr(pipeline, "name", "") or "",
            "started_unix": state["started_unix"],
            "seconds": round(time.monotonic() - state["t0"], 3),
            "aborted": "",
            "artifact_dir": state["trace_dir"],
            "executables": {},
        }
        summary.update(parse_capture_dir(state["trace_dir"]))
        summary["summary_path"] = gallery().add(state["capture_id"], summary)
        _export_op_gauges(summary)
        _remember(summary)
        _captures_counter(REGISTRY).inc(1, trigger="whole_run", outcome="ok")
        _emit("end", state["capture_id"], pipeline)
    except Exception as exc:  # noqa: BLE001 — stop() must complete
        _captures_counter(REGISTRY).inc(
            1, trigger="whole_run", outcome="error")
        _surface_failure(pipeline,
                         f"xplane whole-run trace stop failed: {exc!r}")
    finally:
        _release()
    return summary


def _surface_failure(pipeline, reason: str) -> None:
    """Whole-run trace failures surface as first-class observability —
    the ``health`` hook (healthy stays True: a lost trace is degraded
    evidence, not a broken pipeline) plus a degraded reason on
    ``/healthz`` — instead of the bare ``warnings.warn`` the orphaned
    path used."""
    _emit("error", reason, pipeline)
    if _hooks.enabled:
        _hooks.emit("health", pipeline, True, reason)
    try:
        from .export import register_degraded

        pname = getattr(pipeline, "name", "") or "pipeline"
        register_degraded(f"xplane:{pname}", lambda r=reason: r)
    except Exception:  # noqa: BLE001 — surfacing is best-effort
        pass


# -- HBM forensics ------------------------------------------------------------

# resident while serving: output + scratch + program text; argument
# bytes are the (usually donated/streamed) inputs, reported separately
_RESIDENT_KINDS = ("output_bytes", "temp_bytes", "generated_code_bytes")


def hbm_ledger() -> dict:
    """The per-executable HBM ledger out of the cost registry (the
    backend records ``memory_analysis()`` per compiled entry —
    ``obs/device.py memory_info``): ``{"executables": {fp: {kind:
    bytes, resident_bytes}}, "largest_resident": fp,
    "resident_estimate_bytes": total}``.  Empty dict when no entry
    carries HBM data (pre-compile, or a runtime without
    ``memory_analysis``).  This is what the OOM flight dump embeds."""
    from . import util as _util

    executables: Dict[str, dict] = {}
    total = 0
    largest: Optional[str] = None
    largest_bytes = -1
    for fp, entry in _util.cost_entries().items():
        hbm = entry.get("hbm")
        if not isinstance(hbm, dict) or not hbm:
            continue
        row = {k: int(v) for k, v in hbm.items()
               if isinstance(v, (int, float))}
        resident = sum(row.get(k, 0) for k in _RESIDENT_KINDS)
        row["resident_bytes"] = resident
        executables[fp] = row
        total += resident
        if resident > largest_bytes:
            largest, largest_bytes = fp, resident
    if not executables:
        return {}
    return {
        "executables": executables,
        "largest_resident": largest,
        "resident_estimate_bytes": total,
    }


_hbm_gauges_lock = threading.Lock()
_hbm_gauges_installed: Dict[int, object] = {}


def register_hbm_gauges(registry: Optional[MetricsRegistry] = None):
    """``nnstpu_executable_hbm_bytes{executable,kind}``: every cost-
    registry entry's ``memory_analysis()`` bytes, refreshed at scrape
    time (a registry collector).  Idempotent per registry; returns the
    collector handle."""
    registry = registry if registry is not None else REGISTRY
    with _hbm_gauges_lock:
        handle = _hbm_gauges_installed.get(id(registry))
        if handle is not None:
            return handle
        gauge = registry.gauge(
            "nnstpu_executable_hbm_bytes",
            "Per-executable memory_analysis() footprint (bytes) by kind "
            "(argument/output/temp/alias/generated_code/resident), keyed "
            "by the cost registry's executable fingerprint",
            labelnames=("executable", "kind"),
        )

        def collect():
            for fp, row in (hbm_ledger().get("executables") or {}).items():
                for kind, val in row.items():
                    gauge.set(val, executable=fp, kind=kind)

        handle = registry.add_collector(collect)
        _hbm_gauges_installed[id(registry)] = handle
        return handle


def device_capacity_bytes(devices=None) -> Optional[int]:
    """The smallest per-device allocator limit (``bytes_limit``), or
    None when no device reports one (CPU hosts)."""
    from .device import device_memory_snapshot

    limits = [
        stats["bytes_limit"]
        for stats in device_memory_snapshot(devices).values()
        if isinstance(stats.get("bytes_limit"), int)
        and stats["bytes_limit"] > 0
    ]
    return min(limits) if limits else None


def check_hbm_capacity(pipeline=None, devices=None,
                       capacity_bytes: Optional[int] = None) -> dict:
    """Warmup's pre-PLAYING residency check: sum the per-executable
    resident-set estimates and compare against device capacity.  Over
    capacity → a typed :class:`HbmCapacityWarning` naming the largest
    executable + a degraded reason on ``/healthz`` — **never** a start
    failure (the estimate ignores donation/pooling; serving may fit).
    The report lands on ``pipeline.hbm_report``."""
    ledger = hbm_ledger()
    capacity = capacity_bytes if capacity_bytes is not None \
        else device_capacity_bytes(devices)
    report = {
        "resident_estimate_bytes": ledger.get("resident_estimate_bytes", 0),
        "largest_resident": ledger.get("largest_resident"),
        "capacity_bytes": capacity,
        "executables": len(ledger.get("executables") or {}),
        "over_capacity": False,
    }
    if (capacity is not None and ledger
            and report["resident_estimate_bytes"] > capacity):
        report["over_capacity"] = True
        reason = (
            f"estimated executable resident set "
            f"{report['resident_estimate_bytes']} B exceeds device "
            f"capacity {capacity} B (largest: "
            f"{report['largest_resident']})")
        import warnings

        warnings.warn(reason, HbmCapacityWarning, stacklevel=2)
        try:
            from .export import register_degraded

            pname = getattr(pipeline, "name", "") or "pipeline"
            register_degraded(f"hbm:{pname}", lambda r=reason: r)
        except Exception:  # noqa: BLE001 — the check is advisory
            pass
        _emit("hbm_over_capacity", reason, pipeline)
    if pipeline is not None:
        pipeline.hbm_report = report
    return report


# -- Perfetto drill-down join -------------------------------------------------

def annotate_chrome_trace(doc: dict, summary: Optional[dict] = None) -> dict:
    """Join the most recent capture's drill-down into a Chrome-trace
    document (the merged Perfetto export — ``TraceCollector.
    chrome_trace`` calls this): the top-K op table + category rollup
    land under ``otherData.profile_drilldown``, and every ``device_exec``
    span whose ``cost_key`` matches an attributed executable gets a
    ``profile_capture`` arg pointing at it.  No capture → the doc passes
    through untouched."""
    summary = summary if summary is not None else last_capture()
    if not summary:
        return doc
    drill = {
        "capture_id": summary.get("capture_id"),
        "trigger": summary.get("trigger"),
        "parser": summary.get("parser"),
        "ops": summary.get("ops") or [],
        "op_categories": summary.get("op_categories") or {},
        "executables": summary.get("executables") or {},
    }
    doc.setdefault("otherData", {})["profile_drilldown"] = drill
    attributed = {row.get("executable")
                  for row in drill["ops"] if row.get("executable")}
    attributed |= set(drill["executables"])
    for ev in doc.get("traceEvents") or ():
        if ev.get("ph") != "X" or ev.get("name") != "device_exec":
            continue
        args = ev.get("args")
        if not isinstance(args, dict):
            continue
        if not attributed or args.get("cost_key") in attributed:
            args["profile_capture"] = drill["capture_id"]
    return doc


# -- watchdog degrade detection -----------------------------------------------

class DegradeDetector:
    """Per-executable device-time regression detection on the perfdiff
    noise band: a Welford aggregate per cost fingerprint (fed by
    ``device_exec``), and once ``min_samples`` have landed, a dispatch
    whose duration exceeds ``mean + leg_band_us(...)`` (the same
    sigmas/rel/abs floors tools/perfdiff and the forensics engine use)
    arms the detector.  The watchdog polls :meth:`degraded` each tick
    and auto-triggers a capture (cooldown-gated) when armed."""

    def __init__(self, sigmas: Optional[float] = None,
                 min_rel: Optional[float] = None,
                 min_abs_us: Optional[float] = None,
                 min_samples: Optional[int] = None,
                 alpha: float = 0.2):
        self.sigmas = sigmas if sigmas is not None \
            else _conf_float("profile_sigmas", 3.0)
        self.min_rel = min_rel if min_rel is not None \
            else _conf_float("profile_min_rel", 0.10)
        self.min_abs_us = min_abs_us if min_abs_us is not None \
            else _conf_float("profile_min_abs_us", 50.0)
        self.min_samples = min_samples if min_samples is not None \
            else _conf_int("profile_min_samples", 32)
        self.alpha = alpha
        self._lock = threading.Lock()
        self._stats: Dict[str, object] = {}
        self._armed: Optional[str] = None
        self.verdicts = 0

    def on_device_exec(self, pipeline_name, node_name, device, t0_ns,
                       dur_ns, info) -> None:
        del pipeline_name, device, t0_ns
        from .costmodel import LegStat, leg_band_us

        key = (info or {}).get("cost_key") or f"node:{node_name}"
        dur_us = dur_ns / 1e3
        with self._lock:
            stat = self._stats.get(key)
            if stat is None:
                stat = self._stats[key] = LegStat()
            if stat.count >= self.min_samples:
                band = leg_band_us(stat.snapshot(), sigmas=self.sigmas,
                                   min_rel=self.min_rel,
                                   min_abs_us=self.min_abs_us)
                if dur_us > stat.mean_us + band:
                    self.verdicts += 1
                    self._armed = (
                        f"{key}: {dur_us:.0f}µs vs mean "
                        f"{stat.mean_us:.0f}µs + band {band:.0f}µs")
            stat.add(dur_us, self.alpha)

    def degraded(self, clear: bool = True) -> Optional[str]:
        """The armed verdict (and clear it), or None."""
        with self._lock:
            armed = self._armed
            if clear:
                self._armed = None
            return armed


# stats provider: the deep-profiling lane's own summary ----------------------

def stats() -> dict:
    out: dict = {"gallery": gallery().summary()}
    active = active_capture()
    if active:
        out["active"] = active
    last = last_capture()
    if last:
        out["last_capture"] = {
            k: last.get(k)
            for k in ("capture_id", "trigger", "parser", "ops_total",
                      "seconds", "aborted", "pipeline")
        }
    ledger = hbm_ledger()
    if ledger:
        out["hbm"] = {
            "resident_estimate_bytes": ledger["resident_estimate_bytes"],
            "largest_resident": ledger["largest_resident"],
            "executables": len(ledger["executables"]),
        }
    return out


# the HBM gauges ride the default registry from import time: any process
# that compiles an executable exposes its footprint on the next scrape
register_hbm_gauges(REGISTRY)
