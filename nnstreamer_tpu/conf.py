"""Runtime configuration: the ``nnstreamer_conf`` analog.

The reference merges **three config sources** with fixed precedence — env
vars, an ini file, hardcoded defaults (``nnstreamer_conf.c:37-52``) — and
scans configured directories for subplugin shared objects, lazily loaded on
first lookup (``nnstreamer_conf.c:137-166``, ``nnstreamer_subplugin.c:56-113``).

Here the same shape, Python-native:

- env vars ``NNSTPU_<SECTION>_<KEY>`` (e.g. ``NNSTPU_COMMON_PLUGIN_PATH``)
  take top precedence; ``NNSTPU_CONF`` points at the ini file (the analog of
  ``NNSTREAMER_CONF``);
- an ini file (``configparser`` flavor) searched at ``$NNSTPU_CONF``,
  ``./nnstreamer_tpu.ini``, ``~/.config/nnstreamer_tpu/nnstreamer_tpu.ini``,
  ``/etc/nnstreamer_tpu.ini`` — first hit wins (mirrors the ini template
  ``nnstreamer.ini.in:1-21`` including per-backend knobs);
- hardcoded defaults.

External plugins (the ``libnnstreamer_{filter,decoder}_*.so`` analog) are
plain ``.py`` files named ``nnstpu_*.py`` in the configured plugin dirs.
They are imported on first registry miss (lazy, like the reference's
``dlopen``-on-first-lookup) and self-register via
:func:`~nnstreamer_tpu.graph.registry.register_element`,
:func:`~nnstreamer_tpu.backends.base.register_backend`, or
:func:`~nnstreamer_tpu.elements.decoder.register_decoder`.
"""

from __future__ import annotations

import configparser
import importlib.util
import os
import sys
import threading
from typing import Dict, List, Optional

_TRUE = {"1", "true", "yes", "on"}
_FALSE = {"0", "false", "no", "off"}

DEFAULTS: Dict[str, Dict[str, str]] = {
    "common": {
        "plugin_path": "",          # colon-separated dirs of nnstpu_*.py
        "enable_profiling": "false",
        "native_runtime": "true",   # C++ frame queue (nnstreamer_tpu/native)
        "dump_dot_dir": "",         # write <pipeline>.PLAYING.dot here
        "tracers": "",              # GST_TRACERS analog: "latency;stats;drops"
        "metrics_port": "",         # Prometheus scrape port ("" = disabled)
        "xplane_trace_dir": "",     # jax.profiler xplane trace of PLAYING
    },
    "filter": {
        "jax_dtype": "bfloat16",    # compute dtype for the jax backend
        "torch_device": "cpu",      # the `torch use gpu` knob analog
    },
    "decoder": {},
    # Observability (nnstreamer_tpu/obs): span tracing + metric shaping.
    # Short env spellings NNSTPU_METRICS_BUCKETS / NNSTPU_FLIGHT_RECORDS
    # take precedence over the NNSTPU_OBS_* forms mapped here.
    "obs": {
        "buckets": "",              # latency-histogram bounds, ms ("0.1,1,10")
        "flight_records": "",       # span flight-recorder ring size per thread
        "flight_dump_dir": "",      # write {pipeline}.error.trace.json here
        # Device lane (obs/device.py): completion-probe queue bound for the
        # DeviceTracer reaper thread (overflow drops probes, counted).
        "device_probe_queue": "1024",
        # Utilization lane (obs/util.py): the sliding window behind
        # nnstpu_device_busy_fraction, and the minimum device idle gap that
        # becomes a device_idle flight span.  (MFU/roofline peaks are not
        # knobs: obs.util.DEVICE_PEAKS, keyed by device_kind.)
        "busy_window_s": "10",
        "device_idle_gap_ms": "5",
        # Pipeline health watchdog (obs/watchdog.py, tracer "watchdog").
        "watchdog_interval": "1.0",         # monitor tick, seconds
        "watchdog_stall_s": "5.0",          # source/queue stall window
        "watchdog_queue_depth": "1",        # min depth to call a queue wedged
        "watchdog_device_deadline_s": "30", # device completion deadline
        "watchdog_recover": "false",        # escalate detection to recovery
        "watchdog_recover_budget": "3",     # max recovery attempts per target
        # >0: the watchdog spot-checks the host->device wire (and every
        # registered partition edge) every this many seconds and publishes
        # nnstpu_wire_* gauges (obs/util.py)
        "watchdog_wire_probe_s": "0",
        # Cost observatory (obs/costmodel.py, tracer "costmodel"): the
        # persisted per-stage cost model the partitioner prices cuts
        # against, its EWMA smoothing factor, and whether tracer stop()
        # flushes the model to disk automatically.
        "costmodel_path": "COST_MODEL.json",
        "costmodel_alpha": "0.2",
        "costmodel_autosave": "true",
        # Tail forensics (obs/forensics.py, tracer "forensics"): completed
        # traces whose leg decomposition exceeds the cost-model noise band
        # are counted as outliers and (with a directory set) captured as
        # flight-dump gallery entries, slowest-K retained under a byte cap.
        "forensics_dir": "",            # "" = score + count, never capture
        "forensics_keep": "8",          # gallery entries retained (slowest K)
        "forensics_max_bytes": "16777216",  # gallery byte cap (16 MiB)
        "forensics_sigmas": "3.0",      # noise-band sigmas (leg_band_us)
        "forensics_min_rel": "0.10",    # noise-band relative floor
        "forensics_min_abs_us": "5.0",  # noise-band absolute floor, µs
        "forensics_min_samples": "32",  # live-baseline warmup before verdicts
        # Deep profiling lane (obs/profiler.py): on-demand XPlane capture
        # windows + per-op attribution + HBM forensics.  The gallery holds
        # the newest profile_keep captures under profile_max_bytes; the
        # watchdog auto-trigger (profile_auto) fires a profile_auto_seconds
        # window, at most once per profile_auto_cooldown_s, when a
        # dispatch's device time exceeds the profile_sigmas/profile_min_rel/
        # profile_min_abs_us noise band after profile_min_samples.  See
        # docs/observability.md "Deep profiling lane".
        "profile_dir": "",              # capture gallery ("" = process temp)
        "profile_keep": "4",            # gallery entries retained (newest K)
        "profile_max_bytes": "67108864",  # gallery byte cap (64 MiB)
        "profile_default_seconds": "2.0",  # window when none requested
        "profile_top_k": "20",          # op rows kept in the summary table
        "profile_auto": "false",        # watchdog-triggered auto-capture
        "profile_auto_seconds": "1.0",  # auto-capture window length
        "profile_auto_cooldown_s": "120",  # min seconds between auto-captures
        "profile_sigmas": "3.0",        # degrade noise-band sigmas
        "profile_min_rel": "0.10",      # degrade noise-band relative floor
        "profile_min_abs_us": "50.0",   # degrade noise-band absolute floor, µs
        "profile_min_samples": "32",    # per-executable warmup before verdicts
    },
    # SLO burn-rate engine (obs/slo.py): declarative latency objectives
    # evaluated at scrape time over registry histogram windows, surfaced
    # on /alerts and the `alert` hook.  NNSTPU_SLO_* env vars map here.
    "slo": {
        # objectives spec: "name:metric{label=value,...}<bound_ms@target"
        # semicolon-separated; metric defaults to nnstpu_e2e_latency_ms —
        # e.g. "e2e:<50ms@0.999;tenantA:{tenant=A}<25ms@0.99"
        "objectives": "",
        "fast_window_s": "60",      # fast burn window (paging signal)
        "slow_window_s": "600",     # slow burn window (confirmation)
        "fast_burn": "14.0",        # firing threshold on the fast window
        "slow_burn": "6.0",         # firing threshold on the slow window
        "eval_interval_s": "5",     # min seconds between evaluations
    },
    # Host staging-buffer pool (nnstreamer_tpu/pool): the zero-copy batch
    # assembly + wire staging path.  NNSTPU_POOL_* env vars map here.
    "pool": {
        "enabled": "true",          # false = every lease allocates fresh
        "max_per_class": "4",       # free buffers kept per (shape, dtype)
        "max_bytes": "67108864",    # total free-list bytes (64 MiB)
    },
    # Compile-ahead serving (backends/exec_cache.py + graph/warmup.py +
    # ops/autotune.py): persistent executable/autotune caches and the AOT
    # warmup phase.  NNSTPU_COMPILE_* env vars map here.
    "compile": {
        "cache_dir": "",            # persistent executable + autotune cache
                                    # root ("" = persistence off); jax's own
                                    # XLA binary cache lands in <dir>/xla
        "warmup": "false",          # AOT warmup phase in Pipeline.start:
                                    # compile every negotiated (spec, bucket)
                                    # geometry before PLAYING
        "warmup_workers": "4",      # parallel compile workers for warmup
        "warmup_timeout_s": "600",  # whole-phase deadline (0 = unbounded)
        "autotune": "true",         # consult the persistent Pallas autotune
                                    # cache for kernel block configs
    },
    # Whole-segment compilation (graph/segments.py): fold converter
    # pre-ops and decoder post-ops into the filter's XLA program so each
    # run-to-completion region dispatches as ONE device executable.
    # NNSTPU_SEGMENT_* env vars map here.  See docs/performance.md
    # "Whole-segment compilation".
    "segment": {
        "enabled": "false",         # plan + fold segments in Pipeline.start
                                    # (a pipeline's .segment_compile attr
                                    # overrides this per instance)
        "pallas_nms": "false",      # trace ops/nms.py's Pallas NMS kernel
                                    # into fused detection segments instead
                                    # of the pure-XLA form (interpret mode
                                    # off-TPU; same bits either way)
    },
    # Mesh-sharded dispatch (parallel/mesh.py dispatch_mesh): batch-axis
    # data parallelism over all chips.  The short env spelling NNSTPU_MESH
    # takes precedence over the NNSTPU_MESH_SPEC form mapped here.
    "mesh": {
        "spec": "",                 # "" = off; "auto" | "dp:8" | "8" — see
                                    # parallel.mesh.parse_mesh_spec
    },
    # Dispatcher lanes (graph/lanes.py): run-to-completion event-loop
    # runtime replacing thread-per-element.  NNSTPU_DISPATCH_* env vars
    # map here (NNSTPU_DISPATCH_LANES is the documented spelling).
    "dispatch": {
        "lanes": "0",               # 0 = thread-per-element (legacy);
                                    # "auto" = min(4, cpus); N pins it
        "helpers": "16",            # bounded blocking-task helper pool
        "block_ms": "20",           # source pull over this => blocking,
                                    # shunted to the helper pool
        "quantum": "8",             # frames/items per task slice before
                                    # the lane is yielded
    },
    # Serving QoS (nnstreamer_tpu/sched): NNSTPU_SCHED_* env vars map here.
    # An empty policy disables scheduling entirely (legacy FIFO dispatch).
    "sched": {
        "policy": "",               # fifo | prio | edf | drr
        "max_queue_per_client": "64",
        "rate": "0",                # admitted requests/s per tenant (0 = off)
        "burst": "0",               # token-bucket depth (0 = max(1, rate))
        "deadline_ms": "0",         # queued-request deadline (0 = none)
        "breaker_failures": "0",    # consecutive failures to trip (0 = off)
        "breaker_reset_s": "30",    # open -> half-open probe delay
        "quantum": "8",             # DRR per-round credit (cost units)
        "priorities": "",           # "clientA=10,clientB=2" strict/slot prio
        "max_waiting": "16",        # bounded slot-waiter room (DecodeServer)
    },
    # Chaos engine (nnstreamer_tpu/faults): seeded fault injection.  The
    # short env spelling NNSTPU_FAULTS takes precedence over the
    # NNSTPU_FAULTS_SPEC form mapped here.
    "faults": {
        "spec": "",                 # e.g. "seed=42;invoke_raise@f:every=5"
        "seed": "0",                # default seed (a seed= clause wins)
    },
    # Fleet serving tier (nnstreamer_tpu/fleet): NNSQ router + worker
    # membership.  NNSTPU_FLEET_* env vars map here.
    "fleet": {
        "heartbeat_s": "0.5",       # membership probe interval
        "probe_timeout_s": "2.0",   # per-probe deadline
        "suspect_misses": "2",      # missed probes before SUSPECT (no new
                                    # dispatch; in-flight work completes)
        "death_misses": "6",        # missed probes before DOWN (ejected)
        "breaker_failures": "3",    # data-path failures to quarantine a
                                    # flapping worker (per-worker breaker)
        "breaker_reset_s": "2.0",   # quarantine -> half-open probe delay
        "route_retries": "3",       # extra workers tried per request
        "retry_backoff_ms": "20",   # first re-route backoff (doubles)
        "retry_backoff_cap_ms": "500",
        "connect_timeout_s": "5",   # router -> worker dial deadline
        "request_timeout_s": "30",  # router -> worker reply deadline
        "drain_deadline_s": "10",   # session-drain wait before force-break
        "repo_addr": "",            # host:port of a TensorRepoServer; ""
                                    # keeps tensor_repo process-local
        "migrate": "1",             # live-migrate decode sessions on a
                                    # planned drain (needs repo_addr);
                                    # 0 = legacy force-break [SESSION]
        "migrate_timeout_s": "10",  # per-handoff deadline (quiesce +
                                    # snapshot + restore + re-pin)
        "migrate_check_s": "0.25",  # stateful router's monitor period
                                    # for self-draining workers
    },
    # Elastic fleet autoscaling (nnstreamer_tpu/fleet/autoscaler.py +
    # supervisor.py): the SLO-driven control loop over the fleet's
    # federated signals.  NNSTPU_AUTOSCALE_* env vars map here.
    "autoscale": {
        "min_workers": "1",         # fleet floor (never drained below)
        "max_workers": "4",         # fleet ceiling (never spawned above)
        "interval_s": "0.5",        # control-loop tick period
        "queue_wait_hi_ms": "50",   # queue-wait p99 above this => scale up
        "queue_wait_lo_ms": "5",    # ...below this (and idle) => scale down
        "busy_hi": "0.85",          # device_busy_fraction/MFU upper band
        "busy_lo": "0.20",          # ...lower band (scale-down eligible)
        "shed_hi": "0.01",          # shed-rate (shed/offered) => scale up
        "up_cooldown_s": "1",       # min gap between scale-UP actions
        "down_cooldown_s": "5",     # min gap between scale-DOWN actions
        "flap_window_s": "30",      # direction reversals counted here...
        "flap_limit": "3",          # ...beyond this: damped (held steady)
        "storm_budget": "6",        # max spawns per storm window before
                                    # the typed degraded /healthz escalation
        "storm_window_s": "30",     # the spawn-storm budget window
        "forecast": "true",         # predictive leg over offered-load
                                    # history (diurnal profiles forecast)
        "forecast_horizon_s": "5",  # how far ahead the forecast looks
        "history_window_s": "60",   # offered-load history retained
        "worker_rps": "0",          # per-worker capacity estimate feeding
                                    # the forecast (0 = predictive leg off)
        "crash_limit": "3",         # worker deaths within crash_window_s
                                    # => crash-loop quarantine
        "crash_window_s": "30",     # the crash-loop detection window
        "quarantine_s": "30",       # hold-down before a quarantined
                                    # worker may respawn
        "respawn_backoff_ms": "200",   # first respawn backoff (doubles)
        "respawn_backoff_cap_ms": "5000",  # respawn backoff ceiling
        "spawn_timeout_s": "30",    # spawn + warmup deadline before the
                                    # attempt counts as failed
    },
    # Among-device partitioning (nnstreamer_tpu/partition): the
    # cost-model-driven auto-partitioner.  NNSTPU_PARTITION_* env vars
    # map here.  See docs/partitioning.md.
    "partition": {
        "edge": "edge0",            # default partition-edge label (tags
                                    # nnsq_rtt spans -> hop:{edge} leg)
        "monitor_interval_s": "1.0",   # repartition monitor tick period
        "noise_multiplier": "3.0",  # stage-cost drift beyond
                                    # leg_std_us * this triggers replan
        "default_cut_bytes": "150528",  # transfer bytes per frame at a
                                    # cut when the cost model has no
                                    # copy_bytes_per_frame for it
        "probe_n": "4",             # round trips per edge health probe
        "warm_timeout_s": "30",     # deploy: wait for the server
                                    # fragment worker to report "ok"
    },
    # Analysis instruments (nnstreamer_tpu/analysis): runtime lockdep.
    # The short env spelling NNSTPU_LOCKDEP takes precedence over the
    # NNSTPU_ANALYSIS_LOCKDEP form mapped here.
    "analysis": {
        "lockdep": "false",         # wrap threading.Lock/RLock/Condition
                                    # with the lock-order verifier
        "lockdep_block_ms": "200",  # blocked-while-holding report threshold
        "lockdep_allow": "",        # comma-separated site substrings whose
                                    # findings are accepted (annotated)
    },
    # Self-healing (graph/pipeline.py restart policies + backend
    # degradation).  NNSTPU_RECOVERY_* env vars map here.
    "recovery": {
        "policy": "",               # default per-node policy: restart |
                                    # quarantine-passthrough | fail-pipeline
                                    # ("" = fail-pipeline, legacy behavior)
        "max_restarts": "5",        # restart-storm budget per node ...
        "window_s": "30",           # ... within this sliding window
        "backoff_ms": "50",         # first restart backoff (doubles)
        "backoff_cap_ms": "2000",   # backoff ceiling
        "cpu_fallback": "false",    # opt in: degrade a jax compile failure
                                    # to CPU instead of failing the stream
    },
}


# Short env spellings: convenience env vars that do NOT follow the
# NNSTPU_<SECTION>_<KEY> derivation but alias a DEFAULTS knob (value =
# (section, key)) or are meta-configuration with no knob (value = None,
# e.g. the ini-file locator).  This is a machine-checked contract:
# ``analysis/lint.py`` verifies every literal NNSTPU_* env read in the
# tree resolves through DEFAULTS or this table — a new short spelling
# must be declared here or the lint gate fails.
SHORT_ENV: Dict[str, Optional[tuple]] = {
    "NNSTPU_CONF": None,                # ini file path (the locator itself)
    "NNSTPU_PLUGIN_PATH": ("common", "plugin_path"),
    "NNSTPU_TRACERS": ("common", "tracers"),
    "NNSTPU_METRICS_PORT": ("common", "metrics_port"),
    "NNSTPU_METRICS_BUCKETS": ("obs", "buckets"),
    "NNSTPU_FLIGHT_RECORDS": ("obs", "flight_records"),
    "NNSTPU_MESH": ("mesh", "spec"),
    "NNSTPU_FAULTS": ("faults", "spec"),
    "NNSTPU_LOCKDEP": ("analysis", "lockdep"),
}


class Conf:
    """Layered configuration with lazy external-plugin loading."""

    def __init__(self, ini_path: Optional[str] = None, environ=None):
        self._lock = threading.Lock()
        self._environ = environ if environ is not None else os.environ
        self._explicit_ini = ini_path
        self._loaded_plugin_files: Dict[str, object] = {}
        self.refresh()

    # -- source loading -----------------------------------------------------

    def _ini_candidates(self) -> List[str]:
        cands = []
        if self._explicit_ini:
            cands.append(self._explicit_ini)
        env = self._environ.get("NNSTPU_CONF")
        if env:
            cands.append(env)
        cands.append(os.path.join(os.getcwd(), "nnstreamer_tpu.ini"))
        cands.append(
            os.path.expanduser("~/.config/nnstreamer_tpu/nnstreamer_tpu.ini")
        )
        cands.append("/etc/nnstreamer_tpu.ini")
        return cands

    def refresh(self) -> None:
        """Re-read the ini file (env vars are always read live)."""
        parser = configparser.ConfigParser()
        path = None
        for cand in self._ini_candidates():
            if cand and os.path.isfile(cand):
                path = cand
                break
        if path:
            parser.read(path)
        with self._lock:
            self.ini_path = path
            self._ini = parser

    # -- typed getters (env > ini > defaults) --------------------------------

    def get(self, section: str, key: str, default: Optional[str] = None) -> Optional[str]:
        env_key = f"NNSTPU_{section.upper()}_{key.upper()}"
        val = self._environ.get(env_key)
        if val is not None:
            return val
        with self._lock:
            if self._ini.has_option(section, key):
                return self._ini.get(section, key)
        val = DEFAULTS.get(section, {}).get(key)
        return val if val is not None else default

    def get_bool(self, section: str, key: str, default: bool = False) -> bool:
        val = self.get(section, key)
        if val is None or val == "":
            return default
        low = val.strip().lower()
        if low in _TRUE:
            return True
        if low in _FALSE:
            return False
        raise ValueError(f"[{section}] {key}: not a boolean: {val!r}")

    def get_int(self, section: str, key: str, default: int = 0) -> int:
        val = self.get(section, key)
        return int(val) if val not in (None, "") else default

    def get_float(self, section: str, key: str, default: float = 0.0) -> float:
        val = self.get(section, key)
        return float(val) if val not in (None, "") else default

    def get_path(self, section: str, key: str, default: str = "") -> str:
        val = self.get(section, key, default)
        return os.path.expanduser(val) if val else val

    # -- external plugin scanning (the dlopen analog) ------------------------

    def plugin_dirs(self) -> List[str]:
        """Plugin search dirs: ``$NNSTPU_PLUGIN_PATH`` (colon-separated) then
        ini ``[common] plugin_path`` (the reference's env-over-ini order,
        ``nnstreamer_conf.c:99-109``)."""
        dirs: List[str] = []
        for source in (
            self._environ.get("NNSTPU_PLUGIN_PATH", ""),
            self.get("common", "plugin_path", "") or "",
        ):
            for d in source.split(os.pathsep):
                d = os.path.expanduser(d.strip())
                if d and d not in dirs:
                    dirs.append(d)
        return dirs

    def scan_plugin_files(self) -> List[str]:
        """All ``nnstpu_*.py`` files in the plugin dirs, sorted."""
        files = []
        for d in self.plugin_dirs():
            if not os.path.isdir(d):
                continue
            for fname in sorted(os.listdir(d)):
                if fname.startswith("nnstpu_") and fname.endswith(".py"):
                    files.append(os.path.join(d, fname))
        return files

    def load_external_plugins(self) -> int:
        """Import every not-yet-loaded plugin file; returns how many loaded.

        Modules self-register their elements/backends/decoders at import
        time, exactly like the reference's shared-object constructors calling
        ``register_subplugin`` (``nnstreamer_subplugin.c:117-165``).
        """
        loaded = 0
        for path in self.scan_plugin_files():
            real = os.path.realpath(path)
            with self._lock:
                if real in self._loaded_plugin_files:
                    continue
                # reserve before exec so a recursive lookup can't double-load
                self._loaded_plugin_files[real] = None
            modname = "nnstpu_plugins." + os.path.splitext(os.path.basename(path))[0]
            spec = importlib.util.spec_from_file_location(modname, real)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[modname] = mod
            try:
                spec.loader.exec_module(mod)
            except BaseException:
                with self._lock:
                    del self._loaded_plugin_files[real]
                sys.modules.pop(modname, None)
                raise
            with self._lock:
                self._loaded_plugin_files[real] = mod
            loaded += 1
        return loaded


conf = Conf()


def load_external_plugins() -> int:
    """Module-level convenience used by the registries on lookup miss."""
    return conf.load_external_plugins()


def lookup_with_plugin_fallback(get):
    """Shared registry-miss handler: scan+load external plugins once, then
    retry ``get()`` if anything new was loaded (else None)."""
    if conf.load_external_plugins():
        return get()
    return None
