"""Persistent Pallas autotune cache: tune once per machine, not per
process.

A block split found by searching on the chip dies with the process that
searched.  TVM's discipline (PAPERS.md) is the model: **search offline,
serve from the cache**.  This module is that cache plus the search
driver:

- winners are keyed by ``(kernel, shapes, dtype, platform)`` and stored
  as JSON under ``<[compile] cache_dir>/autotune/<kernel>.json`` — one
  file per kernel, atomically rewritten, loaded once per process (and
  re-loadable for tests via :func:`refresh`);
- :func:`cached_int8_blocks` is the hot-path consult:
  :func:`~nnstreamer_tpu.ops.pallas_kernels.int8_matmul` calls it (at
  trace time — zero per-dispatch cost) whenever the caller left
  ``block_m``/``block_n`` unset, so the tuned tile split survives process
  restarts without any call-site change;
- :func:`autotune_int8_matmul` runs the on-chip search over the
  candidate grid below and records the winner.  It
  refuses to tune in interpret mode — interpret-mode timings would
  poison the cache with host-CPU noise — unless explicitly forced.

Conf: ``[compile] autotune`` (default on) gates the consult;
``[compile] cache_dir`` ("" = off) locates the store.  With no cache
dir, everything degrades to the kernels' built-in static heuristics.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Dict, Optional, Tuple

_LOG = logging.getLogger("nnstreamer_tpu.ops")

_lock = threading.Lock()
# kernel name -> {key: entry}; None = not loaded yet for that kernel
_mem: Dict[str, Optional[Dict[str, dict]]] = {}


def _root() -> str:
    from ..backends.exec_cache import cache_dir

    return cache_dir()


def enabled() -> bool:
    from ..conf import conf

    return bool(_root()) and conf.get_bool("compile", "autotune", True)


def _path(kernel: str) -> str:
    return os.path.join(_root(), "autotune", f"{kernel}.json")


def _platform() -> str:
    from ..backends.exec_cache import platform

    return platform()


def make_key(shapes, dtype, platform: Optional[str] = None) -> str:
    """Canonical cache key: shapes like ``((m, k), (k, n))``, the operand
    dtype, and the platform the timing ran on (a CPU winner must never
    steer a TPU dispatch)."""
    shp = "x".join("_".join(str(d) for d in s) for s in shapes)
    return f"{shp}|{dtype}|{platform or _platform()}"


def _load(kernel: str) -> Dict[str, dict]:
    with _lock:
        cached = _mem.get(kernel)
        if cached is not None:
            return cached
    table: Dict[str, dict] = {}
    try:
        with open(_path(kernel), "rb") as f:
            raw = json.loads(f.read().decode("utf-8"))
        if isinstance(raw, dict):
            table = {str(k): v for k, v in raw.items()
                     if isinstance(v, dict)}
    except (OSError, ValueError):
        # absent or corrupted: serve heuristics; the next record()
        # rewrites the file whole
        table = {}
    with _lock:
        _mem[kernel] = table
    return table


def refresh() -> None:
    """Drop the in-memory tables (tests; cross-process pickup)."""
    with _lock:
        _mem.clear()


def best(kernel: str, key: str) -> Optional[dict]:
    """The winning config entry for ``key``, or None."""
    if not enabled():
        return None
    return _load(kernel).get(key)


def record(kernel: str, key: str, config: dict,
           metric_ms: Optional[float] = None) -> bool:
    """Persist one winner (atomic whole-file rewrite; best-effort)."""
    root = _root()
    if not root:
        return False
    table = dict(_load(kernel))
    entry = dict(config)
    if metric_ms is not None:
        entry["ms"] = round(float(metric_ms), 4)
    entry["recorded_at"] = int(time.time())
    table[key] = entry
    path = _path(kernel)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(table, f, sort_keys=True, indent=1)
        os.replace(tmp, path)
    except OSError as exc:
        _LOG.warning("autotune cache write failed: %r", exc)
        return False
    with _lock:
        _mem[kernel] = table
    return True


# -- int8_matmul -------------------------------------------------------------

INT8_KERNEL = "int8_matmul"
# the candidate grid; None = the kernel's adaptive whole-M heuristic
INT8_BLOCK_M = (None, 128)
INT8_BLOCK_N = (128, 256, 512, 1024)


def cached_int8_blocks(
    m: int, k: int, n: int,
) -> Tuple[Optional[int], Optional[int]]:
    """(block_m, block_n) for an ``(m, k) · (k, n)`` int8 matmul from the
    persistent cache, or ``(None, None)`` → the kernel's static
    heuristic.  Called at trace time by
    :func:`~nnstreamer_tpu.ops.pallas_kernels.int8_matmul`."""
    if not enabled():
        return None, None
    entry = best(INT8_KERNEL, make_key(((m, k), (k, n)), "int8"))
    if not entry:
        return None, None
    try:
        bm = entry.get("block_m")
        bn = entry.get("block_n")
        bm = int(bm) if bm is not None else None
        bn = int(bn) if bn is not None else None
    except (TypeError, ValueError):  # corrupt JSON entry: heuristics win
        return None, None
    if (bm is not None and bm <= 0) or (bn is not None and bn <= 0):
        return None, None
    return bm, bn


def autotune_int8_matmul(m: int, k: int, n: int, reps: int = 30,
                         force: bool = False) -> Optional[dict]:
    """On-chip tile search for one int8 matmul geometry; records the
    winner in the persistent cache and returns its entry.  Refuses in
    interpret mode (non-TPU) unless ``force`` — interpret timings would
    poison the cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .pallas_kernels import int8_matmul
    from .quant import quantize_activations, quantize_weight

    if jax.default_backend() != "tpu" and not force:
        _LOG.info("autotune skipped: platform %r runs Pallas in interpret "
                  "mode", jax.default_backend())
        return None
    rng = np.random.default_rng(0)
    a = rng.standard_normal((m, k)).astype(np.float32)
    w = rng.standard_normal((k, n)).astype(np.float32)
    b = np.zeros(n, np.float32)
    qw = quantize_weight(jnp.asarray(w), axis=-1)
    aq, ascale = quantize_activations(jnp.asarray(a))

    def timeit(fn, *args):
        fn(*args).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        out.block_until_ready()
        return (time.perf_counter() - t0) / reps

    best_cfg = None
    for bm in INT8_BLOCK_M:
        for bn in INT8_BLOCK_N:
            try:
                f = jax.jit(lambda q, s, bm=bm, bn=bn: int8_matmul(
                    q, qw.q, s, qw.scale.reshape(1, -1), b,
                    block_m=bm, block_n=bn))
                t = timeit(f, aq, ascale)
            except Exception:  # noqa: BLE001 — illegal tile for this part
                continue
            if best_cfg is None or t < best_cfg[0]:
                best_cfg = (t, bm, bn)
    if best_cfg is None:
        return None
    t, bm, bn = best_cfg
    key = make_key(((m, k), (k, n)), "int8")
    config = {"block_m": bm, "block_n": bn}
    record(INT8_KERNEL, key, config, metric_ms=t * 1e3)
    return dict(config, ms=round(t * 1e3, 4), key=key)
