"""Persistent on-disk executable cache: compile once per machine, not per
process.

The TVM lesson (PAPERS.md): search and compile **offline**, serve from the
cache.  PR 5's compile accounting made the per-process tax visible — every
fresh process re-compiles every (geometry, mesh) bucket on the request
path.  This module is the persistence layer under
``jax_backend._compile``:

- **key** = (spec key, mesh key, jax version, jaxlib version, platform,
  fn fingerprint).  The fingerprint is a sha256 over the jax-lowered
  StableHLO text of the exact entry being persisted — it captures the
  model function, fused transform wrappers, and wire-reshape geometry in
  one hash, so a changed model can never serve a stale executable.
- **payload** = ``jax.export`` AOT serialization when the backend
  supports it (same-process deserialize skips Python tracing + jax
  lowering entirely); entries that cannot serialize (mesh-sharded
  programs, exotic primitives) store a meta-only witness and fall back
  to a clean recompile.
- **loads are paranoid**: any mismatch in the stored meta (version bump,
  platform change, fingerprint drift) or a corrupted/truncated payload
  is treated as a miss — the stale entry is deleted and the caller
  recompiles.  Never a crash, never a stale executable.
- jax's own persistent compilation cache (the XLA *binary* cache) is
  placed by :func:`ensure_compile_cache` — the one function in the tree
  that decides where it lives — so the StableHLO→XLA step of a
  deserialized entry is served from disk across processes.

Activation: conf ``[compile] cache_dir`` / ``NNSTPU_COMPILE_CACHE_DIR``;
an empty dir disables the repo's own stores (the XLA binary cache is
always in force).  Layout::

    <cache_dir>/
      exec/<sha>.json       entry meta (key parts, payload kind, size)
      exec/<sha>.exp        jax.export payload (absent for witnesses)
      autotune/<kernel>.json  ops/autotune.py block-config winners
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import tempfile
from typing import Optional, Tuple

_LOG = logging.getLogger("nnstreamer_tpu.backends")

# <checkout>/.jax_cache: a fixed path (the directory is part of jax's cache
# key, so one that moves between runs never hits); listed in .gitignore
DEFAULT_JAX_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

ENTRY_VERSION = 1  # bump to invalidate every on-disk entry at once


def cache_dir() -> str:
    """The configured persistent cache root ('' = persistence off)."""
    from ..conf import conf

    return conf.get_path("compile", "cache_dir", "")


def versions() -> Tuple[str, str]:
    """(jax, jaxlib) version pair baked into every key — a runtime bump
    invalidates cleanly (serialized calling conventions drift)."""
    import jax
    import jaxlib

    return jax.__version__, jaxlib.__version__


def platform() -> str:
    import jax

    try:
        return jax.default_backend()
    except Exception:  # noqa: BLE001 — no backend at all
        return "unknown"


def ensure_compile_cache() -> str:
    """Decide where jax's persistent compilation cache (XLA binaries)
    lives, and return that directory.  The only place in the tree that
    sets it: where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already
    read it and nothing is set here; otherwise the cache goes to the
    fixed ``<checkout>/.jax_cache``.  jax binds its cache to the first
    directory it finds set at a compile, so one rule for every entry
    point is what keeps two of them from disagreeing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            and jax.config.jax_compilation_cache_dir != DEFAULT_JAX_CACHE:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_JAX_CACHE)
    return jax.config.jax_compilation_cache_dir


def fingerprint_lowered(lowered) -> str:
    """sha256 over the lowered StableHLO text — the fn fingerprint key
    part.  Raises on lowerings that cannot render (caller skips
    persistence)."""
    text = lowered.as_text()
    return hashlib.sha256(text.encode("utf-8")).hexdigest()


class ExecutableCache:
    """One on-disk executable cache rooted at ``<dir>/exec``."""

    def __init__(self, root: str):
        self.root = root
        self.dir = os.path.join(root, "exec")

    # -- keys ----------------------------------------------------------------

    @staticmethod
    def make_key(spec_key, mesh_key, fingerprint: str,
                 entry: str = "shaped", tag: str = "") -> dict:
        """The full persistence key as a dict of its parts (all of which
        are validated on load).  ``entry`` distinguishes the shaped
        executable from its flat host-wire twin; ``tag`` carries the
        whole-segment label (graph/segments.py) so a segment-fused
        program and the bare model never share a cache lineage.  An
        empty tag is omitted, keeping pre-segment entry hashes stable."""
        jv, jlv = versions()
        key = {
            "v": ENTRY_VERSION,
            "spec": repr(spec_key),
            "mesh": repr(mesh_key),
            "jax": jv,
            "jaxlib": jlv,
            "platform": platform(),
            "fingerprint": fingerprint,
            "entry": entry,
        }
        if tag:
            key["tag"] = tag
        return key

    @staticmethod
    def _hash(key: dict) -> str:
        blob = json.dumps(key, sort_keys=True).encode("utf-8")
        return hashlib.sha256(blob).hexdigest()

    def _paths(self, key: dict) -> Tuple[str, str]:
        h = self._hash(key)
        return (os.path.join(self.dir, f"{h}.json"),
                os.path.join(self.dir, f"{h}.exp"))

    # -- store ---------------------------------------------------------------

    def store(self, key: dict, payload: Optional[bytes],
              extra: Optional[dict] = None) -> bool:
        """Persist one entry (``payload=None`` writes a meta-only witness
        for programs that cannot serialize — the load path then reports a
        clean miss instead of re-attempting export every process).
        ``extra`` merges additional sidecar facts into the meta (e.g. the
        backend's ``{"hbm": memory_analysis bytes}``) — load validation
        only iterates the KEY's parts, so sidecar keys can never fail a
        lookup; read them back with :meth:`load_meta`.  Best-effort: any
        I/O failure is logged and swallowed."""
        meta_path, payload_path = self._paths(key)
        meta = dict(key)
        if extra:
            for part, val in extra.items():
                if part not in meta:  # key parts stay authoritative
                    meta[part] = val
        meta["payload"] = "export" if payload is not None else "none"
        meta["payload_bytes"] = len(payload) if payload is not None else 0
        try:
            os.makedirs(self.dir, exist_ok=True)
            if payload is not None:
                self._atomic_write(payload_path, payload)
            # meta lands LAST: a crash mid-store leaves a payload without
            # meta (ignored + overwritten later), never meta pointing at
            # a missing/truncated payload that a load would half-trust
            self._atomic_write(
                meta_path, json.dumps(meta, sort_keys=True).encode("utf-8"))
            return True
        except OSError as exc:
            _LOG.warning("executable cache store failed: %r", exc)
            return False

    @staticmethod
    def _atomic_write(path: str, data: bytes) -> None:
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   prefix=".tmp-")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(data)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    # -- load ----------------------------------------------------------------

    def lookup(self, key: dict) -> Optional[Tuple[str, Optional[bytes]]]:
        """``("export", payload)`` / ``("none", None)`` when a valid entry
        exists for ``key`` (the latter a meta-only witness: the geometry
        was compiled before; the XLA binary cache carries the bits), or
        None (absent, meta mismatch, or corrupted — corrupted entries are
        deleted so the recompile's fresh store replaces them)."""
        meta_path, payload_path = self._paths(key)
        try:
            with open(meta_path, "rb") as f:
                meta = json.loads(f.read().decode("utf-8"))
        except (OSError, ValueError):
            if os.path.exists(meta_path):
                self._evict(meta_path, payload_path)  # unparseable meta
            return None
        for part, want in key.items():
            if meta.get(part) != want:
                # a hash collision can't realistically get here, but a
                # hand-edited/corrupt meta can: never trust it
                self._evict(meta_path, payload_path)
                return None
        if meta.get("payload") != "export":
            return ("none", None)
        try:
            with open(payload_path, "rb") as f:
                payload = f.read()
        except OSError:
            self._evict(meta_path, payload_path)
            return None
        if len(payload) != meta.get("payload_bytes"):
            # truncated payload (crash mid-write of an old non-atomic
            # writer, disk-full, operator cp): clean recompile
            self._evict(meta_path, payload_path)
            return None
        return ("export", payload)

    def load(self, key: dict) -> Optional[bytes]:
        """The stored ``jax.export`` payload for ``key``, or None."""
        found = self.lookup(key)
        return found[1] if found is not None else None

    def load_meta(self, key: dict) -> Optional[dict]:
        """The full persisted meta dict (key parts + sidecar extras like
        ``hbm``) when a valid entry exists for ``key``, else None — the
        deep-profiling lane reads the HBM ledger of a warm entry from
        here without reconstructing the executable."""
        meta_path, payload_path = self._paths(key)
        try:
            with open(meta_path, "rb") as f:
                meta = json.loads(f.read().decode("utf-8"))
        except (OSError, ValueError):
            return None
        for part, want in key.items():
            if meta.get(part) != want:
                self._evict(meta_path, payload_path)
                return None
        return meta

    def has(self, key: dict) -> bool:
        """Meta-level presence (payload not read) — warmup planning."""
        meta_path, _ = self._paths(key)
        return os.path.isfile(meta_path)

    @staticmethod
    def _evict(*paths: str) -> None:
        for p in paths:
            try:
                os.unlink(p)
            except OSError:
                pass

    def stats(self) -> dict:
        try:
            names = os.listdir(self.dir)
        except OSError:
            names = []
        metas = [n for n in names if n.endswith(".json")]
        return {"dir": self.dir, "entries": len(metas)}


def configured_cache() -> Optional[ExecutableCache]:
    """The process cache for the conf'd dir, or None when persistence is
    off.  Re-resolved per call (tests flip the conf env var); the
    instance itself is stateless beyond its root path."""
    root = cache_dir()
    if not root:
        return None
    return ExecutableCache(root)


# -- (de)serialization helpers -----------------------------------------------

def serialize_entry(fn, structs) -> Optional[bytes]:
    """``jax.export`` serialization of ``jax.jit(fn)`` at ``structs``;
    None when this program cannot export (the caller stores a witness)."""
    try:
        import jax
        from jax import export as jexport

        exported = jexport.export(jax.jit(fn))(*structs)
        return exported.serialize()
    except Exception as exc:  # noqa: BLE001 — serialization is optional
        _LOG.debug("jax.export serialization unavailable: %r", exc)
        return None


def deserialize_entry(payload: bytes):
    """Rebuild the exported program's ``call``; raises on corrupt bytes
    (the caller treats that as a miss + evict)."""
    from jax import export as jexport

    return jexport.deserialize(payload).call
