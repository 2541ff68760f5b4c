"""Host staging-buffer pool + zero-copy batch assembly.

The batched front doors (``tensor_mux → tensor_batch``, ``tensor_dynbatch``)
are the throughput levers of this framework, but their coalescing step was a
fresh ``np.stack`` per dispatch: every batch paid one full memcpy pass PLUS
a cold multi-MB allocation (mmap + page-fault zeroing — the hidden second
pass).  The reference's answer is recycled, ref-counted buffers
(``GstBufferPool`` + the ``allocate_in_invoke`` zero-copy hand-off,
``tensor_filter.c:350-399``); this module is that discipline for the
TPU-native hot path:

- :class:`BufferPool` — a size-classed, bounded pool of host staging
  buffers keyed by ``(shape, dtype)``.  ``lease()`` hands out a
  :class:`PooledArray`; recycling is **refcount-aware**: numpy views keep
  their base alive, so a leased buffer returns to the free list only when
  the last frame/view referencing it is dropped (a GC finalizer — the
  GstBuffer unref analog).  Explicit :meth:`BufferPool.recycle` exists for
  owners that know the buffer is theirs alone (staging loops).
- :class:`WireStager` — double-buffered (ping-pong) pooled staging for
  host→device wire copies: frame N+1's host copy proceeds while frame N's
  ``device_put``/dispatch is still in flight; a slot is only rewritten
  after the transfer issued from it completed.
- :func:`fence` — the async-transfer guard.  ``device_put``/dispatch
  return BEFORE the host buffer has been read (jax copies lazily), so a
  pooled buffer that recycles and is rewritten while a transfer issued
  from it is still in flight corrupts that transfer's payload.  An
  element that hands a pooled buffer to jax registers the in-flight
  device array against the buffer; ``lease()`` blocks on pending fences
  before handing the recycled memory back out for rewriting.  (Merely
  *dropping* the buffer is always safe — jax pins the source for the
  copy's duration; only rewrite-after-recycle needs the gate.)

Knobs (env ``NNSTPU_POOL_*`` > ini ``[pool]`` > defaults, the standard
conf precedence): ``enabled``, ``max_per_class``, ``max_bytes``.

Observability: the default pool publishes ``nnstpu_pool_*`` metrics
(hit/miss/eviction/recycle counters, leased/free-bytes gauges) on the obs
registry, and every element that does a host memcpy on this path emits the
``copy`` hook (see :class:`~nnstreamer_tpu.obs.tracers.CopiesTracer`), so
copy regressions are observable and CI-gateable (``tools/run_ci.sh``).
"""

from __future__ import annotations

import ctypes
import threading
import weakref
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

DEFAULT_MAX_PER_CLASS = 4
DEFAULT_MAX_BYTES = 64 << 20        # 64 MiB of *free* (pooled) bytes


def _conf_int(key: str, default: int) -> int:
    from .conf import conf

    try:
        return conf.get_int("pool", key, default)
    except ValueError:
        return default


def _conf_bool(key: str, default: bool) -> bool:
    from .conf import conf

    try:
        return conf.get_bool("pool", key, default)
    except ValueError:
        return default


class PooledArray(np.ndarray):
    """A leased staging buffer that presents as a plain ndarray.

    Views taken from it (batch rows, flat wire reshapes, ``np.asarray``
    results) hold the lease through numpy's base chain, so the underlying
    buffer cannot recycle while any consumer — a tee branch, an in-flight
    ``device_put`` holding the host array, a collected sink frame — still
    references it.  When the last reference drops, the pool's finalizer
    returns the buffer to the free list.  ``pool_fresh`` is True when the
    lease allocated (pool miss) rather than recycled (used by the
    ``copy`` hook's allocation count).

    numpy collapses ``.base`` chains to the allocation OWNER, skipping
    intermediate view objects — so the refcount handle cannot be an
    ndarray.  Each lease therefore wraps the pooled memory in a per-lease
    ctypes shim (``_lease_shim``): numpy base chains terminate at that
    non-ndarray buffer owner, every view of the lease keeps it alive, and
    its weakref finalizer IS the last-reference-dropped event (the
    GstBuffer unref analog).  The shim also carries ``_pool_owner`` /
    ``_pool_raw`` so :func:`fence` can find the pool from any view.
    """

    # plain attribute storage (ndarray subclasses allow it); set by lease()
    pool_fresh: bool


def _lease_shim(raw: np.ndarray):
    """Per-lease buffer-protocol handle over ``raw``'s memory (no copy)."""
    return (ctypes.c_byte * raw.nbytes).from_buffer(raw)


class BufferPool:
    """Size-classed, bounded pool of recycled host staging buffers.

    Bounds apply to the FREE list only (leased buffers are owned by their
    frames): at most ``max_per_class`` free buffers per ``(shape, dtype)``
    class and ``max_bytes`` free bytes overall.  A recycle that would
    overflow evicts oldest-free-first (so a renegotiated stream's old size
    classes drain out instead of leaking), then drops the incoming buffer
    if it still does not fit — every drop is accounted as an eviction.
    """

    def __init__(
        self,
        max_per_class: Optional[int] = None,
        max_bytes: Optional[int] = None,
        registry=None,
    ):
        if max_per_class is None:
            max_per_class = (
                _conf_int("max_per_class", DEFAULT_MAX_PER_CLASS)
                if _conf_bool("enabled", True) else 0
            )
        if max_bytes is None:
            max_bytes = _conf_int("max_bytes", DEFAULT_MAX_BYTES)
        self.max_per_class = int(max_per_class)
        self.max_bytes = int(max_bytes)
        self._lock = threading.RLock()
        self._free: Dict[Tuple[Tuple[int, ...], str], deque] = {}
        self._order: deque = deque()  # recycle-order mirror of _free entries
        # id(raw) -> [(weakref(raw), inflight), ...]: async transfers still
        # reading a buffer; the id is revalidated through the weakref so a
        # reused id after eviction can never block an unrelated buffer
        self._fences: Dict[int, List] = {}
        self._free_bytes = 0
        self._leased_bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.recycles = 0
        self._metrics = None
        if registry is not None:
            self._metrics = {
                "hits": registry.counter(
                    "nnstpu_pool_hits_total",
                    "Buffer-pool leases served from the free list"),
                "misses": registry.counter(
                    "nnstpu_pool_misses_total",
                    "Buffer-pool leases that allocated a fresh buffer"),
                "evictions": registry.counter(
                    "nnstpu_pool_evictions_total",
                    "Pooled buffers dropped by the free-list bounds"),
                "recycles": registry.counter(
                    "nnstpu_pool_recycles_total",
                    "Buffers returned to the pool (finalizer or explicit)"),
                "leased": registry.gauge(
                    "nnstpu_pool_leased_bytes",
                    "Bytes currently leased out of the pool"),
                "free": registry.gauge(
                    "nnstpu_pool_free_bytes",
                    "Bytes currently idle on the pool free list"),
            }

    # -- lease / recycle ----------------------------------------------------

    @staticmethod
    def _key(shape, dtype) -> Tuple[Tuple[int, ...], str]:
        return (tuple(int(d) for d in shape), np.dtype(dtype).str)

    def lease(self, shape: Sequence[int], dtype) -> PooledArray:
        """A writable ``(shape, dtype)`` host buffer: recycled when the
        class has a free one, freshly allocated otherwise.  The returned
        :class:`PooledArray` auto-recycles when its last reference (or
        last view) drops."""
        key = self._key(shape, dtype)
        raw = None
        with self._lock:
            dq = self._free.get(key)
            if dq:
                raw = dq.pop()  # LIFO: the warmest pages
                self._order.remove(key)
                self._free_bytes -= raw.nbytes
                self.hits += 1
            else:
                self.misses += 1
        self._m_inc("hits" if raw is not None else "misses")
        fresh = raw is None
        if fresh:
            raw = np.empty(tuple(shape), np.dtype(dtype))
        else:
            # recycled memory must not be rewritten while an async transfer
            # issued from its previous life is still reading it
            self._wait_fences(raw)
        shim = _lease_shim(raw)
        shim._pool_owner = self  # fence() resolves the pool through here
        shim._pool_raw = raw
        arr = (np.frombuffer(shim, dtype=raw.dtype)
               .reshape(raw.shape).view(PooledArray))
        arr.pool_fresh = fresh
        # the finalizer fires exactly when the shim — which every view of
        # this lease keeps alive — is gone; its args hold the only
        # long-lived strong ref to ``raw`` while leased.  Kept on the
        # array so recycle() can trigger it early.
        arr._pool_finalizer = weakref.finalize(shim, self._give_back, raw)
        with self._lock:
            self._leased_bytes += raw.nbytes
        self._publish()
        return arr

    def recycle(self, arr: PooledArray) -> None:
        """Explicit early return for an exclusively-owned lease (staging
        loops).  The GC finalizer is the safe default — only call this
        when no view of ``arr`` can still be read by anyone else.  A
        mesh-sharded ``device_put`` counts as such a reader for as long
        as its array lives: the CPU client may zero-copy alias the host
        memory per shard, which no fence wait can make re-writable (the
        GC path is safe — jax's keepalive pins the source).  Idempotent
        (a finalizer fires at most once)."""
        fin = getattr(arr, "_pool_finalizer", None)
        if fin is not None:
            fin()

    def _give_back(self, raw: np.ndarray) -> None:
        key = self._key(raw.shape, raw.dtype)
        evicted = 0
        with self._lock:
            self._leased_bytes -= raw.nbytes
            self.recycles += 1
            dq = self._free.setdefault(key, deque())
            if len(dq) >= self.max_per_class:
                evicted += 1  # class full: drop the incoming buffer
                self._fences.pop(id(raw), None)  # freeing is always safe
            else:
                # total-bytes bound: evict oldest free buffers until it fits
                while (self._order
                       and self._free_bytes + raw.nbytes > self.max_bytes):
                    evicted += self._evict_oldest_locked()
                if raw.nbytes > self.max_bytes:
                    evicted += 1  # can never fit: drop
                    self._fences.pop(id(raw), None)
                    if not dq:
                        del self._free[key]
                else:
                    dq.append(raw)
                    self._order.append(key)
                    self._free_bytes += raw.nbytes
            self.evictions += evicted
        self._m_inc("recycles")
        if evicted:
            self._m_inc("evictions", evicted)
        self._publish()

    def _evict_oldest_locked(self) -> int:
        key = self._order.popleft()
        dq = self._free[key]
        victim = dq.popleft()  # FIFO within the class: coldest pages first
        if not dq:
            del self._free[key]
        self._free_bytes -= victim.nbytes
        self._fences.pop(id(victim), None)  # freeing needs no fence wait
        del victim
        return 1

    # -- async-transfer fences ----------------------------------------------

    def _fence_raw(self, raw: np.ndarray, inflight: Any) -> None:
        # the in-flight array is held WEAKLY: jax's runtime keeps the host
        # source (and so the lease shim) pinned while it reads, and a dead
        # head means that pin was released — whereas a strong ref here
        # would circularly pin the head's own inputs and leak the class.
        # A MESH-SHARDED put is the exception: its per-shard committed
        # arrays each read the host buffer on their own schedule and the
        # global head wrapper can die while shard transfers are still in
        # flight, so every shard must pin the fence individually.  Shard
        # ``.data`` objects are fresh wrappers (a weakref to one dies
        # immediately) — they are held strongly, bounded by fence lifetime
        # exactly like any other non-weakref-able reader.
        shard_readers = _shard_readers(inflight)
        try:
            inflight = weakref.ref(inflight)
        except TypeError:
            pass  # not weakref-able: hold it (bounded by fence lifetime)
        with self._lock:
            self._fences.setdefault(id(raw), []).append(
                (weakref.ref(raw), inflight, shard_readers)
            )

    def _wait_fences(self, raw: np.ndarray) -> None:
        with self._lock:
            fences = self._fences.pop(id(raw), None)
        if not fences:
            return
        for wr, head, shard_readers in fences:
            if wr() is not raw:
                continue  # stale id-reuse entry: not this buffer
            readers = list(shard_readers) if shard_readers else []
            if isinstance(head, weakref.ref):
                head = head()
                # a dead head with no per-shard readers means the single
                # reader's pin was already released
            if head is not None:
                readers.append(head)
            for reader in readers:
                wait = getattr(reader, "block_until_ready", None)
                if wait is None:
                    continue
                try:
                    wait()
                except Exception:
                    # a failed computation released its inputs either way
                    pass

    # -- introspection ------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "recycles": self.recycles,
                "leased_bytes": self._leased_bytes,
                "free_bytes": self._free_bytes,
                "free_buffers": sum(len(d) for d in self._free.values()),
                "classes": len(self._free),
            }

    def _m_inc(self, name: str, amount: float = 1.0) -> None:
        if self._metrics is not None:
            self._metrics[name].inc(amount)

    def _publish(self) -> None:
        m = self._metrics
        if m is None:
            return
        with self._lock:
            leased, free = self._leased_bytes, self._free_bytes
        m["leased"].set(leased)
        m["free"].set(free)


def _shard_readers(inflight: Any) -> Optional[list]:
    """Per-shard committed arrays of a multi-device (mesh-sharded) array,
    or None for single-device / non-jax readers.  Duck-typed on
    ``sharding.device_set`` + ``addressable_shards`` so a fake put in
    tests exercises the same path as a real ``NamedSharding`` put."""
    try:
        sharding = inflight.sharding
        if len(sharding.device_set) <= 1:
            return None
        shards = inflight.addressable_shards
    except Exception:  # noqa: BLE001 — not a sharded device array
        return None
    try:
        readers = [s.data for s in shards]
    except Exception:  # noqa: BLE001
        return None
    return readers if len(readers) > 1 else None


# -- default pool ------------------------------------------------------------

_default_pool: Optional[BufferPool] = None
_default_lock = threading.Lock()


def default_pool() -> BufferPool:
    """The process-wide pool the hot-path elements share (constructed on
    first use from conf; publishes ``nnstpu_pool_*`` on the obs registry)."""
    global _default_pool
    if _default_pool is None:
        with _default_lock:
            if _default_pool is None:
                from .obs.metrics import REGISTRY

                _default_pool = BufferPool(registry=REGISTRY)
    return _default_pool


def reset_default_pool() -> None:
    """Drop the default pool so the next use re-reads conf (test isolation /
    mid-process reconfiguration)."""
    global _default_pool
    with _default_lock:
        _default_pool = None


# -- async-transfer fence -----------------------------------------------------

def fence(arr: Any, inflight: Any) -> bool:
    """Register ``inflight`` (a device array — anything with
    ``block_until_ready``) as an async reader of ``arr``'s underlying
    pooled buffer.  No-op returning False when ``arr`` is not pool-backed.

    ``jax.device_put`` and compiled dispatch return before the host
    source has been copied, so a pooled buffer that recycles and is
    rewritten while such a transfer is in flight corrupts the transfer's
    payload (frame N silently carries frame N+k's data).  Every element
    that hands a pooled buffer to jax must fence it with the resulting
    device array; the owning pool then blocks in ``lease()`` before that
    memory is handed back out for rewriting.  GC'ing/evicting the buffer
    needs no fence — jax pins the source object for the copy's duration;
    only rewrite-after-recycle is hazardous.
    """
    node = arr
    while isinstance(node, np.ndarray):
        node = node.base
    # every view of a lease bottoms out at the per-lease shim
    owner = getattr(node, "_pool_owner", None)
    if owner is None:
        return False
    owner._fence_raw(node._pool_raw, inflight)
    return True


# -- ping-pong wire staging --------------------------------------------------

class WireStager:
    """Double-buffered pooled staging for host→device wire copies.

    ``stage(idx, arr, wire_shape)`` copies ``arr`` into one of ``depth``
    (default 2) leased buffers for tensor index ``idx``, alternating
    slots; ``track(idx, put)`` registers the in-flight device array issued
    from the staged buffer.  A slot is rewritten only after the transfer
    previously issued from it reports ready — so frame N+1's host copy
    overlaps frame N's ``device_put``/dispatch instead of waiting behind
    it (jax never aliases the host buffer: ``device_put`` copies, so a
    ready put means the staging buffer is reusable).
    """

    def __init__(self, pool: Optional[BufferPool] = None, depth: int = 2):
        self._pool = pool
        self._depth = max(1, int(depth))
        self._slots: Dict[int, dict] = {}
        # fresh allocations behind the LAST stage() call (for the copy hook:
        # a reused slot buffer is 0 allocs regardless of its lease history)
        self.last_alloc = 0

    def _pool_or_default(self) -> BufferPool:
        if self._pool is None:
            self._pool = default_pool()
        return self._pool

    def stage(self, idx: int, arr: np.ndarray,
              wire_shape: Tuple[int, ...]) -> PooledArray:
        slot = self._slots.get(idx)
        if slot is None:
            slot = self._slots[idx] = {
                "bufs": [None] * self._depth,
                "busy": [None] * self._depth,
                "turn": 0,
            }
        k = slot["turn"] % self._depth
        slot["turn"] += 1
        slot["last"] = k
        inflight = slot["busy"][k]
        if inflight is not None:
            wait = getattr(inflight, "block_until_ready", None)
            if wait is not None:
                wait()  # transfer from this slot finished ⇒ safe to rewrite
            slot["busy"][k] = None
        buf = slot["bufs"][k]
        if (buf is None or tuple(buf.shape) != tuple(wire_shape)
                or buf.dtype != arr.dtype):
            buf = self._pool_or_default().lease(wire_shape, arr.dtype)
            slot["bufs"][k] = buf
            self.last_alloc = 1 if buf.pool_fresh else 0
        else:
            self.last_alloc = 0
        # copy through the LOGICAL geometry: the staging buffer is
        # contiguous, so viewing it row-major as arr.shape is free, and the
        # strided read of a non-contiguous ``arr`` happens exactly once
        np.copyto(buf.reshape(arr.shape), arr)
        return buf

    def track(self, idx: int, inflight) -> None:
        """Register the device array issued from the last staged buffer of
        ``idx`` (its readiness gates the slot's next reuse — and, via the
        pool fence, any rewrite after the buffer returns to the pool on
        ``reset()``/GC).

        A MESH-SHARDED put never gates a rewrite: the CPU client may
        zero-copy ALIAS an aligned host buffer per shard, so readiness
        does not mean the memory is re-writable — the slot is abandoned
        to the pool instead (jax's keepalive holds an aliased buffer
        until the device array drops; a copied one recycles through the
        normal fence discipline), and the next stage() leases afresh."""
        slot = self._slots.get(idx)
        if slot is not None and "last" in slot:
            k = slot["last"]
            buf = slot["bufs"][k]
            if buf is not None:
                fence(buf, inflight)
            if _shard_readers(inflight) is not None:
                slot["bufs"][k] = None
                slot["busy"][k] = None
            else:
                slot["busy"][k] = inflight

    def reset(self) -> None:
        """Forget all slots (renegotiation): buffers return to the pool via
        their finalizers once any in-flight transfers drop them."""
        self._slots.clear()
