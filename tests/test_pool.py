"""Buffer-pool lifecycle (`nnstreamer_tpu.pool`) — the zero-copy hot path.

Pins the contracts the batched front doors now lean on: refcount-aware
recycling (a buffer returns to the free list only when the LAST view
drops — tee fan-out must not recycle early), bounded free-list accounting
(per-class and total-byte eviction, renegotiated size classes draining
out instead of leaking), the async-transfer fence (recycled memory is
never rewritten while a ``device_put``/dispatch issued from it is still
reading), ping-pong ``WireStager`` staging, and the ``copies`` tracer the
CI regression gate reads.
"""

import numpy as np
import pytest

from nnstreamer_tpu.buffer import Frame
from nnstreamer_tpu.pool import (
    BufferPool,
    PooledArray,
    WireStager,
    fence,
)


class FakeInflight:
    """Stands in for a jax.Array: readiness is explicit."""

    def __init__(self):
        self.waits = 0

    def block_until_ready(self):
        self.waits += 1
        return self


class TestLeaseRecycle:
    def test_miss_then_hit_reuses_memory(self):
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        a = pool.lease((8,), np.float32)
        assert isinstance(a, PooledArray) and a.pool_fresh
        ptr = a.ctypes.data
        pool.recycle(a)
        del a
        b = pool.lease((8,), np.float32)
        assert not b.pool_fresh and b.ctypes.data == ptr
        st = pool.stats()
        assert st["hits"] == 1 and st["misses"] == 1 and st["recycles"] == 1

    def test_distinct_classes_never_cross(self):
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        a = pool.lease((8,), np.float32)
        pool.recycle(a)
        del a
        assert pool.lease((8,), np.int32).pool_fresh  # dtype differs
        assert pool.lease((4, 2), np.float32).pool_fresh  # shape differs

    def test_auto_recycle_when_last_ref_drops(self):
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        a = pool.lease((8,), np.float32)
        nbytes = a.nbytes
        assert pool.stats()["leased_bytes"] == nbytes
        del a  # no explicit recycle: the GC finalizer returns it
        st = pool.stats()
        assert st["recycles"] == 1
        assert st["leased_bytes"] == 0 and st["free_bytes"] == nbytes

    def test_views_keep_lease_alive_tee_fanout(self):
        """Two branches holding views of one pooled batch (tee fan-out):
        the buffer must stay leased until BOTH drop."""
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        a = pool.lease((4, 8), np.float32)
        a[:] = 7.0
        branch1 = np.asarray(a)[0]  # base-class views, like frame consumers
        branch2 = np.asarray(a).reshape(32)
        del a
        assert pool.stats()["recycles"] == 0  # views pin the lease
        del branch1
        assert pool.stats()["recycles"] == 0
        np.testing.assert_array_equal(branch2, np.full(32, 7.0, np.float32))
        del branch2
        st = pool.stats()
        assert st["recycles"] == 1 and st["leased_bytes"] == 0

    def test_explicit_recycle_is_idempotent(self):
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        a = pool.lease((8,), np.float32)
        pool.recycle(a)
        pool.recycle(a)  # finalizers fire at most once
        del a
        assert pool.stats()["recycles"] == 1


class TestBounds:
    def test_per_class_overflow_counts_eviction(self):
        pool = BufferPool(max_per_class=1, max_bytes=1 << 20)
        a, b = pool.lease((8,), np.float32), pool.lease((8,), np.float32)
        pool.recycle(a)
        pool.recycle(b)  # class already full: dropped, accounted
        del a, b
        st = pool.stats()
        assert st["evictions"] == 1
        assert st["free_buffers"] == 1 and st["free_bytes"] == 32

    def test_byte_bound_evicts_oldest_first(self):
        """Renegotiation: a stream that switches (8,)→(16,) must drain the
        old size class out of the bounded pool, oldest first."""
        pool = BufferPool(max_per_class=8, max_bytes=96)
        old = [pool.lease((8,), np.float32) for _ in range(2)]  # 32 B each
        for x in old:
            pool.recycle(x)
        del old
        assert pool.stats()["free_bytes"] == 64
        new = pool.lease((16,), np.float32)  # 64 B: the renegotiated shape
        pool.recycle(new)
        del new
        st = pool.stats()
        # 64 + 64 > 96: one old (8,) buffer evicted to make room
        assert st["evictions"] == 1
        assert st["free_bytes"] == 96 and st["free_buffers"] == 2
        # and the survivors are one of each class
        assert st["classes"] == 2

    def test_oversize_buffer_never_pooled(self):
        pool = BufferPool(max_per_class=4, max_bytes=16)
        a = pool.lease((64,), np.float32)
        pool.recycle(a)
        del a
        st = pool.stats()
        assert st["evictions"] == 1 and st["free_bytes"] == 0

    def test_disabled_via_conf_always_fresh(self, monkeypatch):
        monkeypatch.setenv("NNSTPU_POOL_ENABLED", "false")
        pool = BufferPool()  # conf-driven bounds
        a = pool.lease((8,), np.float32)
        pool.recycle(a)
        del a
        b = pool.lease((8,), np.float32)
        assert b.pool_fresh  # nothing was retained
        assert pool.stats()["free_buffers"] == 0


class TestFence:
    def test_fence_blocks_rewrite_until_transfer_ready(self):
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        a = pool.lease((8,), np.float32)
        inflight = FakeInflight()
        assert fence(a, inflight) is True
        pool.recycle(a)
        del a
        assert inflight.waits == 0  # recycle itself never blocks
        b = pool.lease((8,), np.float32)  # rewrite imminent: must wait
        assert not b.pool_fresh and inflight.waits == 1

    def test_fence_through_view_chain(self):
        """Elements fence the VIEW they handed to jax (reshape of an
        asarray of the lease); the owner is found through .base."""
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        a = pool.lease((4, 2), np.float32)
        view = np.asarray(a).reshape(8)
        inflight = FakeInflight()
        assert fence(view, inflight) is True
        del view
        pool.recycle(a)
        del a
        pool.lease((4, 2), np.float32)
        assert inflight.waits == 1

    def test_fence_noop_for_unpooled_arrays(self):
        assert fence(np.zeros(4), FakeInflight()) is False

    def test_fresh_lease_never_waits(self):
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        a = pool.lease((8,), np.float32)
        inflight = FakeInflight()
        fence(a, inflight)
        # a still leased: a second lease allocates fresh, no fence applies
        b = pool.lease((8,), np.float32)
        assert b.pool_fresh and inflight.waits == 0


class _FakeShard:
    def __init__(self, data, device=None):
        self.data = data
        self.device = device


class _FakeSharding:
    def __init__(self, n):
        self.device_set = frozenset(range(n))


class FakeShardedPut:
    """A mesh-sharded ``device_put`` result: one global head wrapper over
    N per-shard committed arrays (each with its own readiness)."""

    def __init__(self, n):
        self.sharding = _FakeSharding(n)
        self._shards = [_FakeShard(FakeInflight()) for _ in range(n)]

    @property
    def addressable_shards(self):
        return list(self._shards)

    def shard_waits(self):
        return [s.data.waits for s in self._shards]


class TestShardedFence:
    """Regression (mesh-sharded dispatch): the fence must pin EVERY
    per-shard committed array of a multi-device put, not just the global
    head — the head wrapper can be dropped while shard transfers are
    still reading the pooled buffer, and a weak head ref alone would
    treat that as "reader gone" and let the recycled memory be rewritten
    under the in-flight shard transfer."""

    def test_every_shard_pins_the_lease(self):
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        a = pool.lease((8,), np.float32)
        put = FakeShardedPut(8)
        assert fence(a, put) is True
        shards = put._shards  # keep shard handles to inspect waits
        del put  # the global head dies; shard transfers still in flight
        pool.recycle(a)
        del a
        b = pool.lease((8,), np.float32)  # rewrite imminent
        assert not b.pool_fresh
        assert [s.data.waits for s in shards] == [1] * 8

    def test_stager_abandons_slot_on_sharded_put(self):
        """WireStager must never rewrite a slot whose last transfer was a
        mesh-sharded put: readiness does not imply the (possibly aliased)
        memory is re-writable, so the slot is abandoned to the pool and
        the next stage() leases a fresh buffer."""
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        stager = WireStager(pool=pool, depth=1)
        src = np.arange(8, dtype=np.float32)[::2]  # strided: forces staging
        buf1 = stager.stage(0, src, (4,))
        put = FakeShardedPut(4)
        stager.track(0, put)
        buf2 = stager.stage(0, src + 1.0, (4,))
        assert buf2 is not buf1  # fresh lease, not an in-place rewrite
        # and the sharded shards were never "waited into" reusability
        np.testing.assert_array_equal(np.asarray(buf1), [0, 2, 4, 6])

    def test_stager_single_device_slot_reuse_intact(self):
        """The ping-pong fast path survives: a single-device transfer
        still gates slot reuse on readiness and reuses the same memory."""
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        stager = WireStager(pool=pool, depth=1)
        src = np.arange(8, dtype=np.float32)[::2]
        buf1 = stager.stage(0, src, (4,))
        inflight = FakeInflight()
        stager.track(0, inflight)
        buf2 = stager.stage(0, src, (4,))
        assert buf2 is buf1 and inflight.waits == 1

    def test_single_device_put_keeps_weak_head_semantics(self):
        """A 1-device sharding is NOT expanded: the head stays a weak ref
        and a dead head (pin already released) never blocks the lease."""
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        a = pool.lease((8,), np.float32)
        put = FakeShardedPut(1)
        shard = put._shards[0]
        assert fence(a, put) is True
        del put  # weakref-able head dies → reader gone
        pool.recycle(a)
        del a
        b = pool.lease((8,), np.float32)
        assert not b.pool_fresh
        assert shard.data.waits == 0  # never expanded, never waited

    def test_real_sharded_put_fences_all_devices(self):
        """The live-fire version: a real jax NamedSharding put over the
        forced-host 8-device mesh round-trips through the fence path on
        the GC discipline.  (NOT explicit recycle(): the CPU client may
        zero-copy ALIAS an aligned host buffer per shard, in which case
        jax's keepalive holds the lease and the buffer simply never
        recycles while the put lives — recycle() would bypass exactly
        that protection, which is why its contract forbids calling it
        with a live sharded reader.)"""
        import gc

        import jax

        from nnstreamer_tpu.parallel.mesh import batch_sharding, make_mesh

        mesh = make_mesh((8,), ("dp",))
        for _ in range(10):  # the copy-vs-alias choice is allocator-timing
            pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
            a = pool.lease((16, 4), np.float32)
            a[:] = np.arange(64, dtype=np.float32).reshape(16, 4)
            put = jax.device_put(np.asarray(a), batch_sharding(mesh, 2))
            assert len(put.sharding.device_set) == 8
            assert fence(a, put) is True
            expect = np.asarray(a).copy()
            del a  # GC path: recycles only once every reader allows it
            gc.collect()
            b = pool.lease((16, 4), np.float32)
            b[:] = 0.0  # rewrite (fresh, or fence-waited recycled memory)
            np.testing.assert_array_equal(np.asarray(put), expect)


class TestWireStager:
    def test_ping_pong_alternates_and_gates_reuse(self):
        pool = BufferPool(max_per_class=8, max_bytes=1 << 20)
        stager = WireStager(pool=pool)
        src = np.arange(8, dtype=np.float32).reshape(2, 4).T  # strided
        b1 = stager.stage(0, src, (8,))
        f1 = FakeInflight()
        stager.track(0, f1)
        b2 = stager.stage(0, src + 1, (8,))
        assert b2.ctypes.data != b1.ctypes.data  # the other slot
        f2 = FakeInflight()
        stager.track(0, f2)
        assert f1.waits == 0
        b3 = stager.stage(0, src + 2, (8,))  # slot 0 again: must wait on f1
        assert f1.waits == 1 and f2.waits == 0
        assert b3.ctypes.data == b1.ctypes.data

    def test_stage_copies_strided_source_once(self):
        stager = WireStager(pool=BufferPool(max_per_class=8,
                                            max_bytes=1 << 20))
        src = np.arange(12, dtype=np.float32).reshape(3, 4).T
        buf = stager.stage(0, src, (12,))
        np.testing.assert_array_equal(
            np.asarray(buf).reshape(src.shape), src)

    def test_reset_returns_buffers_to_pool(self):
        pool = BufferPool(max_per_class=8, max_bytes=1 << 20)
        stager = WireStager(pool=pool)
        stager.stage(0, np.zeros((2, 2), np.float32).T, (4,))
        stager.reset()
        assert pool.stats()["recycles"] == 1


class TestPipelineIntegration:
    """End-to-end lifecycle through real elements."""

    @staticmethod
    def _batch_pipeline(pool, n_frames, shape=(4,), collect=False):
        from nnstreamer_tpu import Pipeline
        from nnstreamer_tpu.elements.batch import TensorBatch
        from nnstreamer_tpu.elements.sink import TensorSink
        from nnstreamer_tpu.elements.testsrc import DataSrc

        frames = [
            Frame.of(np.full(shape, 2 * i, np.float32),
                     np.full(shape, 2 * i + 1, np.float32), pts=i)
            for i in range(n_frames)
        ]
        got = []
        p = Pipeline()
        src = p.add(DataSrc(data=frames))
        batch = p.add(TensorBatch(pool=pool))
        sink = p.add(TensorSink(collect=collect))
        if not collect:
            sink.connect("new-data",
                         lambda f: got.append(np.array(f.tensor(0))))
        p.link_chain(src, batch, sink)
        p.run(timeout=120)
        return p, sink, got

    def test_recycle_after_sink_and_reuse(self):
        """Batches assembled into pooled buffers recycle once the sink is
        done with each frame — after the first miss, every dispatch is a
        pool hit and nothing stays leased."""
        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        _, _, got = self._batch_pipeline(pool, 6, collect=False)
        assert len(got) == 6
        for a in got:  # correctness: rows landed in their slots
            assert a.shape == (2, 4) and a[1][0] == a[0][0] + 1
        st = pool.stats()
        assert st["misses"] == 1 and st["hits"] == 5
        assert st["recycles"] == 6 and st["leased_bytes"] == 0

    def test_collected_frames_pin_their_buffers(self):
        """A sink that RETAINS frames (collect=True) holds views of the
        pooled batches: none may recycle early, and payloads must stay
        intact — the refcount contract under downstream retention."""
        pool = BufferPool(max_per_class=8, max_bytes=1 << 20)
        _, sink, _ = self._batch_pipeline(pool, 4, collect=True)
        st = pool.stats()
        assert st["recycles"] == 0 and st["hits"] == 0  # all 4 still live
        for i, f in enumerate(sink.frames):  # no buffer was rewritten
            np.testing.assert_array_equal(
                np.asarray(f.tensor(0))[0], np.full(4, 2 * i, np.float32))
        del f  # the loop variable would pin the last frame's buffer
        sink.frames.clear()
        assert pool.stats()["recycles"] == 4

    def test_merge_assembles_into_recycled_pooled_buffers(self, monkeypatch):
        """tensor_merge's host result is a pooled lease like tensor_batch's:
        concatenated once into it, recycled when the sink is done, so a
        steady stream allocates no fresh multi-MB result a round."""
        from nnstreamer_tpu import Pipeline
        from nnstreamer_tpu.elements.merge import TensorMerge
        from nnstreamer_tpu.elements.sink import TensorSink
        from nnstreamer_tpu.elements.testsrc import DataSrc

        pool = BufferPool(max_per_class=4, max_bytes=1 << 20)
        monkeypatch.setattr("nnstreamer_tpu.pool.default_pool", lambda: pool)
        p = Pipeline()
        merge = p.add(TensorMerge(option="1", sync_mode="nosync"))
        for k in range(3):
            src = p.add(DataSrc(data=[
                Frame.of(np.full((2, 4), 10 * i + k, np.float32), pts=i)
                for i in range(6)]))
            p.link(src, f"{merge.name}.sink_{k}")
        got, leased = [], []
        sink = p.add(TensorSink())
        sink.connect("new-data", lambda f: (
            leased.append(isinstance(f.tensor(0), PooledArray)),
            got.append(np.array(f.tensor(0)))))
        p.link(merge, sink)
        p.run(timeout=120)
        assert len(got) == 6 and all(leased)
        for i, a in enumerate(got):
            np.testing.assert_array_equal(
                a, np.concatenate([np.full((2, 4), 10 * i + k, np.float32)
                                   for k in range(3)], axis=0))
        st = pool.stats()
        assert st["misses"] == 1 and st["hits"] == 5
        assert st["recycles"] == 6 and st["leased_bytes"] == 0

    def test_dynbatch_padding_path_pools_and_stays_correct(self):
        from nnstreamer_tpu import Pipeline
        from nnstreamer_tpu.backends.jax_backend import JaxModel
        from nnstreamer_tpu.elements.dynbatch import DynBatch, DynUnbatch
        from nnstreamer_tpu.elements.filter import TensorFilter
        from nnstreamer_tpu.elements.sink import TensorSink
        from nnstreamer_tpu.elements.testsrc import DataSrc
        from nnstreamer_tpu.spec import TensorSpec, TensorsSpec

        pool = BufferPool(max_per_class=8, max_bytes=1 << 20)
        frames = [Frame.of(np.full(4, i, np.float32), pts=i)
                  for i in range(9)]
        model = JaxModel(
            apply=lambda p_, x: x + 1.0,
            input_spec=TensorsSpec.of(
                TensorSpec(dtype=np.float32, shape=(None, 4))),
        )
        got = []
        p = Pipeline()
        src = p.add(DataSrc(data=frames))
        dyn = p.add(DynBatch(max_batch=4))
        dyn._pool = pool
        filt = p.add(TensorFilter(framework="jax", model=model))
        unb = p.add(DynUnbatch())
        sink = p.add(TensorSink())
        sink.connect("new-data", lambda f: got.append(np.asarray(f.tensor(0))))
        p.link_chain(src, dyn, filt, unb, sink)
        p.run(timeout=120)
        assert len(got) == 9
        for i, a in enumerate(got):
            np.testing.assert_allclose(a, i + 1.0)
        st = pool.stats()
        assert st["misses"] >= 1
        # jax's jit fastpath keeps the MOST RECENT call's arguments alive
        # (released by the next call), so at most one batch buffer may
        # still be leased — bounded runtime retention, not a pool leak
        assert st["leased_bytes"] <= 4 * 4 * 4  # ≤ one (4, 4) f32 batch
        assert st["recycles"] >= st["misses"] + st["hits"] - 1


class TestCopiesTracer:
    def test_counts_batch_assembly_bytes_per_frame(self):
        from nnstreamer_tpu import Pipeline
        from nnstreamer_tpu.elements.batch import TensorBatch
        from nnstreamer_tpu.elements.sink import TensorSink
        from nnstreamer_tpu.elements.testsrc import DataSrc
        from nnstreamer_tpu.obs.metrics import MetricsRegistry
        from nnstreamer_tpu.obs.tracers import CopiesTracer

        frames = [Frame.of(np.zeros(4, np.float32),
                           np.ones(4, np.float32), pts=i) for i in range(4)]
        reg = MetricsRegistry()
        p = Pipeline()
        src = p.add(DataSrc(data=frames))
        batch = p.add(TensorBatch(pool=BufferPool(max_per_class=4,
                                                  max_bytes=1 << 20)))
        sink = p.add(TensorSink())
        p.link_chain(src, batch, sink)
        tracer = p.attach_tracer(CopiesTracer(registry=reg))
        p.run(timeout=120)
        summ = tracer.summary()
        assert summ["frames"] == 4
        per = summ["elements"][batch.name]
        assert per["copies"] == 4
        assert per["bytes"] == 4 * 2 * 4 * 4  # 4 batches × (2, 4) f32
        assert per["allocs"] == 1  # first lease only; the rest pooled
        assert summ["bytes_per_frame"] == pytest.approx(per["bytes"] / 4)
        from nnstreamer_tpu.obs.export import render_text

        text = render_text(reg)
        assert "nnstpu_copy_bytes_total" in text
