"""A metric's lock is re-entered on one thread when a garbage collection
inside ``Gauge.set`` runs a finalizer that publishes to the same metric
(``HostPool._give_back`` -> ``_publish``, under jax's buffer collection):
with a plain ``Lock`` that thread waits for itself for ever."""

import gc
import threading

import numpy as np

from nnstreamer_tpu.obs.metrics import MetricsRegistry
from nnstreamer_tpu.pool import BufferPool


def run_with_deadline(fn, seconds=20.0):
    done = []
    t = threading.Thread(target=lambda: done.append(fn()), daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), "deadlock: the thread re-entered a lock it holds"
    return done[0]


class CollectsInside(float):
    """A value whose conversion inside ``set`` (under the child's lock) runs
    a collection, as an allocation at the wrong moment does."""

    def __new__(cls, value, on_collect):
        self = super().__new__(cls, value)
        self.on_collect = on_collect
        return self

    def __float__(self):
        self.on_collect()
        return super().__float__()


def test_a_collection_inside_gauge_set_may_publish_to_the_same_gauge():
    reg = MetricsRegistry()
    gauge = reg.gauge("nnstpu_test_reentry", "a gauge")
    gauge.set(1.0)
    seen = []

    class Finalized:
        def __del__(self):
            gauge.set(7.0)          # what the pool's finalizer does
            seen.append("published")

    def collect():
        a = Finalized()
        a.cycle = a                 # only a collection frees it
        del a
        gc.collect()

    def body():
        gauge.set(CollectsInside(3.0, collect))
        return dict(gauge.children())[()].value

    assert run_with_deadline(body) == 3.0 and seen == ["published"]


def test_labels_and_default_may_be_re_entered_too():
    reg = MetricsRegistry()
    counter = reg.counter("nnstpu_test_reentry_total", "c", labelnames=("k",))
    counter.inc(k="a")

    def body():
        with counter._lock:         # a collection inside labels()/_default()
            counter.inc(k="b")      # ... whose finalizer touches the metric
        with reg._lock:
            reg.gauge("nnstpu_test_reentry_other", "g").set(1)
        return sorted(k for (k,), _ in counter.children())

    assert run_with_deadline(body) == ["a", "b"]


def test_the_pools_give_back_under_its_own_lease_does_not_hang():
    """``lease`` -> a collection -> an earlier lease's finalizer ->
    ``_give_back`` -> ``_publish``: the pool's lock and the gauges' are
    taken again by the thread that holds them."""
    pool = BufferPool(max_per_class=4, max_bytes=1 << 20)

    def body():
        first = pool.lease((16,), np.float32)
        fin = first._pool_finalizer
        with pool._lock:
            fin()                   # the finalizer, on the holder's thread
        pool._publish()
        return pool.recycles

    assert run_with_deadline(body) == 1
