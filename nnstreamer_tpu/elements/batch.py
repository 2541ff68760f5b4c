"""``tensor_batch`` / ``tensor_unbatch``: the mux→device-mesh batching bridge.

The reference's concurrency story for multi-stream inference is "one
interpreter per element" — N camera streams mean N independent
``tensor_filter`` invokes.  The TPU-native replacement (survey §2.6, §3.3:
``tensor_mux`` is "the batching front-door for the TPU pmap path") turns the
muxed N-tensor frame into ONE batched tensor so a single XLA invoke runs all
streams at once, with the batch dim sharded over the device mesh by the
``jax-sharded`` backend (data parallelism over ICI):

    src×N → tensor_mux → tensor_batch → tensor_filter framework=jax-sharded
          → tensor_unbatch → tensor_demux → sink×N

- ``tensor_batch``   — frame with N same-spec tensors → one ``(N, *shape)``
  tensor (``jnp.stack``: stays on device when inputs are device-resident).
- ``tensor_unbatch`` — inverse: ``(N, *shape)`` → N tensors, so the demuxed
  per-stream outputs line up with the original pads.

Host-side assembly is **slot-wise into a pooled batch buffer** (each row
copied once, directly into its slot of a recycled staging buffer —
``nnstreamer_tpu/pool.py``), never a fresh ``np.stack``, which pays a
cold multi-MB allocation per dispatch.  A mesh-sharded consumer takes the
same buffer: ``(N, *row)`` is the per-shard slot layout its batch-axis
``NamedSharding`` scatters (N divisible by the mesh shards evenly;
otherwise the backend falls back to a single-device executable).
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from ..buffer import Frame
from ..graph.node import NegotiationError, Node, Pad
from ..graph.registry import register_element
from ..obs import hooks as _hooks
from ..spec import TensorSpec, TensorsSpec


@register_element("tensor_batch")
class TensorBatch(Node):
    def __init__(self, name: Optional[str] = None, pool=None):
        super().__init__(name)
        self.add_sink_pad("sink")
        self.add_src_pad("src")
        self._n = 0
        self._pool = pool  # default shared pool unless injected (tests)

    def _pool_or_default(self):
        if self._pool is None:
            from ..pool import default_pool

            self._pool = default_pool()
        return self._pool

    def configure(self, in_specs: Dict[str, TensorsSpec]) -> Dict[str, TensorsSpec]:
        spec = in_specs["sink"]
        if spec.num_tensors < 1:
            raise NegotiationError(f"{self.name}: needs at least one tensor")
        first = spec.tensors[0]
        for t in spec.tensors[1:]:
            if t.shape != first.shape or t.dtype != first.dtype:
                raise NegotiationError(
                    f"{self.name}: all tensors must share one spec to batch; "
                    f"got {t} vs {first}"
                )
        self._n = spec.num_tensors
        out = TensorSpec(dtype=first.dtype, shape=(self._n,) + tuple(first.shape))
        return {"src": TensorsSpec(tensors=(out,), rate=spec.rate)}

    def process(self, pad: Pad, frame: Frame):
        del pad
        import jax

        if any(isinstance(t, jax.Array) for t in frame.tensors):
            import jax.numpy as jnp

            # device-resident inputs: stack on device, stays resident
            return frame.with_tensors((jnp.stack(frame.tensors, axis=0),))
        # host inputs: each row copied ONCE, directly into its slot of a
        # recycled pooled batch buffer — the downstream jax filter's flat
        # wire path then moves the whole batch in a single cheap transfer
        # (np.stack here would add a cold multi-MB allocation per dispatch;
        # per-tensor jnp.stack would pay N tiled-layout device_puts)
        rows = [np.asarray(t) for t in frame.tensors]
        buf = self._pool_or_default().lease(
            (len(rows),) + rows[0].shape, rows[0].dtype
        )
        for i, r in enumerate(rows):
            np.copyto(buf[i], r)
        if _hooks.enabled:
            _hooks.emit("copy", self, buf.nbytes, 1 if buf.pool_fresh else 0)
        return frame.with_tensors((buf,))


@register_element("tensor_unbatch")
class TensorUnbatch(Node):
    def __init__(self, name: Optional[str] = None):
        super().__init__(name)
        self.add_sink_pad("sink")
        self.add_src_pad("src")
        self._to_host = True
        self._split = None  # jitted row-splitter (jit caches per input shape)

    def configure(self, in_specs: Dict[str, TensorsSpec]) -> Dict[str, TensorsSpec]:
        spec = in_specs["sink"]
        if spec.num_tensors != 1:
            raise NegotiationError(f"{self.name}: expects one batched tensor")
        t = spec.tensors[0]
        if t.rank < 1 or t.shape[0] is None:
            raise NegotiationError(f"{self.name}: batch dim must be fixed, got {t}")
        n = t.shape[0]
        per = TensorSpec(dtype=t.dtype, shape=tuple(t.shape[1:]))
        from ..graph.residency import chain_device_resident

        # host consumers read every row anyway: one device→host copy of the
        # whole batch (often already in flight — the upstream filter starts
        # it async) beats N per-row d2h round trips; device consumers get a
        # single compiled split instead of N eager slice dispatches.
        self._to_host = not chain_device_resident(self, "down")
        return {"src": TensorsSpec(tensors=(per,) * n, rate=spec.rate)}

    def _device_split(self, batched):
        if self._split is None:
            import jax

            # x.shape is static under trace; jit's own cache handles any
            # alternation of input shapes across renegotiations
            self._split = jax.jit(
                lambda x: tuple(x[i] for i in range(x.shape[0]))
            )
        return self._split(batched)

    def process(self, pad: Pad, frame: Frame):
        del pad
        from ..buffer import WireTensor

        batched = frame.tensors[0]
        if isinstance(batched, WireTensor):
            if self._to_host:
                # wire-layout payload, host consumers: one d2h materialize
                import numpy as np

                batched = np.asarray(batched)
            else:
                # device consumers: restore logical geometry ON DEVICE
                # (cheap reshape) and split there — never a host round trip
                return frame.with_tensors(
                    self._device_split(batched.data.reshape(batched.shape))
                )
        elif hasattr(batched, "copy_to_host_async"):  # jax Array
            if self._to_host:
                import numpy as np

                batched = np.asarray(batched)
            else:
                return frame.with_tensors(self._device_split(batched))
        # numpy: row views share the parent buffer, no copies
        return frame.with_tensors(tuple(batched[i] for i in range(batched.shape[0])))
