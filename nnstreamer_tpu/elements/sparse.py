"""``tensor_sparse_enc`` / ``tensor_sparse_dec``: sparse tensor transport.

Upstream GStreamer-nnstreamer 2.x grew ``tensor_sparse_enc``/``_dec``
(``gst/nnstreamer/elements/gsttensor_sparseenc.c`` upstream; the reference
snapshot predates them): mostly-zero tensors (segmentation masks, one-hot
frames, pruned activations) cross pipeline boundaries as (indices, values)
pairs instead of dense buffers.  TPU-first this matters twice over:

- every byte crosses the host↔device **wire**, and sparse frames shrink
  linearly with density;
- the ``tensor_query`` TCP offload (one process owns the chip) ships
  frames between processes — sparse encoding is the natural codec for it.

Format — **self-describing, tensors-only** (upstream likewise packs its
header into the payload): the encoded frame has three tensors

1. ``header`` int64 ``[empty_flag, dtype_code, d0, d1, ...]`` — the dense
   shape and dtype ride IN BAND, so meta-dropping transports (the
   ``tensor_query`` TCP protocol ships tensors + pts only) still decode;
2. ``indices`` int64, flat positions into the C-contiguous dense layout;
3. ``values`` in the original dtype.

An all-zero tensor sets ``empty_flag`` and ships one sentinel index/value
slot (the spec layer forbids zero-sized dims, matching upstream's refusal
of empty memories).

Both elements negotiate per-frame-variable lengths via partial specs
(``(None,)``), so they sit in front of sinks/queues/query clients — not
in front of a jitted ``tensor_filter`` (decode first; static shapes are
what the MXU wants).  A ``tensor_query_client`` carrying sparse frames
needs ``out_spec=`` (its zero-frame negotiation probe requires fixed
shapes; sparse lengths vary per frame).

Lossless round-trip is pinned by tests, including NaN values, the
all-zero frame, and a meta-stripping transport in between.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from ..buffer import Frame
from ..graph.node import NegotiationError, Node, Pad
from ..graph.registry import register_element
from ..spec import TensorSpec, TensorsSpec, dtype_from_name, dtype_name

# dtype wire codes (stable contract — append only).  Exactly the spec
# layer's negotiable dtypes: anything a pipeline can carry, the codec can
# ship — including float16/bfloat16, the natural dtypes for the
# pruned-activations use case.
_DTYPES = ("int8", "uint8", "int16", "uint16", "int32", "uint32", "int64",
           "uint64", "float32", "float64", "float16", "bfloat16")
_DTYPE_CODE = {name: i for i, name in enumerate(_DTYPES)}


@register_element("tensor_sparse_enc")
class SparseEnc(Node):
    def __init__(self, name: Optional[str] = None):
        super().__init__(name)
        self.add_sink_pad("sink")
        self.add_src_pad("src")
        self._in_spec: Optional[TensorSpec] = None
        self.frames_in = 0
        self.bytes_in = 0
        self.bytes_out = 0  # observability: achieved compression

    def configure(self, in_specs: Dict[str, TensorsSpec]) -> Dict[str, TensorsSpec]:
        spec = in_specs["sink"]
        if spec.num_tensors != 1:
            raise NegotiationError(
                f"{self.name}: sparse encoding is per-tensor; got "
                f"{spec.num_tensors} tensors/frame"
            )
        self._in_spec = spec.tensors[0]
        if dtype_name(self._in_spec.dtype) not in _DTYPE_CODE:
            raise NegotiationError(
                f"{self.name}: unsupported dtype {self._in_spec.dtype} "
                f"(wire codes: {_DTYPES})"
            )
        return {"src": TensorsSpec(
            tensors=(
                TensorSpec(dtype=np.int64, shape=(None,)),  # header
                TensorSpec(dtype=np.int64, shape=(None,)),  # indices
                TensorSpec(dtype=self._in_spec.dtype, shape=(None,)),
            ),
            rate=spec.rate,
        )}

    def process(self, pad: Pad, frame: Frame):
        del pad
        self.frames_in += 1
        dense = np.asarray(frame.tensor(0))
        flat = np.ascontiguousarray(dense).reshape(-1)
        # NaN is a value, not a zero: != keeps it (NaN != 0 is True)
        (nz,) = np.nonzero(flat != 0)
        empty = nz.size == 0
        if empty:  # zero-sized dims are forbidden; ship one sentinel slot
            idx = np.zeros((1,), np.int64)
            vals = np.zeros((1,), dense.dtype)
        else:
            idx = nz.astype(np.int64)
            vals = flat[nz]
        header = np.asarray(
            [int(empty), _DTYPE_CODE[dtype_name(dense.dtype)]]
            + [int(d) for d in dense.shape],
            np.int64,
        )
        self.bytes_in += dense.nbytes
        self.bytes_out += header.nbytes + idx.nbytes + vals.nbytes
        self.src_pads["src"].push(Frame(
            tensors=(header, idx, vals), pts=frame.pts,
            duration=frame.duration, meta=dict(frame.meta),
        ))
        return None


@register_element("tensor_sparse_dec")
class SparseDec(Node):
    def __init__(self, name: Optional[str] = None):
        super().__init__(name)
        self.add_sink_pad("sink")
        self.add_src_pad("src")
        self.frames_in = 0

    def configure(self, in_specs: Dict[str, TensorsSpec]) -> Dict[str, TensorsSpec]:
        spec = in_specs["sink"]
        if spec.num_tensors != 3:
            raise NegotiationError(
                f"{self.name}: expects (header, indices, values) frames "
                f"from tensor_sparse_enc; got {spec.num_tensors} tensors"
            )
        for i in (0, 1):
            if np.dtype(spec.tensors[i].dtype) != np.int64:
                raise NegotiationError(
                    f"{self.name}: tensor {i} must be int64, got "
                    f"{spec.tensors[i].dtype}"
                )
        # dense shape rides in the per-frame header; downstream negotiates
        # open dims with the values dtype
        return {"src": TensorsSpec(
            tensors=(TensorSpec(dtype=spec.tensors[2].dtype, shape=None),),
            rate=spec.rate,
        )}

    def process(self, pad: Pad, frame: Frame):
        del pad
        self.frames_in += 1
        header = np.asarray(frame.tensor(0))
        if header.ndim != 1 or header.size < 2:
            raise ValueError(
                f"{self.name}: malformed sparse header (size {header.size}; "
                "upstream must be tensor_sparse_enc)"
            )
        empty, code = int(header[0]), int(header[1])
        if not 0 <= code < len(_DTYPES):
            raise ValueError(f"{self.name}: unknown dtype code {code}")
        shape = tuple(int(d) for d in header[2:])
        if any(d <= 0 for d in shape):
            raise ValueError(f"{self.name}: bad dense shape {shape}")
        dtype = dtype_from_name(_DTYPES[code])
        dense = np.zeros(int(np.prod(shape)), dtype)
        if not empty:
            idx = np.asarray(frame.tensor(1))
            vals = np.asarray(frame.tensor(2))
            if idx.size != vals.size:
                raise ValueError(
                    f"{self.name}: sparse frame has {idx.size} indices but "
                    f"{vals.size} values (corrupt or truncated transport)"
                )
            if idx.size and (idx.min() < 0 or idx.max() >= dense.size):
                raise ValueError(
                    f"{self.name}: sparse indices out of range for shape "
                    f"{shape}"
                )
            dense[idx] = vals.astype(dtype, copy=False)
        self.src_pads["src"].push(Frame(
            tensors=(dense.reshape(shape),), pts=frame.pts,
            duration=frame.duration, meta=dict(frame.meta),
        ))
        return None
