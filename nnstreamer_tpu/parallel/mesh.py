"""Device-mesh helpers: the TPU-native replacement for the reference's
per-backend accelerator offload (survey §2.6).

The reference never shards — one Interpreter per element, NNAPI/Movidius
offload per frame.  Here parallel invocation is first-class: a
:func:`make_mesh` over the chip's cores (or a CPU-device mesh in tests via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``), batch sharding via
``NamedSharding`` and XLA-inserted collectives over ICI.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    shape: Optional[Sequence[int]] = None,
    axis_names: Tuple[str, ...] = ("dp",),
    devices=None,
) -> Mesh:
    """Build a mesh over available devices.  Default: 1-D data-parallel mesh
    over all devices."""
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    n = 1
    for s in shape:
        n *= s
    if n > len(devices):
        raise ValueError(f"mesh shape {shape} needs {n} devices, have {len(devices)}")
    import numpy as np

    arr = np.array(devices[:n]).reshape(shape)
    return Mesh(arr, axis_names)


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> int:
    """Join a multi-host JAX job (the DCN side of the comm backend).

    The reference's concurrency never leaves one process (no NCCL/MPI —
    survey §2.6); scaling past one host here is the standard JAX recipe:
    every host calls this (TPU pods auto-discover via the metadata server,
    so all arguments may be None; explicit coordinator/process args cover
    CPU/GPU clusters), after which ``jax.devices()`` spans the whole job.
    A :func:`make_mesh` over that global device list lays dp/tp axes so
    XLA routes collectives over ICI within a slice and DCN across hosts —
    the ``jax.distributed`` analog of the NCCL/MPI backends the reference
    never had.  Returns the process count.  Idempotent: a second call is a
    no-op.
    """
    if jax.distributed.is_initialized():
        return jax.process_count()  # already joined: no-op
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return jax.process_count()


def init_from_env() -> int:
    """Join the multi-host job described by ``NNS_MULTIHOST_*`` env vars.

    The contract ``tools/launch_multihost.py`` (the torchrun/mpirun analog
    the reference never needed) exports to every worker it spawns:

    - ``NNS_MULTIHOST_COORD``  — ``host:port`` of process 0's coordinator
    - ``NNS_MULTIHOST_NPROCS`` — total process count
    - ``NNS_MULTIHOST_PROC_ID`` — this process's rank

    With none of them set, falls back to :func:`init_distributed`'s
    auto-discovery (TPU pods find the coordinator via the metadata
    server).  Returns the process count."""
    import os

    # empty string == missing: a wrapper exporting an unset shell var must
    # get the contextual error, not a bare int('') ValueError
    coord = os.environ.get("NNS_MULTIHOST_COORD") or None
    nprocs = os.environ.get("NNS_MULTIHOST_NPROCS") or None
    pid = os.environ.get("NNS_MULTIHOST_PROC_ID") or None
    if coord is None and nprocs is None and pid is None:
        return init_distributed()
    if coord is None or nprocs is None or pid is None:
        raise ValueError(
            "incomplete NNS_MULTIHOST_* env: need COORD, NPROCS and "
            f"PROC_ID together (got coord={coord!r}, nprocs={nprocs!r}, "
            f"proc_id={pid!r})"
        )
    try:
        n, p = int(nprocs), int(pid)
    except ValueError:
        raise ValueError(
            f"NNS_MULTIHOST_NPROCS={nprocs!r} / PROC_ID={pid!r} must be "
            "integers"
        ) from None
    return init_distributed(coord, n, p)


def batch_sharding(mesh: Mesh, rank: int, axis: str = "dp") -> NamedSharding:
    """Shard the leading (batch) dim over ``axis``, replicate the rest."""
    return NamedSharding(mesh, P(axis, *([None] * (rank - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# -- the dispatch mesh (global data-parallel placement mode) ------------------
#
# ``NNSTPU_MESH=dp:8`` (short env spelling) / ini ``[mesh] spec`` turns on
# mesh-sharded dispatch through the whole hot path: the jax filter backend
# compiles batch-axis-sharded executables, the batch elements size their
# buckets in per-shard multiples, and tensor_upload pre-shards the wire.
# Spec grammar: ``auto`` (all devices, axis "dp"), ``<axis>:<n>``,
# ``<axis>`` (all devices on that axis), or a bare ``<n>``; empty / ``off``
# / ``0`` / ``1`` disable.  A request for more devices than the platform
# has clamps down (auto-detection from ``jax.devices()``) — CPU hosts get
# a real multi-device mesh only under
# ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

_dispatch_mesh_cache: Optional[Tuple[str, Optional[Mesh], str]] = None


def parse_mesh_spec(spec: str) -> Tuple[str, int]:
    """``(axis, ndev)`` out of a mesh spec string; ndev 0 = all devices,
    ndev 1 = disabled."""
    s = (spec or "").strip().lower()
    if s in ("", "off", "none", "false", "0", "1"):
        return ("dp", 1)
    if s == "auto":
        return ("dp", 0)
    axis, sep, n = s.partition(":")
    if not sep:
        if axis.isdigit():
            return ("dp", int(axis))
        return (axis, 0)
    if not n.isdigit():
        raise ValueError(f"mesh spec {spec!r}: device count must be an "
                         f"integer, got {n!r}")
    return (axis or "dp", int(n))


def configured_mesh_spec() -> str:
    """The active mesh spec string: ``NNSTPU_MESH`` (short spelling) over
    ini ``[mesh] spec`` (env form ``NNSTPU_MESH_SPEC``) over disabled."""
    import os

    val = os.environ.get("NNSTPU_MESH")
    if val is not None:
        return val
    from ..conf import conf

    return conf.get("mesh", "spec", "") or ""


def dispatch_mesh() -> Optional[Mesh]:
    """The process-wide data-parallel dispatch mesh, or None when mesh
    mode is off (the default) or fewer than 2 devices are usable.  Built
    once per spec string and cached — the hot path asks per compile, not
    per frame.  :func:`reset_dispatch_mesh` drops the cache (tests,
    mid-process reconfiguration)."""
    global _dispatch_mesh_cache
    spec = configured_mesh_spec()
    cached = _dispatch_mesh_cache
    if cached is not None and cached[0] == spec:
        return cached[1]
    axis, ndev = parse_mesh_spec(spec)
    mesh = None
    if ndev != 1:
        devices = jax.devices()
        if ndev == 0 or ndev > len(devices):
            ndev = len(devices)  # auto-detect / clamp to what exists
        if ndev > 1:
            mesh = make_mesh((ndev,), (axis,), devices=devices[:ndev])
    _dispatch_mesh_cache = (spec, mesh, axis)
    return mesh


def dispatch_mesh_axis() -> str:
    """Batch axis name of the active dispatch mesh ("dp" when off)."""
    mesh = dispatch_mesh()
    if mesh is None:
        return "dp"
    return _dispatch_mesh_cache[2]


def dispatch_mesh_devices() -> int:
    """Device count of the active dispatch mesh (1 when mesh mode is off
    — every batch-sizing call site can multiply by this unconditionally)."""
    mesh = dispatch_mesh()
    return int(mesh.devices.size) if mesh is not None else 1


def mesh_cache_key(mesh: Optional[Mesh]) -> Optional[tuple]:
    """Hashable identity of a mesh for executable-cache keying: axis
    layout + the concrete device list (platform, ordinal) — two meshes
    over different chips must never share an executable."""
    if mesh is None:
        return None
    return (
        tuple(mesh.axis_names),
        tuple(mesh.shape[a] for a in mesh.axis_names),
        tuple((getattr(d, "platform", "device"), getattr(d, "id", i))
              for i, d in enumerate(mesh.devices.flat)),
    )


def reset_dispatch_mesh() -> None:
    """Forget the cached dispatch mesh so the next use re-reads conf."""
    global _dispatch_mesh_cache
    _dispatch_mesh_cache = None
