"""Where a primitive's call is lowered to a Pallas kernel or through XLA.

A trace does not know what it is lowered for: the same jaxpr goes to the
TPU, to the CPU under ``jax.default_device``, or into a program GSPMD
partitions over a mesh, where Mosaic refuses a kernel.  So the choice is
made when the program is lowered (:func:`register`), and each call site is
lowered, and counted (:func:`count`) under the path it took, on its own.
"""

from __future__ import annotations

from typing import Callable

from jax.interpreters import mlir


def on_one_device(axis_context) -> bool:
    """Mosaic's own condition for lowering a kernel (jax's
    ``tpu_custom_call``): the program is for one device, or it is the body
    of a ``shard_map`` over every axis of its mesh."""
    if hasattr(axis_context, "num_devices"):  # a jit's ShardingContext
        return axis_context.num_devices == 1
    mesh = getattr(axis_context, "mesh", None)  # shard_map's SPMDAxisContext
    return mesh is not None and (
        set(axis_context.manual_axes) | set(mesh.manual_axes)
        == set(mesh.axis_names))


def register(primitive, plain: Callable, kernel: Callable) -> None:
    """Lower ``primitive`` by ``plain`` everywhere and, on a TPU where the
    program is for one device, by ``kernel`` where it returns a function.
    Both take the call's input avals and the primitive's params, count the
    path they choose and return the function of the operands to lower."""

    def rule(tpu: bool):
        def lower(ctx, *operands, **params):
            fn = None
            if tpu and on_one_device(ctx.module_context.axis_context):
                fn = kernel(ctx.avals_in, **params)
            fn = fn or plain(ctx.avals_in, **params)
            return mlir.lower_fun(fn, multiple_results=False)(ctx, *operands)

        return lower

    mlir.register_lowering(primitive, rule(False), cacheable=False)
    mlir.register_lowering(primitive, rule(True), platform="tpu",
                           cacheable=False)


def count(name: str, text: str, **label) -> None:
    """One more on counter ``name`` (help ``text``) under ``label``."""
    from ..obs.metrics import REGISTRY

    REGISTRY.counter(name, text, labelnames=tuple(label)).inc(**label)


def gauge(name: str, text: str, value, **label) -> None:
    """Gauge ``name`` (help ``text``) under ``label`` set to ``value``."""
    from ..obs.metrics import REGISTRY

    REGISTRY.gauge(name, text, labelnames=tuple(label)).set(value, **label)
