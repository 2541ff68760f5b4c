#!/usr/bin/env python3
"""Lower every benchmark cell's program, and its control, for a TPU from
this host and print a digest of each StableHLO text.

The weights are shapes (``jax.eval_shape`` around the program's own
seeded init, every float in bfloat16 as the cells' checkpoints hold them,
then the harness's build over them), so nothing of a cell's size is made,
and nothing is compiled.
Locations are left out of the text and out of the kernels' Mosaic bodies,
so two trees whose programs are the same print the same digests wherever
their source lines lie: run it on both sides of a refactor that should
change no device instruction, and diff.

    JAX_PLATFORMS=cpu python3 tools/lowered_digest.py [--out DIR] [CELL ...]

``--out`` also writes each text to ``DIR/<cell>[.control].mlir``.
"""

from __future__ import annotations

import argparse
import hashlib
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def lowered(name: str, control: bool) -> str:
    """StableHLO of cell ``name``'s program (``control``: the lower-precision
    build in its place) as the harness builds it, lowered for ``tpu``."""
    import jax
    import jax.numpy as jnp

    from benchmark import manifest
    from nnstreamer_tpu.backends.jax_backend import split_params
    from nnstreamer_tpu.models import layers
    from nnstreamer_tpu.ops import quant

    man = manifest.load_manifest()
    cell = manifest.find(man["workloads"], name, "cell")
    cfg = manifest.load_config(man, cell["config"])
    mix = manifest.load_traffic(cell["traffic"])
    kind = manifest.module("model_kinds", cfg["kind"])
    traffic = manifest.module("traffic_kinds", mix["kind"])
    sizes, batch = kind.sizes(cfg), int(mix["streams"])
    built = {}

    def bf16(a):
        floating = hasattr(a, "dtype") and jnp.issubdtype(a.dtype,
                                                          jnp.floating)
        return a.astype(jnp.bfloat16) if floating else a

    def weights():
        seeded = kind.build_program(sizes, None, batch).params
        model = kind.build_program(sizes, jax.tree.map(bf16, seeded), batch,
                                   control)
        arrays, built["merge"] = split_params(model.params)
        built["model"] = model
        return arrays

    # what draws or quantizes on the host, as shapes under eval_shape
    host = layers._normal, quant.quantize_weight
    layers._normal = lambda key, shape, std: jnp.zeros(shape, jnp.float32)
    quant.quantize_weight = lambda w, axis=-1: quant.QuantizedWeight(
        q=jnp.zeros(w.shape, jnp.int8), scale=jnp.zeros(
            [n if i == axis % w.ndim else 1 for i, n in enumerate(w.shape)],
            jnp.float32))
    try:
        arrays = jax.eval_shape(weights)
    finally:
        layers._normal, quant.quantize_weight = host
    model, merge = built["model"], built["merge"]
    shape, dtype, front = traffic.example_input(mix, cfg, kind, sizes, batch)
    traced = jax.jit(lambda w, x: model.apply(merge(w), front(x))).trace(
        arrays, jax.ShapeDtypeStruct(shape, dtype))
    return traced.lower(lowering_platforms=("tpu",)).as_text(
        debug_info=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*", help="default: every workload")
    ap.add_argument("--out", help="a directory for the texts")
    args = ap.parse_args(argv)

    import jax

    from benchmark import manifest

    # no Python frame in any location, the kernels' bodies included
    jax.config.update("jax_traceback_in_locations_limit", 0)
    cells = args.cells or [w["name"] for w in
                           manifest.load_manifest()["workloads"]]
    for name in cells:
        for control in (False, True):
            text = lowered(name, control)
            label = name + (".control" if control else "")
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                with open(os.path.join(args.out, label + ".mlir"), "w",
                          encoding="utf-8") as f:
                    f.write(text)
            print(hashlib.sha256(text.encode()).hexdigest()[:16], label,
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
