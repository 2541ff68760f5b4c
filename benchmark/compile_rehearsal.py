#!/usr/bin/env python3
"""Compile a cell's executable for a described v5e chip, without the chip,
and print its ``memory_analysis()``: what the chip's compiler would refuse it
refuses here, and the bytes say whether the cell can meet the memory floor.
Takes minutes (the weights are compile-time constants); a script, not a test.

    JAX_PLATFORMS=cpu python3 benchmark/compile_rehearsal.py --workload <cell>

A compile that passes is not a chip run and is never reported as one.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import manifest  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--streams", type=int, help="override the mix's streams")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    man = manifest.load_manifest()
    cell = manifest.find(man["workloads"], args.workload, "cell")
    cfg = manifest.load_config(man, cell["config"])
    mix = manifest.load_traffic(cell["traffic"])
    kind = manifest.module("model_kinds", cfg["kind"])
    traffic = manifest.module("traffic_kinds", mix["kind"])
    sizes = kind.sizes(cfg)
    batch = args.streams or int(mix["streams"])
    weights = kind.init_weights(sizes, int(cfg["weights_seed"]))
    fn = kind.build_program(sizes, weights, batch).fn()
    # what this traffic feeds the executable, and what the pipeline fuses in
    # front of the model (a camera's normalise)
    shape, dtype, front = traffic.example_input(mix, cfg, kind, sizes, batch)

    def program(x):
        return fn(front(x))

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    x = jax.ShapeDtypeStruct(shape, dtype,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    t = time.perf_counter()
    lowered = jax.jit(program).lower(x)
    t_lower = time.perf_counter() - t
    t = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t
    mem = compiled.memory_analysis()
    print(f"{args.workload} batch {batch}: lower {t_lower:.1f} s, compile "
          f"{t_compile:.1f} s (sandbox compile rehearsal, not a chip run)")
    for name in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        print(f"  {name}: {getattr(mem, name, None)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
