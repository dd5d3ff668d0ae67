#!/usr/bin/env python3
"""Compile a cell's timed program for a described TPU v5e, without a chip.

Usage (from the root of a checkout, on a host with no TPU)::

    JAX_PLATFORMS=cpu python3 bench/rehearse.py jacobi2d-16k.t1 ...

For each cell it lowers the dispatch program the window runs, at the
cell's own size, for a ``v5e:2x2`` topology (one of its chips, or a 2x2
mesh of them for a sharded cell), compiles it with the chip's compiler,
and prints whether the engine kernel is there (``tpu_custom_call``),
the ops of the compiled program and its ``memory_analysis`` per device.
Nothing runs, so it says nothing about times or results.
"""
import os
import re
import sys
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def rehearse(workload: str) -> None:
    import jax
    from jax.experimental import topologies
    from jax.sharding import (AxisType, Mesh, NamedSharding,
                              PartitionSpec as P, SingleDeviceSharding)
    import numpy as np

    from bench import harness

    cell = harness.resolve(workload)
    cfg = cell.config
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    driver = harness.driver_module(cell).make(
        cfg, cell.traffic, seed=0, impl="pallas", devices=topo.devices)
    if cfg.get("mesh"):
        mesh = Mesh(np.array(topo.devices[:4]).reshape(cfg["mesh"]),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        driver.mesh = mesh
        sharding = NamedSharding(mesh, P("data", "model"))
    else:
        sharding = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct(tuple(cfg["domain"]), cfg["dtype"],
                             sharding=sharding)
    compiled = jax.jit(driver._program).lower(x).compile()
    text = compiled.as_text()
    ops = Counter(m.group(1) for m in re.finditer(
        r"=\s*\S+\s+([a-z][a-z0-9-]*)\(", text))
    mem = compiled.memory_analysis()
    gib = 2.0 ** 30
    print(f"{workload}: tpu_custom_call={'tpu_custom_call' in text} "
          f"ops={dict(ops.most_common())}")
    print(f"  per device: arguments {mem.argument_size_in_bytes / gib:.3f} "
          f"GiB, output {mem.output_size_in_bytes / gib:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / gib:.3f} GiB, "
          f"aliased {mem.alias_size_in_bytes / gib:.3f} GiB", flush=True)


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    for w in argv:
        rehearse(w)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
