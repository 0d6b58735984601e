#!/usr/bin/env python
"""Compile a cell's real-size programs for a DESCRIBED TPU v5e — no chip —
and print ``memory_analysis()``: how the batch and the KV pool were sized
before any chip time was spent (the ``tools/check_mosaic_aot.py`` pattern).

    JAX_PLATFORMS=cpu python bench/aot_check.py --workload resnet50.fit_1chip
    JAX_PLATFORMS=cpu python bench/aot_check.py --workload cerebras_gpt_1p3b.decode_chat

Nothing runs: a compile that passes is not a chip run and none of its
numbers is a measurement. The program under test is steered from HERE, not
through an option of its own: ``jax.default_backend`` is made to answer
"tpu" while tracing, so every ``on_tpu()`` branch takes its Mosaic kernel,
and the step's real arguments are swapped for shapes placed on the
described device at the one point where the program hands its jitted step
and its arguments to ``health.capture_cost``.

What is compiled sits with each driver (``aot_check(cell, hbm, aot)``).
``fit_cli`` cells: the one-chip fused step at the traffic file's batch per
chip (a dp4 cell holds the same program per chip plus the all-reduce's
buffers; PR 21 proved that it lowers and runs). ``decode_open_loop``
cells: every prefill bucket and slot bucket at the configuration's pool.
"""
import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "examples"))

import jax                                                  # noqa: E402
from jax.experimental import topologies                     # noqa: E402

from bench import harness                                   # noqa: E402

GB = 1e9


class Compiled(Exception):
    """Carries the compiled step out of the program's own call chain."""


def describe():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    print("compiling for a described %s (no device attached)"
          % topo.devices[0].device_kind, flush=True)
    return topo


def report(name, compiled, hbm):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print("%-28s args %.3f GB, outputs %.3f GB, aliased %.3f GB, temps "
          "%.3f GB -> %.3f GB live of %.1f GB%s"
          % (name, ma.argument_size_in_bytes / GB,
             ma.output_size_in_bytes / GB, ma.alias_size_in_bytes / GB,
             ma.temp_size_in_bytes / GB, total / GB, hbm / GB,
             "" if total <= hbm else "  ** DOES NOT FIT **"), flush=True)
    return total


def as_shapes(tree, sharding):
    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        return x                    # python scalars stay weak-typed
    return jax.tree_util.tree_map(leaf, tree)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    cell = harness.Cell(ROOT, args.workload)
    peaks = harness.load_json(os.path.join(cell.bench_dir, "peaks.json"))
    hbm = peaks["devices"]["TPU v5 lite"]["hbm_bytes"]
    # how a driver's programs are built at real size sits with the driver
    driver = harness.load_module(cell.driver_file)
    total = driver.aot_check(cell, hbm, sys.modules[__name__])
    return 0 if total <= hbm else 1


if __name__ == "__main__":
    sys.exit(main())
