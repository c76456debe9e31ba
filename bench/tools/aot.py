"""Compile each cell's jitted shapes for a described TPU v5e chip, without
the chip, and print what the compiler says they hold in device memory.

    JAX_PLATFORMS=cpu python3 bench/tools/aot.py [--workload <cell>]

For a sweep cell that is ``evaluate_batch``'s one program (the whole mix
over the cell's placement table).  Nothing runs: the numbers are the
compiler's memory analysis, not measurements.
"""

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {
        "argument_bytes": int(m.argument_size_in_bytes),
        "output_bytes": int(m.output_size_in_bytes),
        "temp_bytes": int(m.temp_size_in_bytes),
        "generated_code_bytes": int(m.generated_code_size_in_bytes),
    }


def programs(cell, one_chip):
    """``(label, lowered)`` for every jitted shape the cell's window drives."""
    import jax
    import numpy as np

    from bench import sut
    from repro.core.numa.evaluate import _evaluate_batch_jit, sweep_placements
    from repro.core.numa.simulator import support_patterns, thread_class_starts

    def shape(x, dtype=None):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=one_chip)

    cfg = cell.config
    machine = sut.machine_spec(cfg)
    n = int(cfg["n_threads"])
    wls = sut.workloads(cell.traffic, n)
    pl = np.asarray(sweep_placements(
        machine, n, max_placements=cfg["placements"]["max_placements"],
        seed=int(cfg["placements"]["sample_seed"]),
    ))
    support, slab_id = support_patterns(pl)
    stacked = tuple(np.stack([np.asarray(f) for f in fields]) for fields in zip(*(w[1:] for w in wls)))
    lowered = _evaluate_batch_jit.lower(
        machine, tuple(shape(a) for a in stacked), shape(pl), shape(support),
        shape(slab_id), shape(np.zeros((len(wls), 2), np.uint32)),
        float(cell.traffic["noise_std"]), float(cell.traffic["background_bw"]),
        thread_class_starts(wls), False, None,
    )
    yield f"evaluate_batch {len(wls)} x {pl.shape[0]} placements, {support.shape[0]} buckets", lowered


def main(argv=None) -> int:
    import argparse

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import core

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    spec = core.load_json(ROOT / "BENCHMARK.json")
    names = args.workload or [w["name"] for w in spec["workloads"]]
    for name in names:
        cell = core.load_cell(name)
        for label, lowered in programs(cell, one_chip):
            print(json.dumps({"workload": name, "program": label, **_mem(lowered.compile())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
