"""Readings a cell's limits are set from: the program's compared numbers
over many seeds, and the lower-precision control's over a few, in one
process on the chip at the cell's own size and load.

    python3 bench/tools/readings.py --workload <cell> --seeds 12 \
        --control-seeds 3 --seconds 3 [--first-seed <n>]

For each program seed the cell's window runs for ``--seconds`` and the
plain reference checks it exactly as a benchmark run does.  For each
control seed the control (the reference in float32 with three-pass
bfloat16 contractions, ``bench/reference/numa.py``) takes the program's
place over the same sample and is compared with the float64 reference the
same way.  Rows are printed as JSON lines and written to
``readings/readings-<workload>.json`` under the checkout.
"""

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    import argparse

    from bench import core
    from bench.reference import numa as ref

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--first-seed", type=int, default=2**33 + 101)
    args = ap.parse_args(argv)

    cell = core.load_cell(args.workload)
    core.accelerators(cell.chips)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    mod = core.driver_module(cell.traffic["kind"])
    driver = mod.Driver(cell, args.first_seed, core.Spans(False))
    driver.setup()
    rows = []
    try:
        for k in range(args.seeds):
            seed = args.first_seed + 7919 * k
            driver.seed = seed
            t0 = time.perf_counter()
            driver.kept, driver.calls = [], 0
            driver.window(args.seconds)
            kept = [(c, mod.to_host(o)) for c, o in driver.kept]
            driver.kept = []
            program = mod.compare(cell, seed, kept, driver.placements_host())
            control = (
                mod.compare(cell, seed, kept, driver.placements_host(), ar=ref.CONTROL)
                if k < args.control_seeds else None
            )
            row = {"seed": seed, "program": program, "control": control,
                   "seconds": time.perf_counter() - t0}
            rows.append(row)
            core.log(json.dumps(row))
    finally:
        driver.close()
    out = ROOT / "readings"
    out.mkdir(exist_ok=True)
    (out / f"readings-{args.workload}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
