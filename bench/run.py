"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, the host and tables, compiling or loading every jitted
shape the cell's traffic uses, warm-up) is timed from the first line of
this file to the window as ``setup_s``.  The window then runs the cell's
driver for ``--seconds``; with ``--trace 1`` the profiler records it and
the per-layer metrics are read from that trace.  After the window the
device's peak memory is read, the program's state is released, and the
plain reference checks a seeded sample of what the window produced.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``checks`` last); each compared number is also printed beside
its limit as the last lines of standard error.  Without an accelerator,
or with fewer chips than the cell asks for, the run exits 2 and prints no
result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the harness imports as the ``bench`` package and the system under test
# from ``src``; the script's own directory would shadow both
sys.path[:] = [str(ROOT / "src"), str(ROOT)] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"
]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, require_accelerator: bool = True, t_start: float = T_START,
         root: Path = ROOT) -> int:
    """Run the cell; ``require_accelerator=False`` and ``root`` let tests
    drive the rest of a run on the CPU from a copy of the benchmark."""
    from bench import core

    args = parse(argv)
    try:
        cell = core.load_cell(args.workload, root)
        driver_cls = core.driver_module(cell.traffic["kind"], root).Driver
        import jax

        devices = core.accelerators(cell.chips) if require_accelerator else jax.devices()[: cell.chips]
        from repro.runtime.compile_cache import use_compile_cache
    except (core.SetupError, OSError, KeyError, ImportError) as exc:
        print(f"bench: cannot run {args.workload}: {exc}", file=sys.stderr)
        return 2

    use_compile_cache()
    compiles = core.CompileCounter()
    spans = core.Spans(traced=bool(args.trace))
    driver = driver_cls(cell, args.seed, spans, args.seconds)
    try:
        driver.setup()
        setup_s = time.perf_counter() - t_start
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
        if trace_dir:
            from bench import tracing

            tracing.start(trace_dir)
        compiles.armed = True
        with spans("window"):
            driver.window(args.seconds)
        compiles.armed = False
        if trace_dir:
            jax.profiler.stop_trace()
        device = core.device_record(devices)
        e2e = driver.end_to_end()
        driver.release()
    finally:
        driver.close()

    core.log(f"window compile events: {compiles.events or 'none'}")
    core.log(f"program counters: {driver.counters()}")
    run = core.Run(cell=cell, counters=driver.counters(), loadgen=driver.loadgen())
    breakdown = None
    if trace_dir:
        try:
            run.trace = tracing.summarize(*tracing.load_trace(tracing.find_xplane(trace_dir)))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": run.trace.top_ops(), "idle_gaps": run.trace.idle_gaps()}

    try:
        checks = driver.check()
    except Exception:  # outputs the comparison cannot read are not correct
        traceback.print_exc()
        checks = [core.Check("comparison_ran", 1.0, 0.0)]
    metrics = {}
    if args.trace:
        for m in cell.per_layer():
            value = core.metric_reader(m["name"], cell.root)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result = {
        "correct": driver.failed == 0 and all(c.ok for c in checks),
        "attempted": driver.attempted,
        "failed": driver.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    core.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
