"""The harness's shared machinery: finding a cell's pieces by name, the
device check, host spans, the compile counter and the result line.

Everything a cell needs is found from ``BENCHMARK.json`` by name:

* ``bench/configs/<config>.json``     the deployment (host, thread budget);
* ``bench/traffic/<traffic>.json``    the mix; its ``kind`` names the driver
  ``bench/drivers/<kind>.py``;
* ``bench/limits/<workload>.json``    the cell's comparison limits;
* ``bench/metrics/<metric>.py``       one reader per per-layer metric.

A later cell, mix or metric is new files plus new entries; no file here
changes.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPAN_PREFIX = "bench:"


class SetupError(RuntimeError):
    """The run cannot start: a missing piece or no accelerator."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    entry: dict  # the workload entry of BENCHMARK.json
    config: dict
    traffic: dict
    limits: dict
    spec: dict  # the whole BENCHMARK.json
    root: Path

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    def end_to_end(self) -> list[dict]:
        return [m for m in self.spec["end_to_end"] if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list[dict]:
        out = []
        for m in self.spec["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in {e["name"] for e in self.end_to_end()}:
                out.append(m)
        return out


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec_path = root / "BENCHMARK.json"
    if not spec_path.exists():
        raise SetupError(f"no {spec_path}")
    spec = load_json(spec_path)
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SetupError(f"BENCHMARK.json has no workload {name!r}")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name,
        entry=entry,
        config=load_json(root / cfg_entry["file"]),
        traffic=load_json(root / "bench" / "traffic" / f"{entry['traffic']}.json"),
        limits=load_json(root / "bench" / "limits" / f"{name}.json"),
        spec=spec,
        root=root,
    )


def driver_module(kind: str, root: Path = ROOT):
    return _load_file(root / "bench" / "drivers" / f"{kind}.py", f"bench_driver_{kind}")


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    mod = _load_file(root / "bench" / "metrics" / f"{name}.py", "bench_metric_" + name.replace(".", "_"))
    return mod.read


def _load_file(path: Path, modname: str):
    if not path.exists():
        raise SetupError(f"no {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------


def accelerators(chips: int):
    """The first ``chips`` accelerator devices; raises without them (no
    fallback to the CPU)."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SetupError("JAX found no accelerator")
    if len(devices) < chips:
        raise SetupError(f"cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def device_record(devices) -> dict:
    peaks = []
    for d in devices:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (KeyError, TypeError, AttributeError):
            pass
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks) if peaks else None,
    }


# ---------------------------------------------------------------------------
# Host spans and compile counting
# ---------------------------------------------------------------------------


class Spans:
    """The benchmark's host spans around its calls into each layer.  In a
    traced run each becomes a ``TraceAnnotation`` in the profiler's trace,
    on the device's clock, where the per-layer readers find it; otherwise
    a span costs nothing."""

    def __init__(self, traced: bool):
        self.traced = traced

    def __call__(self, name: str):
        if self.traced:
            import jax

            return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        return contextlib.nullcontext()


class CompileCounter:
    """Counts JAX compilation events (tracing, lowering, backend compile)
    while armed."""

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.events: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if self.armed and event.startswith("/jax/core/compile/"):
            self.events[event] = self.events.get(event, 0) + 1


# ---------------------------------------------------------------------------
# What a run hands the per-layer readers, and the result line
# ---------------------------------------------------------------------------


@dataclass
class Run:
    """One run's records, as the per-layer metric readers see them."""

    cell: Cell
    trace: object = None  # bench.tracing.TraceSummary in a traced run
    counters: dict = field(default_factory=dict)  # program counters
    loadgen: dict = field(default_factory=dict)  # load generator records


@dataclass
class Check:
    """One number compared against its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def emit(result: dict, checks: list[Check]) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, and the result as the last line of standard output,
    with the checks under the last key."""
    result = dict(result)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    sys.stdout.flush()
    for c in checks:
        verdict = "ok" if c.ok else "FAIL"
        print(f"check {c.name} = {c.value!r} limit {c.limit!r} {verdict}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def log(msg: str) -> None:
    print(msg, flush=True)
