"""BENCHMARK.json against the benchmark's contract, discovery of every
piece by name, and a new configuration, mix and per-layer metric added as
files plus entries, with no existing file edited."""

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

from bench import core

ROOT = core.ROOT
SPEC = core.load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51


def test_entries_have_exactly_the_contract_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES


def test_names_units_and_lengths():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for x in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"] and "\t" not in x["why"]
    for c in SPEC["configs"]:
        assert 1 <= len(c["source"]) <= 200


def test_every_cell_reports_setup_another_metric_and_a_layer():
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25 for m in SPEC["end_to_end"])
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for w in SPEC["workloads"]:
        cell = core.load_cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in reported and len(reported) >= 2
        layers = cell.per_layer()
        assert layers
        for m in layers:
            assert m["moves"] in reported and m["moves"] in e2e


def test_every_piece_is_found_by_name():
    for c in SPEC["configs"]:
        path = ROOT / c["file"]
        assert path.is_file() and path.parts[len(ROOT.parts)] == "bench"
        assert core.load_json(path)["name"] == c["name"]
    for w in SPEC["workloads"]:
        cell = core.load_cell(w["name"])
        assert hasattr(core.driver_module(cell.traffic["kind"]), "Driver")
        assert set(cell.limits) == {"sample", "limits"}
    for m in SPEC["per_layer"]:
        assert callable(core.metric_reader(m["name"]))


def test_layer_names_agree_letter_for_letter():
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert layers["device_idle_share"] == {"device"}


def _copy_benchmark(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_a_new_config_mix_and_metric_need_only_new_files(tmp_path):
    """Add a two-socket configuration, a two-workload mix, a per-layer
    metric and a cell to a copy: the harness finds each by name, and no
    file that was there changes."""
    root = _copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    bench = root / "bench"

    cfg = core.load_json(bench / "configs" / "e7-4830v3-4s.json")
    cfg["name"] = "two-socket"
    cfg["machine"].update(sockets=2, links=[[0, 1, 16.0e9]])
    cfg["n_threads"] = 8
    cfg["placements"] = {"total": 9, "max_placements": None, "sample_seed": 0}
    (bench / "configs" / "two-socket.json").write_text(json.dumps(cfg))
    mix = core.load_json(bench / "traffic" / "table1.json")
    mix["workloads"] = mix["workloads"][:2]
    (bench / "traffic" / "pair.json").write_text(json.dumps(mix))
    (bench / "limits" / "sweep.two-socket.pair.json").write_text(
        json.dumps({"sample": {"calls": 1, "placements": 4}, "limits": {"bw_rel": 1e-3, "err_abs": 1e-3}})
    )
    (bench / "metrics" / "calls.sweep.py").write_text(
        "def read(run):\n    return float(run.counters['calls'])\n"
    )
    spec = core.load_json(root / "BENCHMARK.json")
    spec["configs"].append({"name": "two-socket", "source": "test", "file": "bench/configs/two-socket.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "sweep.two-socket.pair", "config": "two-socket", "traffic": "pair",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "sweep_placement_evals_per_s":
            m["workloads"].append("sweep.two-socket.pair")
    spec["per_layer"].append({"name": "calls.sweep", "unit": "calls", "better": "higher",
                              "source": "program_counter", "layer": "test", "moves": "sweep_placement_evals_per_s",
                              "workloads": ["sweep.two-socket.pair"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = core.load_cell("sweep.two-socket.pair", root)
    assert cell.config["n_threads"] == 8 and len(cell.traffic["workloads"]) == 2
    assert [m["name"] for m in cell.per_layer()] == ["calls.sweep"]
    assert core.metric_reader("calls.sweep", root)(core.Run(cell=cell, counters={"calls": 3})) == 3.0
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_missing_pieces_are_setup_errors(tmp_path):
    root = _copy_benchmark(tmp_path)
    with pytest.raises(core.SetupError):
        core.load_cell("no.such.cell", root)
    with pytest.raises(core.SetupError):
        core.driver_module("no_such_kind", root)
    with pytest.raises(core.SetupError):
        core.load_cell("x", tmp_path / "empty")


def test_without_an_accelerator_the_run_exits_nonzero_and_prints_no_result(capsys):
    from bench import run

    name = SPEC["workloads"][0]["name"]
    rc = run.main(["--workload", name, "--seed", "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "{" not in out.out
    assert "accelerator" in out.err


def test_a_directory_with_only_the_benchmark_exits_nonzero(tmp_path):
    """Only BENCHMARK.json and bench/: the system under test is missing."""
    import subprocess

    root = _copy_benchmark(tmp_path)
    name = SPEC["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)},
    )
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")
