"""Compile the main path and the two Pallas kernels for a TPU v5e chip that
is described, not attached, and check where the compile cache goes.

The TPU compiler is installed with jax, so these compiles run on the CPU
host and refuse what the chip's compiler would refuse (unaligned tiles,
too much VMEM, a program that does not fit HBM).  Nothing runs, so they
say nothing about results or speed: ``chip_smoke.py`` covers that on the
chip.  The topology is described only inside the module fixture: a
second process loading libtpu at import time would fail, and pytest
workers would then collect different tests.
"""

import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.runtime import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an AOT compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip: keep it out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _spec(a, sharding):
    return jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype, sharding=sharding)


def test_evaluate_batch_compiles_for_v5e_at_sweep_size(one_chip):
    """``chip_smoke.py``'s first sweep: the 4-socket preset, all 1469
    placements of 24 threads, the 23-workload suite, in one trace."""
    from repro.core.numa import E7_4830_V3
    from repro.core.numa.benchmarks import benchmark_workload, suite_names
    from repro.core.numa.evaluate import (
        _evaluate_batch_jit,
        _workload_arrays,
        enumerate_placements,
    )
    from repro.core.numa.simulator import support_patterns, thread_class_starts

    workloads = [benchmark_workload(b, 24) for b in suite_names()]
    stacked = tuple(
        np.stack([np.asarray(a) for a in parts])
        for parts in zip(*(_workload_arrays(w) for w in workloads))
    )
    placements = np.asarray(enumerate_placements(E7_4830_V3, 24))
    support, slab_id = support_patterns(placements)
    keys = np.zeros((len(workloads), 2), np.uint32)
    assert placements.shape == (1469, 4) and len(workloads) == 23

    compiled = _evaluate_batch_jit.lower(
        E7_4830_V3,
        tuple(_spec(a, one_chip) for a in stacked),
        _spec(placements, one_chip),
        _spec(support, one_chip),
        _spec(slab_id, one_chip),
        _spec(keys, one_chip),
        0.02,
        0.0,
        thread_class_starts(workloads),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30  # far inside one chip's HBM


@pytest.mark.parametrize(
    "cell, temp_limit",
    [("sweep.e7-4830v3-4s.table1", 40_000_000), ("sweep.epyc7601-2s.table1", 2_500_000_000)],
)
def test_sweep_cells_compile_for_v5e_without_tile_padding(
    one_chip, monkeypatch, cell, temp_limit
):
    """Each sweep cell's program, as the benchmark builds it
    (``bench/tools/aot.py``), within its temp.  The TPU stores an array's
    two minor axes in (8, 128) tiles, so the fill's per-placement blocks
    laid out as the minor axes are padded up to 16-fold: 9.1 GB at the
    EPYC cell's 8192 placements, where the placements on the lanes take
    0.6 GB.  The layout is the compiler's choice, which the lowered
    shapes do not show; its temp does."""
    monkeypatch.syspath_prepend(str(REPO))
    from bench import core
    from bench.tools.aot import programs

    ((_, lowered),) = programs(core.load_cell(cell), one_chip)
    assert lowered.compile().memory_analysis().temp_size_in_bytes <= temp_limit


def test_flash_attention_compiles_for_v5e_at_llama3_8b_widths(one_chip):
    from repro.kernels.flash_attention.ops import mha_flash

    q = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 128), jnp.bfloat16, sharding=one_chip)
    compiled = mha_flash.lower(q, kv, kv, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mamba_scan_compiles_for_v5e_at_falcon_mamba_7b_widths(one_chip):
    from repro.kernels.mamba_scan.ops import ssm_scan

    B, S, di, n = 1, 4096, 8192, 16

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = ssm_scan.lower(
        f32(B, S, di), f32(di, n), f32(B, S, n), f32(B, S, n), f32(B, S, di),
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_compilation_cache_include_metadata_in_key",
        "jax_hlo_source_file_canonicalization_regex",
    )
    before = {name: getattr(jax.config, name) for name in names}
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        # cached programs keep their own scopes; paths lose the checkout
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        pattern = jax.config.jax_hlo_source_file_canonicalization_regex
        assert re.sub(pattern, "", str(REPO / "src" / "x.py")) == os.path.join("src", "x.py")
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
    assert (REPO / ".gitignore").read_text().splitlines().count(".jax_cache/") == 1


def test_compile_cache_honors_environment(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, the helper sets no path and
    a compile lands there, not in the checkout (a fresh process: JAX
    reads the variable when it is imported)."""
    cache = tmp_path / "cache"
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.runtime.compile_cache import use_compile_cache\n"
        "print(use_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(3.0)).block_until_ready()\n"
    )
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(cache),
        "PYTHONPATH": str(REPO / "src"),
    }
    default = REPO / ".jax_cache"
    before = set(default.rglob("*")) if default.exists() else set()
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=120, check=True,
    ).stdout.split()
    assert out[:2] == [str(cache), str(cache)]
    assert any(cache.iterdir())
    after = set(default.rglob("*")) if default.exists() else set()
    assert after == before
