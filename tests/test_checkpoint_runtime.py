"""Checkpoint/restart, elastic re-shard, straggler detection, compression."""

import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import store
from repro.runtime.fault_tolerance import (
    FailureInjector,
    StragglerMonitor,
    TrainLoop,
)


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "w": jax.random.normal(k, (64, 32)),
        "nested": {"b": jnp.arange(8, dtype=jnp.int32)},
        "m": jnp.zeros((64, 32), jnp.bfloat16),
    }


def test_save_restore_roundtrip(tmp_path):
    state = _state()
    store.save(tmp_path, 7, state)
    like = jax.eval_shape(lambda x: x, state)
    back = store.restore(tmp_path, 7, like)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunked_leaves_roundtrip(tmp_path):
    big = {"x": jnp.arange(4 * 1024 * 300, dtype=jnp.float32).reshape(4, -1)}
    store.save(tmp_path, 1, big, chunk_mb=1)  # force multi-chunk
    back = store.restore(tmp_path, 1, jax.eval_shape(lambda x: x, big))
    np.testing.assert_array_equal(np.asarray(big["x"]), np.asarray(back["x"]))


def test_latest_step_ignores_tmp_and_missing_manifest(tmp_path):
    store.save(tmp_path, 3, _state())
    store.save(tmp_path, 9, _state())
    (tmp_path / "step_00000011.tmp").mkdir()  # crashed writer
    assert store.latest_step(tmp_path) == 9


def test_async_checkpointer(tmp_path):
    ck = store.AsyncCheckpointer(tmp_path)
    ck.save(5, _state())
    ck.wait()
    assert store.latest_step(tmp_path) == 5


def test_train_loop_restart_bit_identical(tmp_path):
    """Kill training at step 7, resume, verify the final state matches an
    uninterrupted run exactly (deterministic replay)."""

    def step_fn(state, step):
        new = jax.tree.map(
            lambda x: x + 1 if jnp.issubdtype(x.dtype, jnp.floating) else x, state
        )
        return new, {"loss": float(step)}

    def run(with_failure):
        loop = TrainLoop(
            step_fn=step_fn,
            ckpt_dir=tmp_path / ("f" if with_failure else "g"),
            save_every=5,
            injector=FailureInjector({7}) if with_failure else None,
        )
        state = _state()
        if with_failure:
            with pytest.raises(FailureInjector.NodeFailure):
                loop.run(state, 12)
            # restart: resumes from step 5's checkpoint automatically
            final, step, _ = loop.run(state, 12)
        else:
            final, step, _ = loop.run(state, 12)
        return final, step

    a, _ = run(with_failure=False)
    b, _ = run(with_failure=True)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(threshold=2.0)
    for s in range(10):
        assert not mon.observe(s, 1.0)
    assert mon.observe(10, 5.0)  # 5x the EWMA
    assert mon.flagged and mon.flagged[0][0] == 10
    assert not mon.observe(11, 1.0)  # EWMA not poisoned by the outlier


_MULTIDEV_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel import context as ctx
from repro.parallel.compression import compressed_psum_mean
from repro.runtime.fault_tolerance import remesh
from repro.checkpoint import store
from repro.launch import mesh as mesh_lib

# --- compressed mean numerics across a 4-way axis ---
mesh = jax.make_mesh((4, 2), ("data", "model"))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 32))

def body(xb):
    return compressed_psum_mean(xb[0], ("data",))[None]

out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data", None, None),
                            out_specs=P("data", None, None), check_vma=False))(x)
expect = jnp.broadcast_to(x.mean(axis=0, keepdims=True), x.shape)
got = np.asarray(out)
err = np.abs(got - np.asarray(expect)).max() / np.abs(np.asarray(expect)).max()
assert err < 0.02, f"compressed mean error too large: {err}"

# --- elastic remesh 8 -> 4 devices via topology-independent specs ---
state = {"w": jax.random.normal(jax.random.PRNGKey(1), (16, 8))}
specs = {"w": ("fsdp", "tp")}
with ctx.use_mesh(mesh):
    sh = mesh_lib.tree_shardings(mesh, specs)
    placed = jax.tree.map(lambda a, s: jax.device_put(a, s), state, sh)
small = jax.sharding.Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
moved = remesh(placed, specs, small)
np.testing.assert_array_equal(np.asarray(moved["w"]), np.asarray(state["w"]))
assert moved["w"].sharding.mesh.shape["data"] == 2
print("MULTIDEV OK")
"""


@pytest.mark.slow
def test_compression_and_remesh_multidevice():
    """Collectives need >1 device; run in a subprocess with 8 host devices
    so the main test session keeps its single-device invariant."""
    r = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SNIPPET],
        capture_output=True,
        text=True,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd="/root/repo",
        timeout=300,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MULTIDEV OK" in r.stdout
