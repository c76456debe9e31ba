"""What the benchmark hands the system under test, built from the
configuration and traffic files: the machine spec, the workload arrays and
the PRNG keys.  The reference reads the same files on its own
(``bench/reference``); nothing built here reaches it.
"""

from __future__ import annotations

import numpy as np

from bench.reference.numa import FIELDS, workload_arrays


def machine_spec(cfg: dict):
    """The configuration's host as a ``repro.core.numa.MachineSpec``, with
    the route of every ordered node pair taken from the file."""
    from repro.core.numa.machine import MachineSpec
    from repro.core.numa.topology import Topology

    m = cfg["machine"]
    s = int(m["sockets"]) * int(m.get("nodes_per_socket", 1))
    ends = tuple((int(a), int(b)) for a, b, _ in m["links"])
    index = {frozenset(e): l for l, e in enumerate(ends)}
    paths = {(p[0], p[-1]): p for p in m.get("multi_hop_paths", [])}
    routes = []
    for i in range(s):
        for j in range(s):
            path = [] if i == j else paths.get((i, j), [i, j])
            routes.append(
                tuple(index[frozenset(e)] for e in zip(path[:-1], path[1:]))
            )
    topo = Topology(
        name=f"{cfg['name']}-links",
        n_nodes=s,
        link_ends=ends,
        link_bw=tuple(float(bw) for _, _, bw in m["links"]),
        routes=tuple(routes),
    )
    spec = MachineSpec(
        name=cfg["name"],
        sockets=int(m["sockets"]),
        cores_per_socket=int(m["cores_per_socket"]),
        local_read_bw=float(m["local_read_bw"]),
        local_write_bw=float(m["local_write_bw"]),
        remote_read_bw=float(m["remote_read_bw"]),
        remote_write_bw=float(m["remote_write_bw"]),
        core_rate=(float(m["core_rate"]),) * s,
        topology=topo,
        hop_attenuation=float(m.get("hop_attenuation", 1.0)),
        nodes_per_socket=int(m.get("nodes_per_socket", 1)),
    )
    spec.validate()
    return spec


def workloads(traffic: dict, n_threads: int) -> list:
    """The mix's workloads as ``repro.core.numa.Workload`` objects."""
    import jax.numpy as jnp

    from repro.core.numa.workload import Workload

    out = []
    for w in traffic["workloads"]:
        arr = workload_arrays(w, n_threads)
        out.append(
            Workload(
                w["name"],
                *(jnp.asarray(arr[f], jnp.float32) for f in FIELDS),
                jnp.asarray(arr["static_socket"], jnp.int32),
            )
        )
    return out


def seed_words(seed: int) -> tuple[int, int]:
    """A seed of up to 64 bits as two 32-bit words (``PRNGKey`` would drop
    the high word without 64-bit mode)."""
    seed = int(seed) & (2**64 - 1)
    return seed >> 32, seed & 0xFFFFFFFF


def call_keys(seed: int, call: int, n: int, warmup: bool = False) -> np.ndarray:
    """``(n, 2)`` uint32 threefry keys of one sweep call: fresh for every
    call, the same for the same seed and call index; warm-up calls draw
    from a stream of their own."""
    rng = np.random.default_rng([*seed_words(seed), int(warmup), int(call)])
    return rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)
