"""Production mesh construction.

Kept as functions (never module-level constants) so importing this
module never touches jax device state.
"""

from __future__ import annotations

import os
from typing import Optional

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_local_mesh():
    """1-device mesh with the production axis names — lets the same
    pjit'd code paths run in tests/benchmarks on one CPU device."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def mesh_device_count(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n


def shard_device_map(n_shards: int, devices=None) -> list:
    """Map a serving shard group onto devices, round-robin.

    Shard i's device-resident state (SPLADE padded postings, and on the
    device-resident PLAID path the token pool) is pinned to the returned
    ``devices[i % n]``, so a shard group's stage-1 ``jax``/``pallas``
    dispatches execute on distinct accelerators instead of queueing on
    the default device. ``devices`` defaults to ``jax.devices()``; on a
    single-device host every shard maps to that device (parallelism
    then comes from the host-side gather fanout only)."""
    if devices is None:
        devices = jax.devices()
    if not devices:
        raise ValueError("no devices to map shards onto")
    return [devices[i % len(devices)] for i in range(n_shards)]


def default_shard_transport() -> str:
    """Pick the tensor transport for process shard workers.

    ``shm`` (zero-copy ring arenas) whenever a writable ``/dev/shm``
    exists — the normal case on Linux serving hosts; ``socket``
    (in-frame ``sendmsg`` segments) otherwise. Overridable per launch
    via ``--shard-transport`` and per group via
    ``build_shard_group(transport=…)``."""
    shm = "/dev/shm"
    if os.path.isdir(shm) and os.access(shm, os.W_OK):
        return "shm"
    return "socket"


def shard_arena_bytes(n_workers: int,
                      requested: Optional[int] = None) -> int:
    """Per-direction ring capacity for each worker's shm arena.

    The arena bounds in-flight tensor bytes per worker (allocation
    back-pressure), so it must cover a few pipelined micro-batches of
    query tensors + candidate slices + reply scores — tens of MB, not
    the index size (index bytes never cross the transport; workers mmap
    their own shard subtree). 64 MiB/direction is comfortable for
    depth≲4 pipelines; when many workers share a small ``/dev/shm``,
    the cap splits a 1 GiB budget evenly rather than oversubscribing
    tmpfs."""
    if requested is not None:
        return max(1 << 20, int(requested))
    budget = 1 << 30
    per = min(64 << 20, budget // max(1, 2 * n_workers))
    return max(8 << 20, per)


def shard_worker_env(n_workers: int, *, pin_host_threads: bool = False,
                     base: Optional[dict] = None) -> dict:
    """Environment for spawned shard *worker processes*.

    Inherits the parent env and pins ``JAX_PLATFORMS`` to ``cpu``: an
    accelerator belongs to one process, and a parent that has touched
    JAX holds it, so a child that tried to open it would fail or hang
    (the coordinator keeps the accelerator; workers own the mmap/host
    side).

    ``pin_host_threads`` restricts each worker's XLA CPU compute to one
    thread — worth it when ``n_workers`` approaches the core count so
    the workers' kernels don't thrash each other's cores. **Off by
    default**: a different intra-op thread count changes floating-point
    reduction order, and the process-group parity contract (process ==
    thread == shards-1, bitwise) requires workers to run the exact XLA
    configuration the coordinator would have used."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    if pin_host_threads and n_workers > 1 and "XLA_FLAGS" not in env:
        env["XLA_FLAGS"] = ("--xla_cpu_multi_thread_eigen=false "
                            "intra_op_parallelism_threads=1")
    return env
