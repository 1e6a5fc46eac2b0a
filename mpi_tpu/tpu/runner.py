"""run_spmd — execute a portable MPI program as one SPMD trace over a Mesh.

SURVEY.md §7 Milestone 1: the TPU-native translation of "N processes
exchanging messages" is ``jax.shard_map`` over a device mesh; the launcher's
job (L0) is done by the TPU runtime.  ``run_spmd(fn, *args)`` gives ``fn`` a
TpuCommunicator and runs it on every device of the mesh; per-rank results
come back stacked on a leading axis (rank order), mirroring
``run_local``'s list-of-results.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .communicator import TpuCommunicator


def default_mesh(nranks: Optional[int] = None, axis_name: str = "world") -> Mesh:
    """1-D mesh over the first ``nranks`` local devices (all, if None).

    On a CPU host, virtual devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (SURVEY.md §4
    item 2 — the standard fake-multi-device fixture)."""
    devs = jax.devices()
    n = len(devs) if nranks is None else nranks
    if n > len(devs):
        raise ValueError(
            f"requested {n} ranks but only {len(devs)} devices are visible; "
            f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count={n}"
        )
    return Mesh(np.array(devs[:n]), (axis_name,))


_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a chip run and
    return its directory: ``JAX_COMPILATION_CACHE_DIR`` where set (JAX
    reads it itself; nothing else is set), else the fixed ``.jax_cache``
    directory of this checkout — a fixed path, because the path is part
    of the cache key.  Called by chip programs (chip_smoke.py, bench.py's
    chip children), never on import: the tests run without a cache."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _REPO_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every compile: a chip call starts cold, and the kernels of a
    # chip run are exactly the short compiles the defaults would skip
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_spmd(
    fn: Callable,
    *args: Any,
    nranks: Optional[int] = None,
    mesh: Optional[Mesh] = None,
    axis_name: str = "world",
    jit: bool = True,
    check_vma: bool = True,
    **kwargs: Any,
):
    """Run ``fn(comm, *args, **kwargs)`` as one SPMD program.

    ``args`` are replicated to every rank; each rank's return value gets a
    length-1 leading axis and the stacked [nranks, ...] result is returned
    (index it by rank to mirror ``run_local``'s per-rank list).

    ``check_vma=False`` disables shard_map's varying-axes typing.  Every
    algorithm, including ``'pallas_ring'``, now works with the checker ON
    (the kernel declares its result varying; see pallas_ring docstrings) —
    the flag remains for users who want the typing overhead gone."""
    if mesh is None:
        mesh = default_mesh(nranks, axis_name)
    comm = TpuCommunicator(axis_name, mesh)

    def shard_fn(*a):
        res = fn(comm, *a, **kwargs)
        return jax.tree.map(lambda r: jnp.asarray(r)[None], res)

    in_specs = tuple(P() for _ in args)
    f = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                      out_specs=P(axis_name), check_vma=check_vma)
    if jit:
        f = jax.jit(f)
    return f(*args)
