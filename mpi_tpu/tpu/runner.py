"""run_spmd — execute a portable MPI program as one SPMD trace over a Mesh.

SURVEY.md §7 Milestone 1: the TPU-native translation of "N processes
exchanging messages" is ``jax.shard_map`` over a device mesh; the launcher's
job (L0) is done by the TPU runtime.  ``run_spmd(fn, *args)`` gives ``fn`` a
TpuCommunicator and runs it on every device of the mesh; per-rank results
come back stacked on a leading axis (rank order), mirroring
``run_local``'s list-of-results.
"""

from __future__ import annotations

import collections
import functools
import itertools
import os
import threading
from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax._src.config import trace_context
from jax.sharding import Mesh, PartitionSpec as P

from .. import mpit, telemetry
from .communicator import TpuCommunicator

# per-process sequence number of run_spmd calls: a ``run`` span's
# ``seq``, and its stage spans' ``parent``
_RUN_SEQ = itertools.count(1)

# compiled programs kept across run_spmd calls, by _program_key, least
# recently used first; evicting one frees its executable
_PROGRAMS_MAX = 64
_PROGRAMS: collections.OrderedDict = collections.OrderedDict()
_PROGRAMS_LOCK = threading.Lock()


def clear_programs() -> None:
    """Drop every kept program: the next call of each traces anew."""
    with _PROGRAMS_LOCK:
        _PROGRAMS.clear()


def _program_key(fn, args, kwargs, mesh, axis_name, check_vma):
    """Everything the trace of ``fn`` reads: the function, its keyword
    values (closed over as constants), the mesh, the arguments' tree and
    each leaf's type and placement, and JAX's trace-time config (x64,
    matmul precision, ...), as ``jax.jit`` keys its own cache.  None
    where a part is unhashable or a leaf is no JAX type: that call keeps
    nothing."""
    try:
        leaves, tree = jax.tree.flatten(args)
        key = (fn, tuple((k, type(v), v) for k, v in sorted(kwargs.items())),
               mesh, axis_name, check_vma, tree,
               tuple((jax.typeof(x), getattr(x, "sharding", None))
                     for x in leaves),
               trace_context())
        hash(key)
    except TypeError:
        return None
    return key


def _kept(key):
    with _PROGRAMS_LOCK:
        compiled = _PROGRAMS.get(key)
        if compiled is not None:
            _PROGRAMS.move_to_end(key)
        return compiled


def _keep(key, compiled) -> None:
    with _PROGRAMS_LOCK:
        _PROGRAMS[key] = compiled
        if len(_PROGRAMS) > _PROGRAMS_MAX:
            _PROGRAMS.popitem(last=False)


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


@functools.cache
def _trace_from_env() -> None:
    """``MPI_TPU_TRACE=1`` starts the flight recorder, read at the first
    call, as ``init()`` reads it for the process backends."""
    telemetry.enable_from_env()


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
    the flag remains for users who want the typing overhead gone.

    With ``jit=True`` the program is traced, lowered and compiled (or
    loaded from the persistent cache) as explicit stages, then the
    executable is called and kept.  A later call with the same ``fn``,
    keyword values, mesh, ``axis_name``, ``check_vma``, argument tree,
    leaf shapes, dtypes, weak types and shardings, and JAX trace config
    calls the kept executable and does nothing else: as on a ``jax.jit``
    cache hit, ``fn``'s Python body does not run, so its side effects,
    and any mutable global it reads while tracing, take effect on a
    trace only.  The last ``_PROGRAMS_MAX`` programs are kept, the least
    recently used evicted first; a call with an unhashable keyword value
    traces every time and keeps nothing.  With ``jit=False`` nothing is
    kept.

    With tracing on (``telemetry.span``) every call is a ``run`` span
    (``seq``, ``nranks``); a call that traces has each stage as a child
    span (``trace``, ``lower``, ``compile``) carrying ``parent=seq``, and
    one that reuses a kept program has none.  The dispatch, and an
    eviction, are the ``run`` span's self time.  The always-on pvars
    ``tpu_program_traces`` and ``tpu_program_reuses`` count the calls
    that traced and those a kept program served."""
    _trace_from_env()
    if mesh is None:
        mesh = default_mesh(nranks, axis_name)
    key = (_program_key(fn, args, kwargs, mesh, axis_name, check_vma)
           if jit else None)
    seq = next(_RUN_SEQ)
    with telemetry.span("run", seq=seq, nranks=mesh.shape[axis_name]):
        compiled = None if key is None else _kept(key)
        if compiled is not None:
            mpit.count(tpu_program_reuses=1)
            return compiled(*args)
        comm = TpuCommunicator(axis_name, mesh)

        def shard_fn(*a):
            mpit.count(tpu_program_traces=1)  # once per trace, not per call
            res = fn(comm, *a, **kwargs)
            return jax.tree.map(lambda r: jnp.asarray(r)[None], res)

        in_specs = tuple(P() for _ in args)
        f = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                          out_specs=P(axis_name), check_vma=check_vma)
        if not jit:
            return f(*args)
        with telemetry.span("trace", parent=seq):
            traced = jax.jit(f).trace(*args)
        with telemetry.span("lower", parent=seq):
            lowered = traced.lower()
        with telemetry.span("compile", parent=seq):
            compiled = lowered.compile()
        out = compiled(*args)
        if key is not None:
            _keep(key, compiled)
        # a program not kept is freed (its executable unloaded) here,
        # inside the span, and not after it where no span would see it
        del f, traced, lowered, compiled
    return out
