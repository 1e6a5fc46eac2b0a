"""mpi_tpu — a TPU-native message-passing framework.

Capability-parity rebuild of the reference MPI-in-Python library
(mgawino/mpi; see SURVEY.md — the reference checkout itself was empty this
session, so SURVEY.md §0's contract extraction from BASELINE.json is the
blueprint).  Two backends behind one Communicator plugin boundary
(BASELINE.json:5):

* ``backend=socket`` — TCP/pickle CPU transport + mpirun-alike launcher; the
  source-compatibility proof and CPU baseline (SURVEY.md §7 Milestone 0).
* ``backend=tpu`` — MPI_COMM_WORLD bound to a ``jax.sharding.Mesh``; p2p
  lowers to ``lax.ppermute``; collectives re-emit as ``lax.psum`` /
  ``lax.all_gather`` / ``lax.all_to_all`` over ICI, with hand-scheduled
  ring / recursive-halving / tree algorithm variants (Milestones 1-2).

Also ``backend=local`` (threads, in-process) for fast tests and fault
injection.

Portable programs are written as ``def main(comm): ...`` and dispatched with
:func:`run`; classic per-process MPI scripts use :data:`COMM_WORLD` or the
flat ``MPI_*`` layer in :mod:`mpi_tpu.api`.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Optional

from .version import __version__
from . import ops
from .ops import SUM, PROD, MAX, MIN, LAND, LOR, LXOR, BAND, BOR, BXOR, ReduceOp
from .communicator import Communicator, Message, P2PCommunicator, Request, Status
from .transport.base import ANY_SOURCE, ANY_TAG
from .transport.local import run_local
from . import datatypes, errors, ft, io, membership, mpi4, progress, schedules, checker, checkpoint, profiling, telemetry, trace, verify
from .intercomm import InterComm, create_intercomm
from .topology import (CartComm, GraphComm, HierarchicalComm, cart_create,
                       dims_create, dist_graph_create_adjacent,
                       graph_create, split_hierarchical)
from .group import Group
from .spawn import (comm_accept, comm_connect, comm_get_parent, comm_spawn,
                    comm_spawn_multiple, close_port, lookup_name, open_port,
                    publish_name, unpublish_name)
from .shmwin import SharedWindow, win_allocate_shared
from .window import GetFuture, P2PWindow
from .membership import rejoin


def connect(addr, timeout: float = 30.0, priority: int = 0):
    """Connect to a resident world server (mpi_tpu/serve.py): returns a
    :class:`~mpi_tpu.serve.ServerClient` whose ``acquire(nranks)``
    leases a warm world in one round-trip.  ``addr`` is "host:port", a
    (host, port) tuple, an in-process WorldServer, or the path to a
    ``serve --addr-file`` file (a missing/partially-written file is
    retried within the connect budget).  A path to a DIRECTORY (a
    ``serve --federation`` namespace) or a list of "host:port" strings
    returns a :class:`~mpi_tpu.federation.FederatedClient` instead,
    which resolves live servers and fails over on server death.
    ``priority`` feeds the server's fair-share lease scheduler.  Lazy
    import: the serve module is also the worker entry point
    (``python -m mpi_tpu.serve``), so the package must not pre-import
    it."""
    from . import serve as _serve

    return _serve.connect(addr, timeout=timeout, priority=priority)

__all__ = [
    "__version__", "ops", "ReduceOp",
    "SUM", "PROD", "MAX", "MIN", "LAND", "LOR", "LXOR", "BAND", "BOR", "BXOR",
    "Communicator", "Message", "P2PCommunicator", "Request", "Status", "ANY_SOURCE", "ANY_TAG",
    "init", "finalize", "is_initialized", "run", "run_local",
    "schedules", "checker", "checkpoint", "ft", "membership", "profiling", "progress", "telemetry", "trace", "verify", "COMM_WORLD", "io", "mpi4",
    "connect", "rejoin", "serve",
    "CartComm", "GraphComm", "HierarchicalComm", "InterComm",
    "create_intercomm", "cart_create", "graph_create", "split_hierarchical",
    "dist_graph_create_adjacent", "dims_create", "Group",
    "GetFuture", "P2PWindow", "SharedWindow", "win_allocate_shared",
    "comm_spawn", "comm_spawn_multiple", "comm_get_parent",
    "open_port", "close_port", "comm_accept", "comm_connect",
    "publish_name", "unpublish_name", "lookup_name",
]

_ENV_RANK = "MPI_TPU_RANK"
_ENV_SIZE = "MPI_TPU_SIZE"
_ENV_RDV = "MPI_TPU_RDV"
_ENV_BACKEND = "MPI_TPU_BACKEND"

_world: Optional[P2PCommunicator] = None
_world_lock = threading.Lock()


def is_initialized() -> bool:
    return _world is not None


def init(backend: Optional[str] = None) -> Communicator:
    """Create (or return) the world communicator — MPI_Init + MPI_COMM_WORLD
    (SURVEY.md §2 component #10).

    Under the launcher (``python -m mpi_tpu.launcher -n N script.py``) this
    builds the socket transport from the launcher-provided environment;
    standalone it returns a single-rank world.
    """
    global _world
    with _world_lock:
        if _world is not None:
            return _world
        backend = backend or os.environ.get(_ENV_BACKEND) or (
            "socket" if _ENV_RANK in os.environ else "self"
        )
        if backend in ("socket", "shm"):
            rank = int(os.environ[_ENV_RANK])
            size = int(os.environ[_ENV_SIZE])
            rdv = os.environ[_ENV_RDV]
            if backend == "socket":
                from .transport.socket import SocketTransport as _T
            else:
                from .transport.shm import ShmTransport as _T

            t = _T(rank, size, rdv)
            # flight recorder (mpi_tpu/telemetry, ISSUE 13):
            # MPI_TPU_TRACE=1 / launcher --trace-dir — enabled before
            # the first collective so world-construction traffic is on
            # the timeline too
            telemetry.enable_from_env(rank=rank)
            # record which incarnation holds this world slot: the
            # elastic-membership layer's identity file (membership.py)
            # — accept_rejoin reads it to refuse an ousted-but-live
            # incarnation re-entering before failure_ack
            membership.publish_incarnation(rdv, rank)
            _world = P2PCommunicator(t, range(size))._mark_generation()
            if os.environ.get("MPI_TPU_FT", "") not in ("", "0"):
                # ULFM fault tolerance (mpi_tpu/ft.py): heartbeat files
                # under the rendezvous dir + a detector thread, so a
                # dead rank surfaces as ProcFailedError within the
                # fault_detect_timeout_s cvar instead of a stall
                from . import ft as _ft

                _ft.enable(_world, rdv_dir=rdv)
            if os.environ.get("MPI_TPU_VERIFY", "") not in ("", "0"):
                # runtime correctness verifier (mpi_tpu/verify):
                # pending-op files under the rendezvous dir — deadlocks
                # surface as DeadlockError within verify_stall_timeout_s
                # + one analysis slice, divergent collectives as
                # CollectiveMismatchError before their data moves
                verify.enable(_world, rdv_dir=rdv)
            if progress.resolve_mode() == "thread":
                # async progress engine (mpi_tpu/progress.py): one
                # daemon thread per world — background completion for
                # nonblocking ops, doorbell-parked transport draining
                # (MPI_TPU_PROGRESS=thread / launcher --progress /
                # the ``progress`` cvar)
                progress.enable(_world)
        elif backend in ("self", "local"):
            from .transport.local import LocalTransport, LocalWorld

            telemetry.enable_from_env(rank=0)
            t = LocalTransport(LocalWorld(1), 0)
            _world = P2PCommunicator(t, range(1))
        else:
            raise ValueError(
                f"unknown backend {backend!r} for process-world init; "
                "the TPU backend is entered via mpi_tpu.run(fn, backend='tpu') "
                "or mpi_tpu.tpu.run_spmd (it is an SPMD program, not a process world)"
            )
        return _world


def finalize() -> None:
    """MPI_Finalize: synchronize, close the transport, and report unexpected
    pending messages (the finalize-time sanitizer check, SURVEY.md §5)."""
    global _world
    with _world_lock:
        if _world is None:
            return
        _world.barrier()
        verified = _world._verify is not None
        if verified:
            _world._verify.world.mark_exited()
        rec = telemetry.REC
        if rec is not None and rec.trace_dir:
            # export at the orderly exit too (atexit covers sys.exit
            # paths; same filename, atomic replace — double export is
            # idempotent)
            rec.export_to_dir()
        pending = _world.close_transport()
        _world = None
    from . import mpi4 as _mpi4

    _mpi4._cfg_prune_all()  # session generation counters die with the world
    if pending:
        import warnings

        warnings.warn(f"MPI_Finalize: {len(pending)} unreceived message(s): {pending[:8]}")
    if verified:
        # finalize-time verifier report (SURVEY.md §5 sanitizer story):
        # leaked requests, unfreed communicators, recorded lints
        problems = verify.finalize_report()
        if problems:
            import warnings

            warnings.warn("MPI_Finalize: verifier report:\n  "
                          + "\n  ".join(problems))


def run(
    fn: Callable,
    *args: Any,
    backend: Optional[str] = None,
    nranks: Optional[int] = None,
    **kwargs: Any,
):
    """Run a portable MPI program ``fn(comm, *args, **kwargs)``.

    * ``backend='socket'`` (or under the launcher): calls ``fn`` with this
      process's world communicator; returns its local result.
    * ``backend='local'``: spawns ``nranks`` threads in-process; returns the
      list of per-rank results.
    * ``backend='tpu'``: traces ``fn`` once as an SPMD program over a device
      mesh (shard_map) and executes it on all devices; returns the stacked
      per-rank results (SURVEY.md §7 Milestone 1).  The compiled program
      is kept: a later call with the same ``fn``, keyword values, mesh and
      argument types runs it again without tracing, so, as with
      ``jax.jit``, ``fn``'s Python side effects and the mutable globals it
      reads take effect on a trace only (``tpu.run_spmd``).
    """
    backend = backend or os.environ.get(_ENV_BACKEND) or (
        "socket" if _ENV_RANK in os.environ else "local"
    )
    if backend in ("socket", "shm", "self"):
        return fn(init(backend), *args, **kwargs)
    if backend == "local":
        if nranks is None:
            nranks = int(os.environ.get(_ENV_SIZE, "1"))
        return run_local(fn, nranks, args=args, kwargs=kwargs)
    if backend == "tpu":
        from .tpu import run_spmd

        return run_spmd(fn, *args, nranks=nranks, **kwargs)
    raise ValueError(f"unknown backend {backend!r}")


_self_store = threading.local()


def comm_self() -> P2PCommunicator:
    """MPI_COMM_SELF [S]: the size-1 communicator containing only this
    process — independent of (and usable alongside) any world backend.
    Collectives on it are identities; it is the conventional home for
    per-process libraries (e.g. opening an MPI-IO file privately).

    Per-THREAD, not per-process: the local backend simulates ranks as
    threads, and a process-global SELF would share one mailbox across
    those ranks (self-sends could be stolen cross-rank).  For an ordinary
    single-threaded rank process the two scopes coincide."""
    comm = getattr(_self_store, "comm", None)
    if comm is None:
        from .transport.local import LocalTransport, LocalWorld

        comm = P2PCommunicator(LocalTransport(LocalWorld(1), 0), range(1))
        _self_store.comm = comm
    return comm


def __getattr__(name: str):
    if name == "COMM_WORLD":
        return init()
    if name == "COMM_SELF":
        return comm_self()
    if name == "serve":
        # lazy: mpi_tpu.serve doubles as the worker's ``-m`` entry
        # point, and an eager import here would shadow runpy's execution
        import importlib

        return importlib.import_module(".serve", __name__)
    raise AttributeError(f"module 'mpi_tpu' has no attribute {name!r}")
