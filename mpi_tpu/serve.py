"""Resident world server: MPI-as-a-service over a warm worker pool.

ROADMAP direction #1: the "millions of users" shape for an MPI library
is many SMALL worlds churned at a high rate, not one big job — and the
cold path (fork N interpreters, import numpy, bind ports, handshake
rings) costs ~seconds per world.  This module keeps all of that warm:

* ``WorldServer`` (the ``python -m mpi_tpu.launcher serve`` daemon)
  spawns ``pool_size`` **worker processes once**, each holding its live
  transport endpoints (socket connections / shm rings + pre-mapped
  arenas) and an enabled ULFM detector, then **leases** sub-worlds to
  clients: an acquire is one control round-trip that reserves idle
  slots — no fork, no handshake — and a job builds its communicator
  locally on every leased worker from ``(slots, job_id)`` (communicator
  construction is pure bookkeeping over the warm transport).
* ``mpi_tpu.connect(addr)`` is the client: ``acquire(nranks)`` →
  ``lease.run(fn, *args)`` → ``release()``.  ``fn`` is pickled by
  reference (workers import the same code), runs as ``fn(comm, *args)``
  on every leased worker, and rank 0's return value comes back.  Every
  lease either completes or raises a NAMED error — a worker death
  mid-collective surfaces to the client as ``ProcFailedError``
  (``MPI_ERR_PROC_FAILED``) within the detection bound, never a hang.
* **Self-healing** (the elastic-membership layer, mpi_tpu/membership):
  the server watches worker liveness (child exit + the PR-3 heartbeat
  files); a death bumps the pool's membership epoch, survivors are
  told to drop the corpse's endpoints (``survivor_transition``), and a
  replacement worker is spawned to ``rejoin`` the world under the new
  epoch through the claim/admit/ready protocol — so the pool keeps
  serving under continuous ``kill -9`` chaos (``bench.py --chaos
  --serve`` drives exactly that and asserts worlds/sec never reaches
  zero).

Wire protocol: length-prefixed pickle frames on a local TCP socket; the
server is the only party that ever coordinates membership, so workers
need no agreement rounds — their ULFM detectors only CONVERT blocked
waits into errors.
"""

from __future__ import annotations

import argparse
import os
import pickle
import socket
import struct
import subprocess
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import membership
from . import mpit as _mpit
from . import telemetry as _telemetry
from .errors import (DeadlockError, EpochSkewError, NoQuorumError,
                     ProcFailedError, RejoinRefusedError, RevokedError,
                     ServerBusyError, error_class)
from .transport.base import RecvTimeout, TransportError
from .transport.socket import _recv_exact

_FRAME = struct.Struct("!I")
_HOST = "127.0.0.1"

# serve defaults — the knobs the README documents; constructor / CLI
# arguments override per server.
_POOL_SIZE = 4
_WORLD_LEASE_TIMEOUT_S = 30.0   # acquire wait + default run bound
_REJOIN_TIMEOUT_S = 20.0        # one healing round's handshake bound
_DETECT_TIMEOUT_S = 2.0         # pool-internal ULFM detection bound
_HEARTBEAT_S = 0.25

# Worker pvars piggybacked on every job_done reply (ISSUE 13): the
# server keeps the latest snapshot per slot and stats()/the metrics
# endpoint aggregate them — the pool's data-plane story (healed links,
# arena hits, detected deaths) without a second control round-trip.
_WORKER_PVARS = ("msgs_sent", "collectives_started", "link_reconnects",
                 "link_faults_masked", "coll_sm_hits",
                 "proc_failures_detected", "epoch_skews_detected",
                 "trace_events")

# Sliding window of the worlds/s gauge (per-second completion buckets).
_RATE_WINDOW_S = 60.0

# ISSUE 15: idle-worker pvar piggyback cadence (PR-13 residual: the
# latest-per-slot snapshot rode job_done ONLY, so a worker that never
# completed a job reported nothing) and the orphaned-worker budget — a
# worker whose server died polls the federation namespace this long for
# the survivor that adopted its pool before giving up and exiting.
_PVAR_PUSH_S = 1.0
_ORPHAN_TIMEOUT_S = 60.0

# Slack added to a timeout-bearing request's client-side reply bound
# (ServerClient._request): covers server scheduling + reply transit on
# a loaded box.  Generous on purpose — the bound only exists to turn an
# infinite wedge (frozen server, connection ESTABLISHED but silent)
# into a finite ServerLostError.
_RPC_GRACE_S = 15.0

# Bounded admission queue (ISSUE 15): acquires past this many waiting
# requests are rejected IMMEDIATELY with ServerBusyError instead of
# converting overload into unbounded acquire latency.
_MAX_PENDING = 64

# Federation leader-lease bound (mpi_tpu/federation.py): authority
# self-expires at half this, takeover fires past it.
_FED_LEASE_TIMEOUT_S = 3.0


class ServerLostError(TransportError):
    """The control connection to the world server died mid-request —
    the server process was killed or went away.  Distinct from a
    server-SHIPPED TransportError (a worker-side failure relayed by a
    live server): only THIS class means "fail over"; a federated
    client retries acquire/stats on a survivor, while an in-flight
    ``lease.run`` surfaces it named (the lease died with its server —
    re-acquire and decide about re-running the job yourself)."""


# -- framing ------------------------------------------------------------------


def _send_msg(sock: socket.socket, lock: Optional[threading.Lock],
              msg: dict) -> None:
    blob = pickle.dumps(msg, protocol=pickle.HIGHEST_PROTOCOL)
    frame = _FRAME.pack(len(blob)) + blob
    if lock is None:
        sock.sendall(frame)
    else:
        with lock:
            sock.sendall(frame)


def _recv_msg(sock: socket.socket) -> Optional[dict]:
    head = _recv_exact(sock, _FRAME.size)
    if head is None:
        return None
    (n,) = _FRAME.unpack(head)
    body = _recv_exact(sock, n)
    if body is None:
        return None
    return pickle.loads(body)


# -- error shipping -----------------------------------------------------------

_ERROR_KINDS = {
    "ProcFailedError": ProcFailedError,
    "RevokedError": RevokedError,
    "DeadlockError": DeadlockError,
    "EpochSkewError": EpochSkewError,
    "RejoinRefusedError": RejoinRefusedError,
    "RecvTimeout": RecvTimeout,
    "TransportError": TransportError,
    "ServerBusyError": ServerBusyError,
    "ServerLostError": ServerLostError,
    "NoQuorumError": NoQuorumError,
}


def _admission_order(waiters: List[dict], grants: Dict[str, int]
                     ) -> List[dict]:
    """Scheduling order of the waiting acquires (ISSUE 15 lease
    scheduler): strict priority first, then FAIR SHARE — fewest leases
    already granted to the waiter's client identity — then FIFO.  Pure
    so the policy is unit-testable; the grant loop walks this order and
    admits the first waiter the idle capacity can satisfy (work-
    conserving: an unsatisfiable large request does not idle the pool,
    it keeps its place and the lease timeout bounds its wait)."""
    return sorted(waiters, key=lambda w: (-w["priority"],
                                          grants.get(w["client"], 0),
                                          w["seq"]))


def _pack_error(exc: BaseException) -> dict:
    return {"kind": type(exc).__name__, "code": error_class(exc),
            "msg": str(exc),
            "failed": list(getattr(exc, "failed", ()) or ()),
            "collective": getattr(exc, "collective", None)}


def _raise_error(err: dict) -> None:
    """Re-raise a shipped worker/server error client-side under its own
    name: the lease contract is 'completes or raises a NAMED FT error',
    and `except ProcFailedError` must work across the wire."""
    kind = err.get("kind", "RuntimeError")
    msg = err.get("msg", "remote failure")
    if kind == "LeaseTimeout":
        raise TimeoutError(msg)
    cls = _ERROR_KINDS.get(kind)
    if cls is ProcFailedError:
        raise ProcFailedError(msg, failed=err.get("failed", ()),
                              collective=err.get("collective"))
    if cls is not None:
        raise cls(msg)
    raise RuntimeError(f"{kind}: {msg}")


# -- built-in jobs (bench / chaos / quickstart) -------------------------------


def job_allreduce(comm, n: int = 1024) -> float:
    """The demo/bench lease payload: a correctness-checkable allreduce.
    Returns sum(1..P) so the client can assert the world really ran."""
    import numpy as np

    out = comm.allreduce(np.full(int(n), comm.rank + 1.0, np.float32))
    return float(out[0])


def job_kill_rank(comm, victim: int = 1, n: int = 4096) -> float:
    """Chaos payload: lease-rank ``victim`` dies WITHOUT cleanup inside
    the leased world (after the barrier, so every rank has entered the
    job) while the rest run a collective on it — the kill-mid-lease
    acceptance story.  Survivors surface ProcFailedError; the client
    sees MPI_ERR_PROC_FAILED."""
    import numpy as np

    comm.barrier()
    if comm.rank == victim:
        os._exit(137)
    out = comm.allreduce(np.ones(int(n), np.float32), algorithm="ring")
    return float(out[0])


def job_sleep(comm, seconds: float = 0.1) -> int:
    comm.barrier()
    time.sleep(float(seconds))
    return comm.rank


def job_allreduce_arena(comm, n: int = 1024) -> tuple:
    """Arena-observability lease payload (ISSUE 11): one auto-routed
    allreduce, returning ``(value, coll_sm_hits delta, live arena
    names)`` from lease-rank 0 so the client can assert the lease rode
    the warm POOLED arena tier (``coll_sm_hits > 0`` under a shm pool;
    on socket pools the delta is honestly 0 — there is no arena)."""
    import numpy as np

    from . import coll_sm as _coll_sm
    from . import mpit as _mpit

    before = _mpit.pvar_read("coll_sm_hits")
    out = comm.allreduce(np.full(int(n), comm.rank + 1.0, np.float32))
    hits = _mpit.pvar_read("coll_sm_hits") - before
    return (float(out[0]), int(hits), sorted(_coll_sm.live_arenas()))


def job_allreduce_link_chaos(comm, n: int = 1024, resets: int = 2) -> float:
    """Link-chaos lease payload (ISSUE 10): each leased rank hard-resets
    its cached connection to the next rank ``resets`` times while
    running allreduces — a lease must ride HEALED links (socket pool:
    the resilient layer reconnects + replays; no ProcFailedError, no
    wrong result).  Returns the last allreduce's checkable value.  On
    transports without connection links (shm pool) the injector is a
    no-op and the job degenerates to job_allreduce."""
    import numpy as np

    inject = getattr(comm._t, "_inject_link_reset", None)
    comm.barrier()
    out = None
    for i in range(int(resets) + 1):
        if inject is not None and i < int(resets) and comm.size > 1:
            inject((comm._group[(comm.rank + 1) % comm.size]))
        out = comm.allreduce(np.full(int(n), comm.rank + 1.0, np.float32),
                             algorithm="ring")
    return float(out[0])


# -- the worker process -------------------------------------------------------


def _worker_main() -> int:
    """Body of one pool worker (``python -m mpi_tpu.serve --worker``):
    bring up the world transport (fresh pool member via init(), or a
    replacement rejoining under MPI_TPU_SERVE_REJOIN=epoch:slot), then
    serve jobs from the control connection.  A control reader thread
    applies membership transitions IMMEDIATELY (even mid-job — dropping
    a corpse's endpoints must not wait for the current lease), while
    the main thread runs one job at a time.

    ISSUE 15: under a federation namespace (MPI_TPU_SERVE_FED) the
    worker SURVIVES its server — on control-channel EOF it polls the
    namespace for the survivor that adopted its pool and re-registers
    there, keeping its warm transport, arenas, and FT detector; without
    a namespace, server death still ends the worker (nothing to fail
    over to)."""
    import faulthandler
    import signal as _signal

    from . import ft as _ft
    from . import init as _init
    from . import mpit as _mpit
    from .communicator import P2PCommunicator

    # field diagnosability: the server SIGUSR2s a worker whose job
    # blew the lease timeout, so the worker's stacks land on its
    # inherited stderr — a wedged lease is diagnosable from the logs
    faulthandler.register(_signal.SIGUSR2, all_threads=True, chain=True)

    detect = os.environ.get("MPI_TPU_SERVE_DETECT_S")
    if detect:
        _mpit.cvar_write("fault_detect_timeout_s", float(detect))
    hb = os.environ.get("MPI_TPU_SERVE_HEARTBEAT_S")
    if hb:
        _mpit.cvar_write("fault_heartbeat_interval_s", float(hb))
    rdv = os.environ["MPI_TPU_RDV"]
    backend = os.environ.get("MPI_TPU_BACKEND", "socket")
    rejoin_spec = os.environ.get("MPI_TPU_SERVE_REJOIN")
    if rejoin_spec:
        epoch, slot = (int(x) for x in rejoin_spec.split(":"))
        rj_timeout = float(os.environ.get(
            "MPI_TPU_SERVE_REJOIN_TIMEOUT_S", 0) or 0) or None
        # the init() path enables tracing from the environment; the
        # rejoin path builds its transport directly, so mirror it here
        # — BEFORE the rejoin handshake, which is exactly the window
        # the rejoin-hello-race class of war story lives in
        _telemetry.enable_from_env(rank=slot)
        t, _ann = membership.rejoin_transport(
            rdv, slot=slot, epoch=epoch, backend=backend,
            timeout=rj_timeout)
        home = P2PCommunicator(t, range(t.world_size), ("epoch", epoch))
        home._mark_generation()
        _ft.enable(home, rdv_dir=rdv)
        # readiness AFTER ft.enable: the heartbeat file must be fresh
        # before survivors are told to re-admit this slot
        membership.publish_ready(rdv, epoch, t.world_rank)
        _mpit.count(rejoins=1)
    else:
        home = _init()  # MPI_TPU_FT=1 in the env: detector enabled
        t = home._t
    world_ft = t._ft_world
    slot = t.world_rank

    pool_id = (os.environ.get("MPI_TPU_SERVE_POOL")
               or os.path.basename(rdv.rstrip("/")))
    fed_ns = os.environ.get("MPI_TPU_SERVE_FED") or None
    orphan_timeout = float(os.environ.get(
        "MPI_TPU_SERVE_ORPHAN_TIMEOUT_S", str(_ORPHAN_TIMEOUT_S)))
    ctrl_addr = os.environ["MPI_TPU_SERVE_CTRL"]
    dead_addr: Optional[str] = None
    orphan_deadline: Optional[float] = None
    rc = 0
    while True:
        outcome = _worker_serve_one(ctrl_addr, t, world_ft, slot, pool_id)
        if outcome == "shutdown":
            break
        if fed_ns is None:
            # no federation: nothing to fail over to — exit LOUDLY
            # (a dial failure while the server lives would otherwise
            # crash-loop heal/respawn with zero diagnostic output)
            sys.stderr.write(
                f"mpi_tpu.serve: worker slot {slot} (pool {pool_id}) "
                f"control channel {outcome} (server {ctrl_addr}); no "
                f"federation namespace to fail over to — exiting\n")
            rc = 1
            break
        # the server died under us: the pool outlives its server
        # (ISSUE 15) — resolve the survivor that adopted this pool from
        # the federation namespace and RE-REGISTER there.  Everything
        # warm stays warm.  Only an ESTABLISHED registration dying
        # ("lost") excludes its address from the re-resolve and renews
        # the orphan budget; a failed DIAL ("unreachable") must not —
        # the current owner may be live-but-briefly-swamped, and
        # excluding it would strand this warm worker until the budget
        # ran out while the owner cold-healed the slot instead.
        from . import federation as _federation

        now = time.monotonic()
        if outcome == "lost":
            dead_addr = ctrl_addr
            orphan_deadline = now + orphan_timeout
        elif orphan_deadline is None:
            orphan_deadline = now + orphan_timeout
        remaining = orphan_deadline - now
        new_ctrl = _federation.wait_pool_owner(
            fed_ns, pool_id, not_ctrl=dead_addr,
            timeout=max(0.0, remaining)) if remaining > 0 else None
        if new_ctrl is None:
            sys.stderr.write(
                f"mpi_tpu.serve: worker slot {slot} (pool {pool_id}) "
                f"orphaned: no reachable pool owner within "
                f"{orphan_timeout}s — exiting\n")
            break
        ctrl_addr = new_ctrl
    # orderly pool shutdown: retire the pooled lease arenas (ISSUE 12
    # satellite, PR-11 residual (d)) — a worker set that never re-leased
    # after its last job has nobody else to unlink its /dev/shm segment
    from . import coll_sm as _coll_sm

    _coll_sm.retire_pooled(t)
    return rc


def _worker_serve_one(ctrl_addr: str, t, world_ft, slot: int,
                      pool_id: str) -> str:
    """One control-connection lifetime of a pool worker: dial, hello,
    serve jobs until an orderly ``shutdown`` op (→ "shutdown"), an
    ESTABLISHED registration dying (→ "lost": the server went away —
    exclude its address from the re-resolve), or a failed dial/hello
    (→ "unreachable": never registered — the target may be live but
    swamped, so the re-resolve may legitimately return it again)."""
    import queue

    from . import ft as _ft
    from .communicator import P2PCommunicator
    from .resilience import retry_connect

    host, port = ctrl_addr.rsplit(":", 1)
    try:
        ctrl = retry_connect(
            lambda: socket.create_connection((host, int(port)),
                                             timeout=10.0),
            timeout_s=10.0)
    except OSError:
        return "unreachable"
    ctrl.settimeout(None)
    ctrl.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    send_lock = threading.Lock()
    try:
        _send_msg(ctrl, send_lock, {
            "op": "hello", "slot": slot, "pool": pool_id,
            "pid": os.getpid(),
            "incarnation": membership.incarnation(), "epoch": t.epoch})
    except OSError:
        ctrl.close()
        return "unreachable"

    jobs: "queue.Queue[Optional[dict]]" = queue.Queue()
    shutdown = threading.Event()  # orderly stop vs connection death
    gone = threading.Event()      # this connection is finished

    def reader() -> None:
        while True:
            try:
                msg = _recv_msg(ctrl)
            except OSError:
                msg = None
            if msg is None or msg.get("op") == "shutdown":
                if msg is not None:
                    shutdown.set()
                gone.set()
                jobs.put(None)
                return
            op = msg.get("op")
            if op == "job":
                jobs.put(msg)
            elif op == "transition":
                # observe() FIRST: a job thread wedged in a ring-full
                # send to the corpse exits via the _peer_suspected
                # check, releasing the per-dest send lock that
                # survivor_transition's invalidate needs — the reverse
                # order deadlocks this reader against that sender for
                # a full local detection bound
                # self-filter as a second line of defense: observing
                # our own rank failed is never recoverable locally
                dead = [d for d in msg["dead"] if d != slot]
                for d in dead:
                    world_ft.observe(d, "server-declared dead "
                                        "(pool transition)")
                # even mid-job: the corpse's endpoints must go NOW, or
                # the current lease's sends keep streaming into them
                membership.survivor_transition(t, msg["epoch"], dead)
                try:
                    _send_msg(ctrl, send_lock,
                              {"op": "transition_ack", "slot": slot,
                               "epoch": msg["epoch"]})
                except OSError:
                    pass  # EOF path delivers the verdict next round
            elif op == "rejoined":
                world_ft.reset_rank(msg["slot"])
                t.min_peer_epoch[int(msg["slot"])] = int(msg["epoch"])

    threading.Thread(target=reader, daemon=True,
                     name=f"serve-ctrl-{slot}").start()

    fed_ns = os.environ.get("MPI_TPU_SERVE_FED") or None

    def pvar_push() -> None:
        # ISSUE 15 satellite (PR-13 metrics residual): the pvar
        # snapshot used to piggyback on job_done ONLY, so an idle or
        # wedged worker reported nothing — push it on the control
        # channel at a fixed cadence too, so stats() sees every worker
        while not gone.wait(_PVAR_PUSH_S):
            try:
                _send_msg(ctrl, send_lock, {
                    "op": "pvars", "slot": slot,
                    "pvars": {n: _mpit.pvar_read(n)
                              for n in _WORKER_PVARS}})
            except OSError:
                return
            if fed_ns is None:
                continue
            # the frozen-master escape (a SIGSTOP'd server keeps our
            # TCP connection ESTABLISHED forever — EOF alone can never
            # free us): if the namespace names a LIVE owner other than
            # the server we are serving, our master was deposed while
            # frozen — defect by closing the connection ourselves,
            # which drops us into the normal re-resolve path
            from . import federation as _federation

            rec = _federation.read_pool_owner(fed_ns, pool_id)
            if rec is not None and rec.get("ctrl") \
                    and rec["ctrl"] != ctrl_addr:
                srv = _federation.read_server_record(
                    fed_ns, str(rec.get("owner")))
                if srv is None or _federation.record_live(srv):
                    sys.stderr.write(
                        f"mpi_tpu.serve: worker slot {slot} (pool "
                        f"{pool_id}): ownership moved to "
                        f"{rec.get('owner')} while our master "
                        f"{ctrl_addr} held the connection — "
                        f"defecting\n")
                    gone.set()
                    try:
                        # shutdown BEFORE close: close() alone never
                        # wakes the reader thread blocked in recv()
                        ctrl.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass
                    try:
                        ctrl.close()
                    except OSError:
                        pass
                    return

    threading.Thread(target=pvar_push, daemon=True,
                     name=f"serve-pvars-{slot}").start()

    while True:
        msg = jobs.get()
        if msg is None:
            break
        job_id, slots = msg["job_id"], list(msg["slots"])
        rec = _telemetry.REC
        t_job = time.perf_counter_ns() if rec is not None else 0
        try:
            fn = pickle.loads(msg["fn"])
            args = pickle.loads(msg["args"])
            comm = P2PCommunicator(t, slots, ("lease", job_id))
            comm._ft = _ft.CommFT(world_ft, ("lease", job_id))
            # coll/sm arena via the POOLED path (ISSUE 11, closes the
            # PR-7 "leases skip the arena" residual): one epoch-stamped
            # arena per worker set, reused across leases — the epoch is
            # the SERVER's stamp shipped with the job, so every leased
            # worker keys the same segment even if a concurrent
            # transition broadcast races the dispatch
            comm._coll_sm_pool_ctx = ("lease-pool",
                                      int(msg.get("epoch", 0)))
            result = fn(comm, *args)
            reply = {"op": "job_done", "job_id": job_id, "slot": slot,
                     "ok": True}
            if comm.rank == 0:
                reply["result"] = pickle.dumps(
                    result, protocol=pickle.HIGHEST_PROTOCOL)
        except BaseException as e:  # noqa: BLE001 - shipped to the client
            reply = {"op": "job_done", "job_id": job_id, "slot": slot,
                     "ok": False, "error": _pack_error(e)}
        if rec is not None:
            rec.emit("lease", "job",
                     dur_ns=time.perf_counter_ns() - t_job,
                     attrs={"job_id": job_id, "slots": slots,
                            "ok": reply["ok"],
                            "error": (reply.get("error") or {}).get(
                                "kind")})
        # ISSUE 13: piggyback a pvar snapshot for the server's metrics
        # aggregation — latest-per-slot, summed by stats()
        reply["pvars"] = {n: _mpit.pvar_read(n) for n in _WORKER_PVARS}
        try:
            _send_msg(ctrl, send_lock, reply)
        except OSError:
            # server gone mid-reply: the lease died with it; drop the
            # reply and let the caller re-resolve the pool's owner
            gone.set()
            try:
                ctrl.close()
            except OSError:
                pass
            return "lost"
    try:
        ctrl.close()
    except OSError:
        pass
    return "shutdown" if shutdown.is_set() else "lost"


# -- the server ---------------------------------------------------------------


class _Worker:
    __slots__ = ("slot", "pool", "proc", "pid", "conn", "send_lock",
                 "state", "incarnation", "epoch", "lease_id",
                 "spawned_at")

    def __init__(self, slot: int, pool: str) -> None:
        self.slot = slot
        self.pool = pool
        self.proc: Optional[subprocess.Popen] = None
        # adopted workers (federation takeover) were never our children:
        # no Popen handle — the hello's pid + the heartbeat file carry
        # their liveness instead
        self.pid: Optional[int] = None
        self.conn: Optional[socket.socket] = None
        self.send_lock = threading.Lock()
        self.state = "starting"  # starting|idle|leased|dead
        self.incarnation: Optional[str] = None
        self.epoch = 0
        self.lease_id: Optional[int] = None
        self.spawned_at = time.monotonic()


class _Pool:
    """One warm worker pool: a transport world over one rendezvous dir.
    A server's HOME pool is forked by start(); ADOPTED pools (ISSUE 15
    federation takeover) arrive as metadata — their live orphaned
    workers re-register over the control channel, and worker-level
    healing runs the same announce/claim/admit protocol against the
    adopted rendezvous dir."""

    __slots__ = ("pool_id", "rdv", "backend", "size", "epoch", "home",
                 "adopted_at", "owned_since")

    def __init__(self, pool_id: str, rdv: str, backend: str, size: int,
                 home: bool, epoch: int = 0) -> None:
        self.pool_id = pool_id
        self.rdv = rdv
        self.backend = backend
        self.size = int(size)
        self.epoch = int(epoch)
        self.home = home
        self.adopted_at = None if home else time.monotonic()
        self.owned_since = time.time()


class WorldServer:
    """The resident daemon: a pool of warm workers, leased as worlds.

    Use as a context manager (tests / in-process benches) or through
    ``python -m mpi_tpu.launcher serve`` (deployment).  ``addr`` is the
    ``host:port`` clients pass to :func:`connect`."""

    def __init__(self, pool_size: int = _POOL_SIZE, backend: str = "socket",
                 host: str = _HOST, port: int = 0,
                 detect_timeout_s: float = _DETECT_TIMEOUT_S,
                 heartbeat_s: float = _HEARTBEAT_S,
                 world_lease_timeout_s: float = _WORLD_LEASE_TIMEOUT_S,
                 rejoin_timeout_s: float = _REJOIN_TIMEOUT_S,
                 env_extra: Optional[dict] = None,
                 metrics_port: Optional[int] = None,
                 federation: Optional[str] = None,
                 server_id: Optional[str] = None,
                 fed_lease_timeout_s: float = _FED_LEASE_TIMEOUT_S,
                 max_pending: int = _MAX_PENDING,
                 orphan_timeout_s: float = _ORPHAN_TIMEOUT_S) -> None:
        if pool_size < 1:
            raise ValueError("pool_size must be >= 1")
        if max_pending < 1:
            raise ValueError("max_pending must be >= 1")
        if backend == "shm":
            from .native import ensure_built

            ensure_built()  # compile once, not pool_size racing ranks
        self.pool_size = pool_size
        self.backend = backend
        self.detect_timeout_s = float(detect_timeout_s)
        self.heartbeat_s = float(heartbeat_s)
        self.world_lease_timeout_s = float(world_lease_timeout_s)
        self.rejoin_timeout_s = float(rejoin_timeout_s)
        self.max_pending = int(max_pending)
        self.orphan_timeout_s = float(orphan_timeout_s)
        self._env_extra = dict(env_extra or {})
        self.rdv = membership.new_rendezvous_dir(prefix="mpi_tpu_serve_")
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(pool_size + 16)
        self.addr = "%s:%d" % self._listener.getsockname()[:2]
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._closing = False
        # pools (ISSUE 15): the home pool is this server's forked
        # worker world; adopted pools arrive via federation takeover.
        # Workers/healing/pvars are keyed (pool_id, slot) throughout.
        self._home = os.path.basename(self.rdv.rstrip("/"))
        self._pools: Dict[str, _Pool] = {
            self._home: _Pool(self._home, self.rdv, backend, pool_size,
                              home=True)}
        self._relinquished_home_epoch = 0
        self._workers: Dict[Tuple[str, int], _Worker] = {}
        self._leases: Dict[int, dict] = {}
        self._jobs: Dict[int, dict] = {}
        self._healing: Dict[Tuple[str, int], dict] = {}
        self._seq = 0
        # admission control (ISSUE 15): bounded waiter queue + the
        # fair-share grant ledger (leases granted per client identity)
        self._waiters: List[dict] = []
        self._client_grants: Dict[str, int] = {}
        self.stats_counters = {"leases_granted": 0, "leases_denied": 0,
                               "jobs_ok": 0, "jobs_failed": 0,
                               "heals_completed": 0, "workers_lost": 0,
                               "busy_rejected": 0,
                               "no_quorum_rejected": 0,
                               "orphans_reregistered": 0,
                               "pools_adopted": 0,
                               "pools_relinquished": 0}
        self._threads: List[threading.Thread] = []
        # federation membership (ISSUE 15): namespace dir + identity;
        # the member thread starts in start()
        self._fed_ns = federation
        self.server_id = server_id or ("srv-" + uuid.uuid4().hex[:8])
        self._fed_lease_timeout_s = float(fed_lease_timeout_s)
        self._fed = None
        # ISSUE 18: refuse NEW leases while the namespace store has no
        # quorum (minority side of a partition).  Default on; the
        # chaos "pre" leg turns it off to demonstrate the failure mode
        # it closes (a minority server serving on stale authority).
        self._store_fence = os.environ.get(
            "MPI_TPU_SERVE_STORE_FENCE", "1") != "0"
        # observability (ISSUE 13): uptime anchor for the worlds/s
        # gauge, per-second completed-job buckets (sliding window —
        # bounded at ~window-many keys regardless of rate, unlike a
        # timestamp deque whose maxlen would cap the measurable rate),
        # the latest per-slot worker pvar snapshot, and the optional
        # Prometheus endpoint (metrics_port; 0 = ephemeral, see
        # metrics_addr)
        self._t0 = time.monotonic()
        self._ok_buckets: Dict[int, int] = {}
        self._worker_pvars: Dict[Tuple[str, int], dict] = {}
        self._metrics_port = metrics_port
        self._metrics_httpd = None
        self.metrics_addr: Optional[str] = None
        self._host = host

    # -- lifecycle ---------------------------------------------------------

    @property
    def epoch(self) -> int:
        """The HOME pool's membership epoch (the single-pool stats
        contract every pre-federation caller relies on); per-pool
        epochs live in ``stats()["pools"]``."""
        pool = self._pools.get(self._home)
        return pool.epoch if pool is not None \
            else self._relinquished_home_epoch

    def start(self, wait_ready: bool = True,
              timeout: float = 120.0) -> "WorldServer":
        # the lease-acquire histogram is a process-global mpit pvar:
        # start this server's document clean so sequential in-process
        # servers (the test idiom) don't report a predecessor's tail
        # as their own p99.  (Two CONCURRENT servers in one process —
        # not a deployment shape — still share it.)
        _mpit.pvar_hist_reset("lease_acquire_s")
        for slot in range(self.pool_size):
            key = (self._home, slot)
            self._workers[key] = _Worker(slot, self._home)
            self._spawn_worker(key)
        for name, target in (("accept", self._accept_loop),
                             ("monitor", self._monitor_loop)):
            th = threading.Thread(target=target, daemon=True,
                                  name=f"serve-{name}")
            th.start()
            self._threads.append(th)
        if self._metrics_port is not None:
            self._start_metrics(self._metrics_port)
        if self._fed_ns is not None:
            # join the federation namespace: endpoint record, leader
            # lease, pool-ownership publication, takeover duties
            from . import federation as _federation

            self._fed = _federation.FederationMember(
                self, self._fed_ns, server_id=self.server_id,
                lease_timeout_s=self._fed_lease_timeout_s).start()
        if wait_ready:
            deadline = time.monotonic() + timeout
            with self._cond:
                while any(w.state == "starting"
                          for w in self._workers.values()):
                    if self._closing:
                        raise RuntimeError("server stopped during start")
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"worker pool not ready within {timeout}s: "
                            + str({s: w.state for s, w
                                   in self._workers.items()}))
                    self._cond.wait(min(0.25, remaining))
        return self

    def __enter__(self) -> "WorldServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def stop(self) -> None:
        with self._cond:
            if self._closing:
                return
            self._closing = True
            # every mutable read snapshotted HERE: the monitor thread
            # may be mid-heal, mutating conns and self._healing
            conns = [(w.conn, w.send_lock)
                     for w in self._workers.values()
                     if w.conn is not None]
            procs = [w.proc for w in self._workers.values()
                     if w.proc is not None]
            procs += [h["proc"] for h in self._healing.values()
                      if h.get("proc") is not None]
            # adopted workers are not our children: ask them to stop
            # via the shutdown op (sent below); their pids are the only
            # handle left for the last-resort sweep
            adopted_pids = [w.pid for w in self._workers.values()
                            if w.proc is None and w.pid]
            pools = list(self._pools.values())
            self._cond.notify_all()
        if self._fed is not None:
            # leave the namespace FIRST: records retract before the
            # pools die, so no leader assigns a takeover of a pool
            # whose workers are about to receive shutdown
            self._fed.stop()
        for conn, lk in conns:
            try:
                _send_msg(conn, lk, {"op": "shutdown"})
            except OSError:
                pass
        try:
            self._listener.close()
        except OSError:
            pass
        httpd = self._metrics_httpd
        if httpd is not None:
            self._metrics_httpd = None
            try:
                httpd.shutdown()
                httpd.server_close()
            except OSError:  # pragma: no cover - teardown race
                pass
        deadline = time.monotonic() + 5.0
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(max(0.0, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(2.0)
                except subprocess.TimeoutExpired:  # pragma: no cover
                    pass
        # adopted workers received the shutdown op above; sweep any
        # that did not exit (two masters of one pool must never coexist
        # with the rendezvous dirs about to vanish)
        deadline = time.monotonic() + 3.0
        for pid in adopted_pids:
            while membership._pid_alive(pid) \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
            if membership._pid_alive(pid):
                try:
                    os.kill(pid, 9)
                except OSError:
                    pass
        for pool in pools:
            membership.cleanup_rendezvous(pool.rdv)

    # -- metrics endpoint (ISSUE 13) ---------------------------------------

    def _start_metrics(self, port: int) -> None:
        """Serve ``GET /metrics`` (Prometheus text format, rendered by
        mpi_tpu/telemetry/metrics.py from the same ``stats()`` document
        ``client.stats()`` returns) on a side HTTP port.  Port 0 binds
        ephemeral — ``metrics_addr`` reports the outcome.  The handler
        only READS (stats() takes the server lock briefly); a scrape
        can never wedge the monitor/heal machinery."""
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        from .telemetry import metrics as _metrics

        server = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self) -> None:  # noqa: N802 - http.server API
                if self.path.split("?", 1)[0] not in ("/", "/metrics"):
                    self.send_error(404)
                    return
                try:
                    body = _metrics.prometheus_text(
                        server.stats()).encode()
                except Exception as e:  # noqa: BLE001 - shipped as 500
                    self.send_error(500, type(e).__name__)
                    return
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args) -> None:  # noqa: D102
                pass  # scrapes are not server-log events

        httpd = ThreadingHTTPServer((self._host, int(port)), Handler)
        httpd.daemon_threads = True
        self._metrics_httpd = httpd
        self.metrics_addr = "%s:%d" % httpd.server_address[:2]
        th = threading.Thread(target=httpd.serve_forever,
                              daemon=True, name="serve-metrics")
        th.start()
        self._threads.append(th)

    # -- worker processes --------------------------------------------------

    def _worker_env(self, key: Tuple[str, int],
                    rejoin_epoch: Optional[int] = None) -> dict:
        from .launcher import cpu_pinned_env

        pool_id, slot = key
        pool = self._pools[pool_id]
        env = dict(os.environ)
        want = self._env_extra.get("MPI_TPU_RANK_JAX_PLATFORMS")
        cpu_pinned_env(env, want, pool.size)
        env.update({
            "MPI_TPU_RANK": str(slot),
            "MPI_TPU_SIZE": str(pool.size),
            "MPI_TPU_RDV": pool.rdv,
            "MPI_TPU_BACKEND": pool.backend,
            "MPI_TPU_FT": "1",
            "MPI_TPU_SERVE_CTRL": self.addr,
            "MPI_TPU_SERVE_POOL": pool_id,
            "MPI_TPU_SERVE_DETECT_S": str(self.detect_timeout_s),
            "MPI_TPU_SERVE_HEARTBEAT_S": str(self.heartbeat_s),
            "MPI_TPU_SERVE_ORPHAN_TIMEOUT_S": str(self.orphan_timeout_s),
        })
        env.pop("MPI_TPU_SERVE_FED", None)
        if self._fed_ns is not None:
            # CLIENT spec: a raft:<idx>@... member spec must not leak
            # into workers — they resolve pool owners over the store's
            # RPC port, never by embedding a node
            from . import federation_store as _fstore

            env["MPI_TPU_SERVE_FED"] = _fstore.client_spec(self._fed_ns)
        env.pop("MPI_TPU_SERVE_REJOIN", None)
        if rejoin_epoch is not None:
            env["MPI_TPU_SERVE_REJOIN"] = f"{rejoin_epoch}:{slot}"
            env["MPI_TPU_SERVE_REJOIN_TIMEOUT_S"] = \
                str(self.rejoin_timeout_s)
        env.update(self._env_extra)
        return env

    def _spawn_worker(self, key: Tuple[str, int],
                      rejoin_epoch: Optional[int] = None
                      ) -> subprocess.Popen:
        proc = subprocess.Popen(
            [sys.executable, "-m", "mpi_tpu.serve", "--worker"],
            env=self._worker_env(key, rejoin_epoch))
        if rejoin_epoch is None:
            self._workers[key].proc = proc
            self._workers[key].spawned_at = time.monotonic()
        return proc

    # -- accept / connection handling --------------------------------------

    def _accept_loop(self) -> None:
        while not self._closing:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(target=self._conn_loop, args=(conn,),
                             daemon=True, name="serve-conn").start()

    def _conn_loop(self, conn: socket.socket) -> None:
        first = _recv_msg(conn)
        if first is None:
            conn.close()
            return
        if first.get("op") == "hello":
            self._worker_loop(conn, first)
        else:
            self._client_loop(conn, first)

    # -- worker side -------------------------------------------------------

    def _worker_loop(self, conn: socket.socket, hello: dict) -> None:
        slot = int(hello["slot"])
        pool_id = str(hello.get("pool") or self._home)
        key = (pool_id, slot)
        with self._cond:
            w = self._workers.get(key)
            pool = self._pools.get(pool_id)
            if w is None or pool is None:
                # unknown slot, or a pool relinquished/adopted-away in
                # the hello race: EOF sends the worker back to the
                # namespace, where the current owner's record is
                conn.close()
                return
            if w.conn is not None and w.state in ("idle", "leased") \
                    and hello.get("incarnation") != w.incarnation:
                # the slot is LIVE under a different incarnation (e.g.
                # a long-frozen ex-orphan thawing after its slot was
                # healed): refuse — two incarnations of one slot must
                # never coexist, and the EOF sends the zombie through
                # the re-resolve path to reap itself
                conn.close()
                return
            heal = self._healing.pop(key, None)
            if heal is not None:
                w.proc = heal["proc"]
                self.stats_counters["heals_completed"] += 1
            elif w.proc is None and not pool.home:
                # an orphan of the adopted pool re-registering (ISSUE
                # 15): everything about it is warm — it becomes
                # leasable the moment this hello lands
                self.stats_counters["orphans_reregistered"] += 1
            w.conn = conn
            w.pid = hello.get("pid")
            w.incarnation = hello.get("incarnation")
            w.epoch = int(hello.get("epoch", 0))
            w.lease_id = None
            # an adopted pool learns its epoch from its workers (the
            # dead server's transitions already reached them)
            pool.epoch = max(pool.epoch, w.epoch)
            # (conn, lock) pairs snapshotted under the lock — see
            # _begin_heal for the concurrent-death rationale
            peers = [(p.conn, p.send_lock)
                     for p in self._workers.values()
                     if p is not w and p.pool == pool_id
                     and p.conn is not None
                     and p.state not in ("dead",)]
            behind = w.epoch < pool.epoch
            catchup = {"op": "transition", "epoch": pool.epoch,
                       # never list the hello-ing worker's OWN slot
                       # (its state is still 'dead' right here): a
                       # worker observing itself failed would poison
                       # every FT decision of its future leases
                       "dead": [p.slot for p in self._workers.values()
                                if p is not w and p.pool == pool_id
                                and (p.state == "dead"
                                     or (pool_id, p.slot)
                                     in self._healing)]}
        if behind:
            # another death's transition was broadcast while this
            # worker was still rejoining (excluded as 'dead'): resync
            # it NOW or its first send to an up-epoch survivor raises
            # EpochSkewError forever while stats report a healthy pool
            try:
                _send_msg(conn, w.send_lock, catchup)
            except OSError:
                pass  # EOF path marks it dead next
        if heal is not None:
            # tell the survivors the slot is live again under its epoch
            # BEFORE the slot becomes leasable: a job dispatched to a
            # peer rides the same FIFO control connection as this
            # 'rejoined', so each peer clears its detector's failed
            # entry before it can possibly run a lease with the healed
            # slot — idle-first would let the first post-heal lease
            # raise a spurious ProcFailedError off the stale failed set
            for conn_p, lk_p in peers:
                try:
                    _send_msg(conn_p, lk_p,
                              {"op": "rejoined", "slot": slot,
                               "epoch": w.epoch})
                except OSError:
                    pass
        with self._cond:
            w.state = "idle"
            self._cond.notify_all()
        while True:
            msg = _recv_msg(conn)
            if msg is None:
                with self._cond:
                    if not self._closing \
                            and self._workers.get(key) is w \
                            and w.conn is conn and w.state != "dead":
                        self._mark_dead_locked(w, "control channel EOF")
                    self._cond.notify_all()
                return
            op = msg.get("op")
            if op == "job_done":
                self._job_done(key, msg)
            elif op == "pvars":
                # ISSUE 15 satellite: the idle/wedged-worker pvar push
                # — latest-per-slot, same aggregation as the job_done
                # piggyback, so stats() sees workers that never
                # completed a job.  Existence-guarded: an in-flight
                # push must not resurrect a key relinquish_pool just
                # popped (the usurper counts those slots now —
                # double-counting would falsify the roll-up for good)
                with self._cond:
                    if key in self._workers:
                        self._worker_pvars[key] = msg.get("pvars") or {}
            # transition_acks are informational: the monitor's spawn of
            # the replacement does not wait on them (a wedged worker
            # must not stall the pool's healing)

    def _job_done(self, key: Tuple[str, int], msg: dict) -> None:
        slot = key[1]
        with self._cond:
            pvars = msg.get("pvars")
            if pvars and key in self._workers:
                self._worker_pvars[key] = pvars
            job = self._jobs.get(msg["job_id"])
            if job is None:
                return
            job["pending"].discard(slot)
            if msg.get("ok"):
                if "result" in msg:
                    job["result"] = msg["result"]
            else:
                job["errors"].append(msg.get("error", {}))
            if not job["pending"]:
                job["event"].set()
            self._cond.notify_all()

    def _mark_dead_locked(self, w: _Worker, why: str) -> None:
        """State transition for a lost worker (caller holds the lock):
        pool-epoch bump + fail its in-flight job; the monitor loop
        picks the slot up for healing on its next tick."""
        if w.state == "dead":
            return
        pool = self._pools.get(w.pool)
        w.state = "dead"
        w.conn = None
        rec = _telemetry.REC
        if rec is not None:
            rec.emit("lease", "worker_dead",
                     attrs={"slot": w.slot, "pool": w.pool, "why": why,
                            "epoch": (pool.epoch + 1 if pool is not None
                                      else -1)})
        if w.proc is not None and w.proc.poll() is None:
            # declared dead but the process lives (heartbeat-stale
            # wedge): kill it — two live incarnations of one slot must
            # never coexist, and the replacement hello overwrites
            # w.proc, dropping stop()'s only handle on this one
            try:
                w.proc.kill()
            except OSError:
                pass
        elif w.proc is None and w.pid:
            # adopted worker (no Popen handle): same rule, by pid
            try:
                os.kill(w.pid, 9)
            except OSError:
                pass
        self.stats_counters["workers_lost"] += 1
        if pool is not None:
            pool.epoch += 1
        for job in self._jobs.values():
            if job.get("pool") == w.pool and w.slot in job["pending"]:
                job["pending"].discard(w.slot)
                job["errors"].append({
                    "kind": "ProcFailedError",
                    "code": error_class(ProcFailedError("")),
                    "msg": f"leased worker slot {w.slot} died ({why})",
                    "failed": [w.slot], "collective": None})
                if not job["pending"]:
                    job["event"].set()

    # -- monitoring / healing ----------------------------------------------

    def _hb_stale(self, pool: _Pool, slot: int, now: float) -> bool:
        age = membership.heartbeat_age(pool.rdv, slot, now)
        if age is None:
            return False  # not yet published: proc liveness covers it
        return age > 3.0 * self.detect_timeout_s

    def _adopt_grace_s(self) -> float:
        """How long an adopted pool's slot may stay 'starting' (its
        orphan resolving the takeover from the namespace) before its
        heartbeat decides whether it is a corpse to heal."""
        return max(5.0, 3.0 * self.detect_timeout_s)

    def _monitor_loop(self) -> None:
        while not self._closing:
            time.sleep(self.heartbeat_s)
            if self._closing:
                return
            try:
                self._monitor_tick()
            except Exception as e:  # noqa: BLE001 - the pool's lifeline
                if self._closing:
                    return  # shutdown raced a heal (rdv dir removed)
                # a monitor crash must never silently end healing: a
                # STRUCTURED line (what failed, pool state) + telemetry
                # event instead of ISSUE 7's bare print_exc, then keep
                # ticking (ISSUE 13 satellite)
                import traceback

                with self._lock:
                    epoch, healing = self.epoch, sorted(self._healing)
                sys.stderr.write(
                    f"mpi_tpu.serve: monitor tick failed "
                    f"({type(e).__name__}: {str(e)[:200]}; epoch "
                    f"{epoch}, healing slots {healing}) — healing "
                    f"continues:\n{traceback.format_exc()}")
                rec = _telemetry.REC
                if rec is not None:
                    rec.emit("serve", "monitor_error",
                             attrs={"error": type(e).__name__,
                                    "epoch": epoch,
                                    "healing": healing})

    def _monitor_tick(self) -> None:
        now_wall = time.time()
        with self._cond:
            for key, w in self._workers.items():
                if w.state == "dead" or key in self._healing:
                    continue
                pool = self._pools.get(w.pool)
                if pool is None:
                    continue  # relinquish race: workers go next tick
                lost = (w.proc is not None
                        and w.proc.poll() is not None)
                if not lost and w.proc is None and w.pid \
                        and w.state != "starting":
                    # adopted worker: no Popen handle — pid liveness
                    lost = not membership._pid_alive(w.pid)
                if not lost and w.state != "starting":
                    lost = self._hb_stale(pool, w.slot, now_wall)
                if not lost and w.state == "starting" \
                        and pool.adopted_at is not None:
                    # an adopted slot whose orphan never re-registered:
                    # past the adoption grace, the heartbeat file (the
                    # one liveness signal that survives a change of
                    # ownership) decides corpse-or-slow
                    if time.monotonic() - pool.adopted_at \
                            > self._adopt_grace_s():
                        age = membership.heartbeat_age(pool.rdv, w.slot,
                                                       now_wall)
                        lost = (age is None
                                or age > 3.0 * self.detect_timeout_s)
                if lost:
                    self._mark_dead_locked(
                        w, "process exited"
                        if w.proc is not None
                        and w.proc.poll() is not None
                        else "heartbeat stale")
            # heal EVERY dead slot without a healing round in
            # flight — deaths are marked both here and by the
            # worker-connection EOF path, and both must converge on
            # a replacement
            dead_now = [w for key, w in self._workers.items()
                        if w.state == "dead" and key not in self._healing]
            if dead_now:
                self._cond.notify_all()
        if dead_now:
            self._begin_heal(dead_now)
        self._drive_healing()

    def _begin_heal(self, dead: List[_Worker]) -> None:
        """One healing round per affected pool: tell that pool's
        survivors, announce the vacancies on ITS rendezvous dir, spawn
        replacements that rejoin under the pool's bumped epoch —
        identical for the home pool and an adopted one (the membership
        protocol is all files under the pool's own rdv)."""
        by_pool: Dict[str, List[_Worker]] = {}
        for w in dead:
            by_pool.setdefault(w.pool, []).append(w)
        for pool_id, ws in by_pool.items():
            dead_slots = [w.slot for w in ws]
            with self._lock:
                pool = self._pools.get(pool_id)
                if pool is None:
                    continue  # relinquished mid-round: new owner heals
                epoch = pool.epoch
                # snapshot (conn, lock) PAIRS under the lock: a
                # concurrent death nulls worker.conn, and re-reading it
                # outside the lock would hand None to sendall
                # (AttributeError kills the monitor thread — the pool
                # would stop healing entirely)
                live = [(p.conn, p.send_lock)
                        for p in self._workers.values()
                        if p.pool == pool_id
                        and p.state not in ("dead", "starting")
                        and p.conn is not None]
            for conn, lk in live:
                try:
                    _send_msg(conn, lk,
                              {"op": "transition", "epoch": epoch,
                               "dead": dead_slots})
                except OSError:
                    pass  # its own death will be noticed next tick
            slots_meta = {
                s: {"ousted": membership.read_incarnation(pool.rdv, s),
                    # the server IS the membership authority: it
                    # observed the death and decided to replace, which
                    # is the ack — the refusal gate still protects
                    # against an UNINVITED ousted incarnation claiming
                    # before the server's replacement (it presents the
                    # ousted id; the spawned replacement presents a
                    # fresh one)
                    "acked": False}
                for s in dead_slots}
            membership.announce_rejoin(pool.rdv, epoch, slots_meta,
                                       pool.size, pool.backend)
            with self._lock:
                if self._closing:
                    return  # a stop() racing this heal owns every process
                if pool_id not in self._pools:
                    continue  # relinquished while announcing
                for w in ws:
                    key = (pool_id, w.slot)
                    proc = self._spawn_worker(key, rejoin_epoch=epoch)
                    self._healing[key] = {
                        "epoch": epoch, "proc": proc,
                        "since": time.monotonic(), "meta": slots_meta}

    def _drive_healing(self) -> None:
        """Per-tick healing duties: validate claims/admit replacements
        (the announcer role of the membership protocol), and respawn a
        replacement that died during its own rejoin handshake — the
        pool recovers, no epoch fork (the announce stays valid)."""
        with self._lock:
            healing = dict(self._healing)
        for key, h in healing.items():
            pool_id, slot = key
            pool = self._pools.get(pool_id)
            if pool is None:
                # the pool was relinquished mid-heal: the usurper owns
                # its healing now — reap our half-spawned replacement
                with self._lock:
                    self._healing.pop(key, None)
                try:
                    h["proc"].kill()
                except OSError:
                    pass
                continue
            membership.process_claims(pool.rdv, h["epoch"],
                                      {slot: h["meta"][slot]})
            proc = h["proc"]
            if proc.poll() is not None:
                with self._lock:
                    if self._closing or key not in self._healing:
                        continue
                    h["proc"] = self._spawn_worker(
                        key, rejoin_epoch=h["epoch"])
                    h["since"] = time.monotonic()
                    self._healing[key] = h
            elif time.monotonic() - h["since"] > self.rejoin_timeout_s:
                # the replacement is ALIVE but wedged in its handshake
                # past the rejoin bound: kill it — next tick's poll()
                # branch respawns, and process_claims sweeps its
                # leftover claim (dead pid).  Re-check under the lock
                # that this round is STILL healing (mirroring the
                # respawn branch): the worker may have completed its
                # hello since the snapshot, and killing a just-healed,
                # possibly-leased worker would livelock healing
                with self._lock:
                    still = (not self._closing
                             and self._healing.get(key) is h)
                if still:
                    try:
                        proc.kill()
                    except OSError:
                        pass

    # -- federation hooks (ISSUE 15; called by FederationMember) -----------

    def owned_pool_records(self) -> Dict[str, dict]:
        """Metadata of every pool this server currently serves — what
        the federation member publishes as ownership records."""
        with self._lock:
            return {pid: {"rdv": p.rdv, "backend": p.backend,
                          "size": p.size, "epoch": p.epoch,
                          "since": p.owned_since}
                    for pid, p in self._pools.items()}

    def fed_summary(self) -> dict:
        """The light per-server summary embedded in the endpoint
        record (federation_stats sums these across the namespace)."""
        now = time.monotonic()
        with self._lock:
            states = [w.state for w in self._workers.values()]
            return {"pools": len(self._pools),
                    "workers": len(states),
                    "idle": states.count("idle"),
                    "leases_active": len(self._leases),
                    "waiting": len(self._waiters),
                    "worlds_per_s": self._worlds_per_s_locked(now),
                    "backend": self.backend}

    def adopt_pool(self, pool_id: str, rec: dict, term: int = 0) -> bool:
        """Take over a dead server's pool (leader-assigned takeover):
        register its metadata and one 'starting' worker entry per slot
        — the live orphans re-register via their control-channel
        re-resolve, and a slot whose orphan never shows is healed
        through the normal announce/claim/admit path against the
        adopted rendezvous dir after the adoption grace."""
        with self._cond:
            if self._closing or pool_id in self._pools:
                return False
            pool = _Pool(pool_id, rec["rdv"],
                         rec.get("backend", "socket"), int(rec["size"]),
                         home=False, epoch=int(rec.get("epoch", 0)))
            self._pools[pool_id] = pool
            for s in range(pool.size):
                self._workers[(pool_id, s)] = _Worker(s, pool_id)
            self.stats_counters["pools_adopted"] += 1
            self._cond.notify_all()
        rec_t = _telemetry.REC
        if rec_t is not None:
            rec_t.emit("serve", "pool_adopted",
                       attrs={"pool": pool_id, "size": pool.size,
                              "epoch": pool.epoch, "term": term})
        sys.stderr.write(
            f"mpi_tpu.serve: server {self.server_id} adopted pool "
            f"{pool_id} ({pool.size} slots, epoch {pool.epoch}, "
            f"term {term})\n")
        return True

    def relinquish_pool(self, pool_id: str,
                        new_owner: Optional[str] = None) -> None:
        """The thawed-usurped path: the namespace says another server
        now owns this pool — stop serving it IMMEDIATELY.  Closing the
        worker control connections is the handover itself (a worker
        serves exactly one master at a time; EOF sends it to the
        namespace, where the usurper's record is), and every in-flight
        lease on the pool fails with a NAMED error, never a hang."""
        with self._cond:
            pool = self._pools.pop(pool_id, None)
            if pool is None:
                return
            if pool.home:
                self._relinquished_home_epoch = pool.epoch
            conns = []
            for key in [k for k in self._workers if k[0] == pool_id]:
                w = self._workers.pop(key)
                if w.conn is not None:
                    conns.append(w.conn)
                self._worker_pvars.pop(key, None)
            heal_procs = [self._healing.pop(k)["proc"]
                          for k in list(self._healing)
                          if k[0] == pool_id]
            for job in self._jobs.values():
                if job.get("pool") == pool_id and job["pending"]:
                    job["pending"].clear()
                    job["errors"].append({
                        "kind": "TransportError",
                        "msg": f"pool {pool_id} taken over by server "
                               f"{new_owner} (ownership moved "
                               f"mid-lease; re-acquire)",
                        "failed": [], "collective": None})
                    job["event"].set()
            for lease_id in [lid for lid, lease in self._leases.items()
                             if lease.get("pool") == pool_id]:
                self._leases.pop(lease_id)
            # queued acquires that can NEVER be satisfied by the
            # remaining pools must fail over NOW with the named
            # signal, not stall to a LeaseTimeout the federated
            # client treats as a live-server verdict
            cap = max((p.size for p in self._pools.values()),
                      default=0)
            for waiter in self._waiters:
                if waiter["nranks"] > cap:
                    waiter["lost"] = True
            self.stats_counters["pools_relinquished"] += 1
            self._cond.notify_all()
        for c in conns:
            try:
                # shutdown first: the worker side's reader thread is
                # blocked in recv(), which a bare close() never wakes
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        for p in heal_procs:
            try:
                p.kill()  # the usurper heals its own pool
            except OSError:
                pass
        rec_t = _telemetry.REC
        if rec_t is not None:
            rec_t.emit("serve", "pool_relinquished",
                       attrs={"pool": pool_id, "to": new_owner})
        sys.stderr.write(
            f"mpi_tpu.serve: server {self.server_id} relinquished pool "
            f"{pool_id} to {new_owner} (taken over while this server "
            f"was unresponsive)\n")

    def is_leader(self) -> bool:
        return self._fed is not None and self._fed.is_leader()

    # -- client side -------------------------------------------------------

    def _client_loop(self, conn: socket.socket, first: dict) -> None:
        lock = threading.Lock()
        owned: List[int] = []  # lease ids owned by this connection
        msg: Optional[dict] = first
        try:
            while msg is not None:
                try:
                    reply = self._client_op(msg, owned)
                except Exception as e:  # noqa: BLE001 - shipped back
                    reply = {"error": _pack_error(e)}
                try:
                    _send_msg(conn, lock, reply)
                except OSError:
                    break
                if msg.get("op") == "shutdown":
                    threading.Thread(target=self.stop,
                                     daemon=True).start()
                    break
                msg = _recv_msg(conn)
        finally:
            for lease_id in list(owned):
                self._release(lease_id)
            conn.close()

    def _client_op(self, msg: dict, owned: List[int]) -> dict:
        op = msg.get("op")
        if op == "acquire":
            return self._acquire(msg, owned)
        if op == "run":
            return self._run_job(msg)
        if op == "release":
            self._release(int(msg["lease_id"]))
            if int(msg["lease_id"]) in owned:
                owned.remove(int(msg["lease_id"]))
            return {"ok": True}
        if op == "stats":
            return {"ok": True, "stats": self.stats()}
        if op == "shutdown":
            return {"ok": True}
        return {"error": {"kind": "ValueError",
                          "msg": f"unknown op {op!r}"}}

    def _pick_idle_locked(self, nranks: int
                          ) -> Optional[Tuple[str, List[int]]]:
        """A pool with ``nranks`` idle slots (a lease never spans
        pools — they are different transport worlds).  BEST-FIT: the
        pool with the FEWEST idle slots that still satisfies (home as
        the tiebreak), so small leases are packed into small remnants
        and a large later request keeps an unfragmented pool to land
        on — most-idle-first would carve up exactly the pool a
        full-size lease needs."""
        best = None
        for pool_id, pool in self._pools.items():
            idle = sorted(s for (pid, s), w in self._workers.items()
                          if pid == pool_id and w.state == "idle")
            if len(idle) >= nranks:
                score = (len(idle), 0 if pool.home else 1, pool_id)
                if best is None or score < best[0]:
                    best = (score, pool_id, idle[:nranks])
        return None if best is None else (best[1], best[2])

    def _try_grant_locked(self, waiter: dict
                          ) -> Optional[Tuple[str, List[int]]]:
        """Grant ``waiter`` iff it is the first waiter in admission
        order (priority → fair share → FIFO) that the current idle
        capacity can satisfy."""
        for w in _admission_order(self._waiters, self._client_grants):
            pick = self._pick_idle_locked(w["nranks"])
            if pick is None:
                continue
            return pick if w is waiter else None
        return None

    def _acquire(self, msg: dict, owned: List[int]) -> dict:
        nranks = int(msg["nranks"])
        timeout = float(msg.get("timeout") or self.world_lease_timeout_s)
        client_id = str(msg.get("client") or "anon")
        priority = int(msg.get("priority") or 0)
        t_req = time.monotonic()
        deadline = t_req + timeout
        with self._cond:
            if self._fed is not None and self._store_fence \
                    and not self._fed.healthy():
                # ISSUE 18 admission fence: this server sits on the
                # MINORITY side of a namespace-store partition (or the
                # store group has no leader).  Granting a lease here
                # could double-serve a pool the majority is about to
                # reassign — refuse with the NAMED verdict instead;
                # FederatedClient treats it as a failover signal and
                # lands on a majority-side server.  In-flight leases
                # run to completion (reads and running jobs are not
                # gated); only NEW authority is refused.
                self.stats_counters["leases_denied"] += 1
                self.stats_counters["no_quorum_rejected"] += 1
                raise NoQuorumError(
                    f"server {self.server_id} has no namespace-store "
                    f"quorum (minority side of a partition): refusing "
                    f"new leases — fail over to a majority-side "
                    f"server")
            # under the lock: the federation thread mutates _pools
            # (adopt/relinquish) — iterating it bare would crash with
            # dict-changed-size exactly during a takeover, when failed-
            # over acquires flood the survivor
            cap = max((p.size for p in self._pools.values()), default=0)
            if cap == 0:
                # every pool relinquished (thawed fully-usurped
                # server): this endpoint cannot serve ANY lease — ship
                # the failover signal, not an argument error, so a
                # federated client moves to a survivor
                raise ServerLostError(
                    "server owns no pools (relinquished after a "
                    "takeover): fail over to a live owner")
            if nranks < 1 or nranks > cap:
                raise ValueError(
                    f"nranks must be in [1, {cap}] for this pool")
            was_full = len(self._waiters) >= self.max_pending
            self._seq += 1
            waiter = {"client": client_id, "priority": priority,
                      "nranks": nranks, "seq": self._seq}
            self._waiters.append(waiter)
            # work-conserving door: an arrival the CURRENT idle
            # capacity can satisfy (net of better-ranked waiters) is
            # granted immediately and never occupies a queue slot —
            # a full queue of unsatisfiable large requests must not
            # bounce small ones that idle workers could serve now
            grant = self._try_grant_locked(waiter)
            if grant is None and was_full:
                # bounded admission queue with a PRIORITY-AWARE door
                # (ISSUE 15): overload becomes an immediate named
                # rejection, not unbounded latency — but an arrival
                # that outranks the WORST waiter (priority, then fair
                # share, then FIFO: the same admission order) bumps it
                # instead, so a flood of low-priority acquires can
                # never lock a prioritized client out of a full queue.
                # Either way depth stays <= max_pending and every
                # rejection is a named ServerBusyError.
                self._waiters.remove(waiter)
                order = _admission_order(self._waiters,
                                         self._client_grants)
                worst = order[-1] if order else None
                cand_key = (-priority,
                            self._client_grants.get(client_id, 0),
                            waiter["seq"])
                worst_key = None if worst is None else (
                    -worst["priority"],
                    self._client_grants.get(worst["client"], 0),
                    worst["seq"])
                self.stats_counters["leases_denied"] += 1
                self.stats_counters["busy_rejected"] += 1
                if worst is None or worst_key <= cand_key:
                    raise ServerBusyError(
                        f"admission queue full ({self.max_pending} "
                        f"waiting acquires, capacity "
                        f"{sum(p.size for p in self._pools.values())} "
                        f"workers): back off or fail over")
                worst["bumped"] = True
                self._waiters.remove(worst)
                self._waiters.append(waiter)
                self._cond.notify_all()
            try:
                while grant is None:
                    if self._closing:
                        raise RuntimeError("server shutting down")
                    if waiter.get("lost"):
                        raise ServerLostError(
                            "the pool(s) that could have served this "
                            "acquire were relinquished to another "
                            "server: fail over to the new owner")
                    if waiter.get("bumped"):
                        raise ServerBusyError(
                            f"bumped from the full admission queue by "
                            f"a higher-ranked acquire "
                            f"({self.max_pending} waiting): back off "
                            f"or fail over")
                    grant = self._try_grant_locked(waiter)
                    if grant is not None:
                        break
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        idle = sum(1 for w in self._workers.values()
                                   if w.state == "idle")
                        self.stats_counters["leases_denied"] += 1
                        return {"error": {
                            "kind": "LeaseTimeout",
                            "msg": f"no {nranks} idle workers within "
                                   f"{timeout}s (pool {self.pool_size}, "
                                   f"idle {idle}, waiting "
                                   f"{len(self._waiters)})"}}
                    self._cond.wait(min(0.25, remaining))
            finally:
                if waiter in self._waiters:
                    self._waiters.remove(waiter)
            pool_id, slots = grant
            self._seq += 1
            lease_id = self._seq
            for s in slots:
                self._workers[(pool_id, s)].state = "leased"
                self._workers[(pool_id, s)].lease_id = lease_id
            epoch = self._pools[pool_id].epoch
            self._leases[lease_id] = {"slots": slots, "epoch": epoch,
                                      "pool": pool_id}
            self.stats_counters["leases_granted"] += 1
            # the fair-share ledger: whoever got this grant moves back
            # in the order among equals.  Bounded: an unbounded client-
            # uuid dict is a slow leak under connect()-churn, so reset
            # the baseline rather than grow without limit.
            self._client_grants[client_id] = \
                self._client_grants.get(client_id, 0) + 1
            if len(self._client_grants) > 4096:
                self._client_grants.clear()
        # lease-acquire latency distribution (ISSUE 13): always on —
        # the grant is a control round-trip, one histogram add is noise
        # (this is what the metrics endpoint's p50/p99 summarize)
        _mpit.hist_record("lease_acquire_s", time.monotonic() - t_req)
        rec = _telemetry.REC
        if rec is not None:
            rec.emit("lease", "grant",
                     attrs={"lease_id": lease_id, "slots": slots,
                            "pool": pool_id, "epoch": epoch})
        owned.append(lease_id)
        return {"ok": True, "lease_id": lease_id, "slots": slots,
                "epoch": epoch, "pool": pool_id}

    def _run_job(self, msg: dict) -> dict:
        lease_id = int(msg["lease_id"])
        timeout = float(msg.get("timeout") or self.world_lease_timeout_s)
        with self._cond:
            lease = self._leases.get(lease_id)
            if lease is None:
                raise ValueError(f"unknown lease {lease_id}")
            pool_id = lease.get("pool", self._home)
            slots = list(lease["slots"])
            dead = [s for s in slots
                    if self._workers[(pool_id, s)].state != "leased"
                    or self._workers[(pool_id, s)].lease_id != lease_id]
            self._seq += 1
            job_id = self._seq
            job = {"pending": set(slots) - set(dead), "errors": [],
                   "result": None, "event": threading.Event(),
                   "pool": pool_id}
            if dead:
                job["errors"].append({
                    "kind": "ProcFailedError",
                    "code": error_class(ProcFailedError("")),
                    "msg": f"leased worker slot(s) {dead} died before "
                           f"the job started",
                    "failed": dead, "collective": None})
            self._jobs[job_id] = job
            targets = [(self._workers[(pool_id, s)].conn,
                        self._workers[(pool_id, s)].send_lock)
                       for s in job["pending"]]
        if not job["pending"]:
            job["event"].set()
        for conn, lk in targets:
            try:
                _send_msg(conn, lk, {
                    "op": "job", "job_id": job_id, "slots": slots,
                    # the lease's epoch stamp: keys the pooled coll/sm
                    # arena identically on every leased worker
                    "epoch": lease.get("epoch", 0),
                    "fn": msg["fn"], "args": msg["args"]})
            except OSError:
                pass  # its death is noticed by the monitor and synthesized
        ok = job["event"].wait(timeout)
        with self._cond:
            self._jobs.pop(job_id, None)
            stuck = sorted(job["pending"])
            # pin the exact PROC OBJECTS (and, for adopted workers that
            # were never our children, the hello pid) while holding the
            # lock: a concurrent heal could install a healthy
            # replacement under the same slot, and signalling by slot
            # would dump/kill it
            stuck_procs = [(s, self._workers[(pool_id, s)].proc,
                            self._workers[(pool_id, s)].pid)
                           for s in stuck
                           if (pool_id, s) in self._workers]
        if not ok:
            # dump the unresponsive workers' stacks to their stderr
            # (faulthandler SIGUSR2 handler) for the diagnosis, then
            # QUARANTINE them by killing: a worker that blew the lease
            # timeout is still wedged in the old job (its job loop is
            # serial), and returning it to the idle pool on release
            # would poison every subsequent lease it joins — killed, it
            # takes the already-tested healing path and comes back as a
            # fresh replacement under the next epoch
            import signal as _signal

            for s, proc, pid in stuck_procs:
                target = None
                if proc is not None and proc.poll() is None:
                    target = proc.pid
                elif proc is None and pid \
                        and membership._pid_alive(pid):
                    target = pid
                if target is not None:
                    try:
                        os.kill(target, _signal.SIGUSR2)
                        time.sleep(0.1)  # let the dump reach stderr
                        os.kill(target, _signal.SIGKILL)
                    except OSError:
                        pass
            sys.stderr.write(
                f"mpi_tpu.serve: job {job_id} on lease {lease_id} "
                f"blew the {timeout}s lease timeout; quarantined "
                f"worker slots {stuck}\n")
            return {"error": {
                "kind": "LeaseTimeout",
                "msg": f"job on lease {lease_id} did not complete "
                       f"within {timeout}s (unresponsive worker "
                       f"slots {stuck}: stacks dumped to the server "
                       f"log, workers killed for pool healing)"}}
        if job["errors"]:
            self.stats_counters["jobs_failed"] += 1
            # the most diagnosable error wins: a named FT error over a
            # generic one
            errs = sorted(
                job["errors"],
                key=lambda e: 0 if e.get("kind") in _ERROR_KINDS else 1)
            # ISSUE 13 satellite: a lease failure is attributable in
            # the server log — job/lease id, error class, failed slots
            sys.stderr.write(
                f"mpi_tpu.serve: job {job_id} on lease {lease_id} "
                f"failed: {errs[0].get('kind')}: "
                f"{str(errs[0].get('msg', ''))[:200]} "
                f"(failed slots {errs[0].get('failed')})\n")
            return {"error": errs[0]}
        with self._cond:
            self.stats_counters["jobs_ok"] += 1
            sec = int(time.monotonic())
            self._ok_buckets[sec] = self._ok_buckets.get(sec, 0) + 1
            for k in [k for k in self._ok_buckets
                      if sec - k > _RATE_WINDOW_S]:
                del self._ok_buckets[k]
        return {"ok": True, "result": job["result"]}

    def _release(self, lease_id: int) -> None:
        with self._cond:
            lease = self._leases.pop(lease_id, None)
            if lease is None:
                return
            pool_id = lease.get("pool", self._home)
            for s in lease["slots"]:
                w = self._workers.get((pool_id, s))
                if w is not None and w.state == "leased" \
                        and w.lease_id == lease_id:
                    w.state = "idle"
                    w.lease_id = None
            self._cond.notify_all()

    def _worlds_per_s_locked(self, now: float) -> float:
        # worlds/s over the sliding window (completed jobs), the
        # gauge ROADMAP direction 1 asks for; uptime-bounded so a
        # young server reads its true rate, not a diluted one
        window = min(_RATE_WINDOW_S, max(1e-9, now - self._t0))
        recent = sum(c for sec, c in self._ok_buckets.items()
                     if now - sec <= _RATE_WINDOW_S)
        return round(recent / window, 3)

    def stats(self) -> dict:
        now = time.monotonic()
        with self._lock:
            # single-pool back-compat: "workers"/"epoch" describe the
            # HOME pool; "idle" counts every pool (a lease can land on
            # any); "pools" carries the per-pool detail
            states = {s: w.state for (pid, s), w in self._workers.items()
                      if pid == self._home}
            pools = {
                pid: {"home": p.home, "epoch": p.epoch, "size": p.size,
                      "workers": {s: w.state
                                  for (wp, s), w
                                  in self._workers.items()
                                  if wp == pid}}
                for pid, p in self._pools.items()}
            agg: Dict[str, int] = {}
            for snap in self._worker_pvars.values():
                for k, v in snap.items():
                    agg[k] = agg.get(k, 0) + int(v)
            out = {
                "addr": self.addr, "backend": self.backend,
                "pool_size": self.pool_size, "epoch": self.epoch,
                "workers": states,
                "idle": sum(1 for w in self._workers.values()
                            if w.state == "idle"),
                "healing": [f"{pid}:{s}"
                            for pid, s in sorted(self._healing)],
                "leases_active": len(self._leases),
                "uptime_s": round(now - self._t0, 3),
                "worlds_per_s": self._worlds_per_s_locked(now),
                "worker_pvars": agg,
                "metrics_addr": self.metrics_addr,
                "pools": pools,
                "waiting": len(self._waiters),
                "max_pending": self.max_pending,
                "server_id": self.server_id,
                **self.stats_counters,
            }
        # None (not False) outside a federation: a standalone server
        # must not scrape as a non-leader federation member
        out["is_leader"] = (self.is_leader() if self._fed is not None
                            else None)
        if self._fed_ns is not None:
            # namespace roll-up (store reads; deliberately OUTSIDE the
            # server lock): keeps the Prometheus endpoint truthful
            # when pools move between servers.  Through the MEMBER's
            # own store handle (not the spec) — a raft member serves
            # its local applied state instead of dialing itself
            from . import federation as _federation

            out["federation"] = _federation.federation_stats(
                self._fed.store if self._fed is not None
                else self._fed_ns)
            if self._fed is not None:
                out["store_healthy"] = self._fed.healthy()
        # lease-acquire quantiles from the histogram pvar (log-bucket
        # estimates — mpit.hist_quantile documents the error bound)
        for q, label in ((0.5, "p50"), (0.99, "p99")):
            est = _mpit.hist_quantile("lease_acquire_s", q)
            out[f"lease_acquire_{label}_ms"] = (
                None if est is None else round(est * 1e3, 3))
        return out


# -- the client ---------------------------------------------------------------


class WorldLease:
    """A leased world: run jobs on it, release it when done."""

    def __init__(self, client: "ServerClient", lease_id: int,
                 slots: List[int], epoch: int,
                 pool: Optional[str] = None) -> None:
        self._client = client
        self.lease_id = lease_id
        self.slots = list(slots)
        self.epoch = int(epoch)
        self.pool = pool  # which pool served it (federation takeovers)
        self._released = False

    @property
    def size(self) -> int:
        return len(self.slots)

    def run(self, fn, *args: Any, timeout: Optional[float] = None) -> Any:
        """Execute ``fn(comm, *args)`` on every leased worker (``fn``
        pickled by reference — workers must be able to import it);
        returns lease-rank 0's return value.  Raises the worker-side
        error BY NAME (ProcFailedError & co.) on any failure."""
        reply = self._client._request({
            "op": "run", "lease_id": self.lease_id,
            "fn": pickle.dumps(fn, protocol=pickle.HIGHEST_PROTOCOL),
            "args": pickle.dumps(args, protocol=pickle.HIGHEST_PROTOCOL),
            "timeout": timeout})
        blob = reply.get("result")
        return pickle.loads(blob) if blob is not None else None

    def release(self) -> None:
        if not self._released:
            self._released = True
            self._client._request({"op": "release",
                                   "lease_id": self.lease_id})

    def __enter__(self) -> "WorldLease":
        return self

    def __exit__(self, *exc) -> None:
        try:
            self.release()
        except (TransportError, OSError):
            pass  # server gone: the lease died with it (and a release
            # failure must never mask the body's real exception)


class ServerClient:
    """Client handle to a resident world server (see :func:`connect`).

    The initial connect retries the TRANSIENT dial failures
    (ConnectionRefusedError AND a connect timeout — ISSUE 15 satellite;
    mpi_tpu/resilience.py TRANSIENT_DIAL_ERRORS) with exponential
    backoff + jitter for up to the ``connect_retry_timeout_s`` mpit
    cvar: a freshly-spawned server (``launcher serve --addr-file``
    races its own bind) and a just-elected federation survivor look
    exactly like a refused/absorbed connection.  Any other failure — or
    one that outlives the budget — raises as before.

    A connection that dies MID-REQUEST raises :class:`ServerLostError`
    (a named TransportError subclass): the server process itself is
    gone, which is what a federated client fails over on."""

    def __init__(self, host: str, port: int, timeout: float = 30.0,
                 priority: int = 0, client_id: Optional[str] = None,
                 dial_retry_s: Optional[float] = None) -> None:
        from .resilience import retry_connect

        self._sock = retry_connect(
            lambda: socket.create_connection((host, port),
                                             timeout=timeout),
            timeout_s=dial_retry_s)
        self._sock.settimeout(None)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._lock = threading.Lock()  # one request/response in flight
        # fair-share identity + default priority (ISSUE 15): the server
        # schedules waiting acquires by (priority, grants-per-client,
        # FIFO) — one uuid per client handle is the ledger key
        self._id = client_id or uuid.uuid4().hex
        self.priority = int(priority)

    def _request(self, msg: dict) -> dict:
        # Bound the reply wait when the caller bounded the op: the
        # server enforces msg["timeout"] itself (acquire/run clamp to
        # world_lease_timeout_s), so a live server's reply — grant,
        # TimeoutError verdict, or any named error — must land within
        # it plus slack.  Without this a SIGSTOP-frozen server (socket
        # ESTABLISHED in the kernel, no reply, no EOF — the PR-15
        # frozen-master class) wedges the client in recv forever; with
        # it the stall surfaces as ServerLostError, which is exactly
        # what a federated client fails over on.  timeout-less ops
        # (stats, release) keep the blocking-read semantics.
        t = msg.get("timeout")
        with self._lock:
            try:
                self._sock.settimeout(float(t) + _RPC_GRACE_S
                                      if t else None)
                _send_msg(self._sock, None, msg)
                reply = _recv_msg(self._sock)
            except OSError as e:
                raise ServerLostError(
                    f"world server connection lost mid-request: "
                    f"{type(e).__name__}: {e}") from e
            finally:
                try:
                    self._sock.settimeout(None)
                except OSError:
                    pass  # socket already dead: the raise above rules
        if reply is None:
            raise ServerLostError("world server closed the connection")
        if "error" in reply:
            _raise_error(reply["error"])
        return reply

    def acquire(self, nranks: int, timeout: Optional[float] = None,
                priority: Optional[int] = None) -> WorldLease:
        """Lease ``nranks`` warm workers as a world: ONE round-trip (the
        server reserves idle slots; no fork, no handshake).  Raises
        TimeoutError when the pool cannot supply them in time, and
        ServerBusyError when the admission queue is at its bound."""
        reply = self._request({
            "op": "acquire", "nranks": int(nranks), "timeout": timeout,
            "client": self._id,
            "priority": self.priority if priority is None
            else int(priority)})
        return WorldLease(self, reply["lease_id"], reply["slots"],
                          reply["epoch"], pool=reply.get("pool"))

    def run(self, fn, *args: Any, nranks: int = 2,
            timeout: Optional[float] = None) -> Any:
        """acquire + run + release in one call (the simple path)."""
        lease = self.acquire(nranks, timeout=timeout)
        try:
            return lease.run(fn, *args, timeout=timeout)
        finally:
            try:
                lease.release()
            except (TransportError, OSError):
                pass  # server gone: must not mask run()'s real error

    def stats(self) -> dict:
        return self._request({"op": "stats"})["stats"]

    def shutdown(self) -> None:
        """Ask the server process to stop (admin surface)."""
        try:
            self._request({"op": "shutdown"})
        except (TransportError, OSError):
            pass

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass

    def __enter__(self) -> "ServerClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _parse_hostport(text: str) -> Optional[Tuple[str, int]]:
    host, _, port = text.rpartition(":")
    if not host:
        return None
    try:
        return host, int(port)
    except ValueError:
        return None


def _resolve_addr_file(path: str) -> Tuple[str, int]:
    """Resolve a ``serve --addr-file`` path to (host, port), retrying a
    MISSING or PARTIALLY-WRITTEN file with backoff for up to the
    ``connect_retry_timeout_s`` budget (ISSUE 15 satellite): a
    just-started — or just-elected — server publishing its record
    loses the race against an eager client routinely, and that is the
    same transient the refused-dial retry already heals.  A file that
    never appears (or never parses) within the budget raises a named
    TransportError; budget 0 keeps first-failure raise."""
    from .resilience import backoff_delays

    budget = float(_mpit.cvar_read("connect_retry_timeout_s"))
    deadline = time.monotonic() + budget
    delays = backoff_delays()
    while True:
        content = ""
        try:
            with open(path) as f:
                content = f.read().strip()
        except OSError:
            pass
        got = _parse_hostport(content) if content else None
        if got is not None:
            return got
        if time.monotonic() > deadline:
            raise TransportError(
                f"server address file {path!r} was not published as a "
                f"parseable host:port within {budget}s "
                f"(content {content[:40]!r})")
        time.sleep(min(next(delays), 0.25))


def connect(addr: Any, timeout: float = 30.0, priority: int = 0):
    """Connect to a resident world server — or a FEDERATION of them.

    ``addr`` is one of:

    * ``"host:port"``, a ``(host, port)`` tuple, or an in-process
      :class:`WorldServer` → a plain :class:`ServerClient`;
    * a path to a file containing ``host:port`` (the launcher's
      ``serve --addr-file``) → a :class:`ServerClient`; a missing or
      partially-written file is retried within the
      ``connect_retry_timeout_s`` budget;
    * a path to a DIRECTORY (a ``serve --federation`` namespace) or a
      list of ``"host:port"`` strings → a
      :class:`~mpi_tpu.federation.FederatedClient` that resolves live
      servers and fails acquire/stats over on server death."""
    if isinstance(addr, WorldServer):
        addr = addr.addr
    if isinstance(addr, (tuple, list)):
        # a server LIST only when every element is a "host:port"
        # string; anything else — including the legacy (host, port)
        # tuple whose port arrived as a string ("8080" has no colon) —
        # keeps the single-server meaning
        if addr and all(isinstance(a, str) and ":" in a for a in addr):
            from . import federation as _federation

            return _federation.FederatedClient(
                addrs=list(addr), timeout=timeout, priority=priority)
        host, port = addr[0], int(addr[1])
        return ServerClient(host, port, timeout=timeout,
                            priority=priority)
    text = str(addr)
    if os.path.isdir(text) or text.startswith("raft:"):
        from . import federation as _federation

        return _federation.FederatedClient(
            namespace=text, timeout=timeout, priority=priority)
    direct = None if os.path.exists(text) else _parse_hostport(text)
    if direct is not None:
        host, port = direct
    elif os.path.exists(text) or os.sep in text:
        # an existing file, or a PATH-shaped string that must be a
        # yet-to-be-published addr file: poll it within the budget
        host, port = _resolve_addr_file(text)
    else:
        # neither host:port nor path-shaped: a typo deserves an
        # immediate diagnostic, not a silent poll of the full budget
        raise ValueError(
            f"connect: {text!r} is neither a host:port address nor a "
            f"path to an addr file / federation namespace")
    return ServerClient(host, port, timeout=timeout, priority=priority)


# -- CLI ----------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "--worker":
        return _worker_main()
    ap = argparse.ArgumentParser(
        prog="mpi_tpu.launcher serve",
        description="resident world server: pool warm workers, lease "
                    "worlds to clients, self-heal under kill injection")
    ap.add_argument("--pool-size", type=int, default=_POOL_SIZE)
    ap.add_argument("--backend", choices=("socket", "shm"),
                    default="socket")
    ap.add_argument("--host", default=_HOST)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--addr-file", default=None,
                    help="write host:port here once listening "
                         "(clients: mpi_tpu.connect(path))")
    ap.add_argument("--detect-timeout", type=float,
                    default=_DETECT_TIMEOUT_S,
                    help="pool-internal ULFM detection bound (s)")
    ap.add_argument("--heartbeat", type=float, default=_HEARTBEAT_S)
    ap.add_argument("--lease-timeout", type=float,
                    default=_WORLD_LEASE_TIMEOUT_S,
                    help="world_lease_timeout_s: max wait for idle "
                         "workers / default job bound")
    ap.add_argument("--rejoin-timeout", type=float,
                    default=_REJOIN_TIMEOUT_S,
                    help="rejoin_timeout_s of one healing handshake")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve GET /metrics (Prometheus text format: "
                         "worlds/s, lease p50/p99, pool epoch, per-"
                         "worker health, aggregated worker pvars) on "
                         "this HTTP port; 0 binds an ephemeral port "
                         "(printed at startup)")
    ap.add_argument("--federation", default=None, metavar="SPEC",
                    help="join a federation namespace "
                         "(mpi_tpu/federation.py): a shared DIR "
                         "(FileStore — single host/NFS), or "
                         "raft:<idx>@h0:p0,h1:p1,... to embed store "
                         "node <idx> of a replicated quorum group "
                         "(mpi_tpu/federation_store.py — N hosts, no "
                         "shared FS; a partitioned minority refuses "
                         "leases with NoQuorumError).  N servers share "
                         "endpoint records + a CAS leader lease; a "
                         "dead server's pool is adopted by a survivor "
                         "and its workers re-register there; clients "
                         "connect(DIR | raft:h0:p0,...) and fail over")
    ap.add_argument("--server-id", default=None,
                    help="federation identity (default: random "
                         "srv-<hex8>)")
    ap.add_argument("--fed-lease-timeout", type=float,
                    default=_FED_LEASE_TIMEOUT_S, metavar="S",
                    help="leader-lease takeover bound; authority "
                         "self-expires at half this (the split-brain "
                         "safety margin)")
    ap.add_argument("--max-pending", type=int, default=_MAX_PENDING,
                    help="bounded admission queue depth: acquires "
                         "beyond this many waiters are rejected with "
                         "ServerBusyError instead of queueing "
                         "unboundedly")
    ap.add_argument("--orphan-timeout", type=float,
                    default=_ORPHAN_TIMEOUT_S, metavar="S",
                    help="how long an orphaned worker polls the "
                         "federation namespace for its pool's new "
                         "owner before exiting")
    args = ap.parse_args(argv)
    server = WorldServer(
        pool_size=args.pool_size, backend=args.backend, host=args.host,
        port=args.port, detect_timeout_s=args.detect_timeout,
        heartbeat_s=args.heartbeat,
        world_lease_timeout_s=args.lease_timeout,
        rejoin_timeout_s=args.rejoin_timeout,
        metrics_port=args.metrics_port,
        federation=args.federation, server_id=args.server_id,
        fed_lease_timeout_s=args.fed_lease_timeout,
        max_pending=args.max_pending,
        orphan_timeout_s=args.orphan_timeout)
    server.start()
    print(f"mpi_tpu serve: listening on {server.addr} "
          f"(pool {args.pool_size} x {args.backend})", flush=True)
    if args.federation:
        print(f"mpi_tpu serve: federation member {server.server_id} "
              f"in {args.federation}", flush=True)
    if server.metrics_addr:
        print(f"mpi_tpu serve: metrics on "
              f"http://{server.metrics_addr}/metrics", flush=True)
    if args.addr_file:
        tmp = args.addr_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(server.addr)
        os.replace(tmp, args.addr_file)
    try:
        while not server._closing:
            time.sleep(0.25)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
