"""mpirun-alike launcher (SURVEY.md §2 component #1).

Spawns N rank processes of a user script, assigns ranks 0..N-1 via
environment, hands them a file-based rendezvous directory for port exchange
(see transport/socket.py), propagates the first nonzero exit code, and
kills the remaining ranks on failure — the L0 contract of SURVEY.md §1.

Usage::

    python -m mpi_tpu.launcher -n 4 examples/pi.py [script args...]
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional, Sequence

ENV_RANK = "MPI_TPU_RANK"
ENV_SIZE = "MPI_TPU_SIZE"
ENV_RDV = "MPI_TPU_RDV"
ENV_BACKEND = "MPI_TPU_BACKEND"


def launch(
    nranks: int,
    argv: Sequence[str],
    env_extra: Optional[dict] = None,
    timeout: Optional[float] = None,
    backend: Optional[str] = None,
    restarts: int = 0,
) -> int:
    """Run ``python argv...`` as ``nranks`` rank processes; return exit code.

    ``backend`` picks the rank transport ('socket' or 'shm'); default is the
    MPI_TPU_BACKEND env var, then 'socket'.

    ``restarts``: the elastic-recovery knob (SURVEY.md §5 failure story) —
    after a nonzero exit or a hang (timeout), the WHOLE world is killed and
    relaunched up to this many times.  Paired with crash-safe checkpoints
    (mpi_tpu.checkpoint: generation-committed save/load), a rank program
    that reloads its last checkpoint at startup resumes where the crashed
    attempt left off — the same restart-from-checkpoint model a TPU slice
    preemption needs.  MPI_TPU_ATTEMPT carries the attempt number to the
    ranks."""
    last = 0
    for attempt in range(restarts + 1):
        extra = dict(env_extra or {})
        extra["MPI_TPU_ATTEMPT"] = str(attempt)
        try:
            last = _launch_once(nranks, argv, extra, timeout, backend)
        except TimeoutError:
            if attempt == restarts:
                raise
            continue
        if last == 0:
            return 0
    return last


def cpu_pinned_env(env: dict, want: Optional[str] = None,
                   nprocs: int = 1) -> dict:
    """Pin a child rank's jax to CPU (in place) unless ``want`` (or the
    env's ``MPI_TPU_RANK_JAX_PLATFORMS``) names another platform.  The
    ONE shared helper for the launcher, comm_spawn and the serve pool.

    A chip belongs to one process at a time, so ``nprocs`` ranks on an
    accelerator platform would contend for it: that raises before any
    child is spawned.  Several chips are driven from ONE process
    (``run_spmd`` / ``TpuCommunicator`` over a mesh)."""
    want = want or env.pop("MPI_TPU_RANK_JAX_PLATFORMS", None) or "cpu"
    if want != "cpu" and nprocs > 1:
        raise ValueError(
            f"MPI_TPU_RANK_JAX_PLATFORMS={want!r} with {nprocs} rank "
            f"processes: a chip belongs to one process, so {nprocs} ranks "
            f"cannot share it.  Drive the chips from one process with "
            f"backend='tpu' (run_spmd over a mesh), or run one rank.")
    env["JAX_PLATFORMS"] = want
    return env


def _launch_once(
    nranks: int,
    argv: Sequence[str],
    env_extra: Optional[dict] = None,
    timeout: Optional[float] = None,
    backend: Optional[str] = None,
) -> int:
    if nranks < 1:
        raise ValueError(f"nranks must be >= 1, got {nranks}")
    backend = backend or os.environ.get(ENV_BACKEND, "socket")
    if backend == "shm":
        # compile the native ring once, up front, instead of N ranks racing
        # to the flock at import time
        from .native import ensure_built

        ensure_built()
    # the rendezvous dir is the membership service's root (port/
    # readiness/heartbeat/incarnation/claim files — mpi_tpu/membership)
    from . import membership

    rdv = membership.new_rendezvous_dir()
    procs: List[subprocess.Popen] = []
    try:
        for r in range(nranks):
            env = dict(os.environ)
            # the escape hatch may arrive via env_extra OR the caller's
            # environment — honor both before pinning
            want = (env_extra or {}).get("MPI_TPU_RANK_JAX_PLATFORMS")
            cpu_pinned_env(env, want, nranks)
            env.update(
                {
                    ENV_RANK: str(r),
                    ENV_SIZE: str(nranks),
                    ENV_RDV: rdv,
                    ENV_BACKEND: backend,
                }
            )
            if env_extra:
                env.update(env_extra)
            procs.append(subprocess.Popen([sys.executable, *argv], env=env))
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            codes = [p.poll() for p in procs]
            bad = [c for c in codes if c not in (None, 0)]
            if bad:
                _kill_all(procs)
                sys.stderr.write(_exit_summary(procs))
                return bad[0]
            if all(c == 0 for c in codes):
                return 0
            if deadline is not None and time.monotonic() > deadline:
                _kill_all(procs)
                sys.stderr.write(_exit_summary(procs))
                raise TimeoutError(f"ranks still running after {timeout}s")
            time.sleep(0.02)
    finally:
        _kill_all(procs)
        membership.cleanup_rendezvous(rdv)


def _kill_all(procs: List[subprocess.Popen]) -> None:
    """TERM → bounded wait → KILL → reap.  The escalation matters: a rank
    wedged in native code (shm ring memcpy, a jammed jax runtime) ignores
    SIGTERM, and a launcher that only TERMs leaves it holding /dev/shm
    segments and the TPU lock.  The final wait reaps the KILLed zombies
    so the exit summary below reports real wait statuses, not None."""
    for p in procs:
        if p.poll() is None:
            p.terminate()
    deadline = time.monotonic() + 5.0
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.send_signal(signal.SIGKILL)
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(2.0)
            except subprocess.TimeoutExpired:  # pragma: no cover - kernel
                pass  # unkillable (D-state); the summary reports it


def _exit_summary(procs: List[subprocess.Popen]) -> str:
    """Per-rank outcome table, printed on any non-zero outcome so a
    failure-story log is diagnosable without spelunking: WHICH rank died
    first-order (its own exit code / signal) vs which were merely killed
    by the launcher's TERM→KILL escalation."""
    lines = ["mpi_tpu.launcher: per-rank exit summary:"]
    for r, p in enumerate(procs):
        code = p.poll()
        if code is None:
            what = "still running (unkillable?)"
        elif code < 0:
            try:
                name = signal.Signals(-code).name
            except ValueError:
                name = f"signal {-code}"
            what = f"killed by {name}"
        else:
            what = f"exit code {code}"
        lines.append(f"  rank {r}: {what}")
    return "\n".join(lines) + "\n"


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "serve":
        # resident world server (mpi_tpu/serve.py): pools warm worker
        # processes and leases worlds to clients in one round-trip;
        # dead workers are shrunk out and replaced under a fresh
        # membership epoch.  `python -m mpi_tpu.launcher serve --help`
        from . import serve

        return serve.main(argv[1:])
    parser = argparse.ArgumentParser(
        prog="mpi_tpu.launcher",
        description="mpirun-alike launcher for mpi_tpu (or "
                    "'... launcher serve' for the resident world server)"
    )
    parser.add_argument("-n", "--np", type=int, required=True, dest="nranks",
                        help="number of rank processes")
    parser.add_argument("--timeout", type=float, default=None,
                        help="kill all ranks after this many seconds")
    parser.add_argument("--backend", choices=("socket", "shm"), default=None,
                        help="rank transport (default: MPI_TPU_BACKEND or socket)")
    parser.add_argument("--restarts", type=int, default=0,
                        help="relaunch the world up to N times after a "
                             "crash/hang (resume from checkpoints)")
    parser.add_argument("--verify", action="store_true",
                        help="enable the runtime correctness verifier "
                             "(MPI_TPU_VERIFY=1 on every rank): deadlock "
                             "detection, collective-matching signatures, "
                             "request lints — see mpi_tpu/verify")
    parser.add_argument("--progress", choices=("none", "thread"),
                        default=None,
                        help="async progress mode for every rank "
                             "(MPI_TPU_PROGRESS): 'thread' starts one "
                             "dedicated progress engine per rank — "
                             "background completion for nonblocking ops "
                             "(mpi_tpu/progress.py)")
    parser.add_argument("--link-retry-timeout", type=float, default=None,
                        metavar="S",
                        help="socket link-healing budget for every rank "
                             "(MPI_TPU_LINK_RETRY_S -> the "
                             "link_retry_timeout_s cvar): a send-path "
                             "OSError whose peer is not failure-"
                             "suspected reconnects with backoff for up "
                             "to this many seconds, replaying unacked "
                             "frames (mpi_tpu/resilience.py).  Keep it "
                             "below fault_detect_timeout_s; 0 disables "
                             "healing (every link fault terminal)")
    parser.add_argument("--trace-dir", default=None, metavar="DIR",
                        help="enable the flight recorder on every rank "
                             "(MPI_TPU_TRACE=1, mpi_tpu/telemetry) and "
                             "export one Chrome-trace/Perfetto JSON per "
                             "rank into DIR at exit; merge them onto "
                             "one aligned timeline with "
                             "tools/tracecat.py DIR -o merged.json")
    parser.add_argument("--tuning-table", default=None, metavar="PATH",
                        help="per-machine tuned-dispatch table for every "
                             "rank (MPI_TPU_TUNING_TABLE): measured "
                             "(transport, nranks, collective, payload-"
                             "band) -> algorithm rows that "
                             "algorithm='auto' consults before the "
                             "built-in constants (mpi_tpu/tuning; "
                             "generate with tools/tune.py)")
    parser.add_argument("script", help="python script to run on every rank")
    parser.add_argument("script_args", nargs=argparse.REMAINDER,
                        help="arguments passed to the script")
    args = parser.parse_args(argv)
    env_extra = {}
    if args.verify:
        env_extra["MPI_TPU_VERIFY"] = "1"
    if args.progress is not None:
        env_extra["MPI_TPU_PROGRESS"] = args.progress
    if args.link_retry_timeout is not None:
        env_extra["MPI_TPU_LINK_RETRY_S"] = str(args.link_retry_timeout)
    if args.trace_dir is not None:
        env_extra["MPI_TPU_TRACE"] = "1"
        env_extra["MPI_TPU_TRACE_DIR"] = os.path.abspath(args.trace_dir)
    if args.tuning_table is not None:
        env_extra["MPI_TPU_TUNING_TABLE"] = os.path.abspath(
            args.tuning_table)
    return launch(args.nranks, [args.script, *args.script_args],
                  env_extra=env_extra or None,
                  timeout=args.timeout, backend=args.backend,
                  restarts=args.restarts)


if __name__ == "__main__":
    sys.exit(main())
