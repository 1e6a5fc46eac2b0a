"""Multi-host (DCN) support: COMM_WORLD over every host's devices.

On a TPU pod each host owns a subset of the chips; one process runs per
host, ``jax.distributed.initialize()`` wires them into one runtime, and a
``Mesh`` over ``jax.devices()`` (the GLOBAL device list) makes every
mpi_tpu communicator span hosts transparently — ``shard_map`` collectives
over a mesh axis compile to ICI transfers inside a host/slice and DCN
transfers across them.  Nothing in TpuCommunicator changes: the plugin
seam (SURVEY.md §1 L2/L1) absorbs the scale-out exactly as the north-star
demands.

Axis-layout guidance (the scaling-book recipe): put axes that carry the
heavy, latency-sensitive collectives (tensor/sequence parallel) on ICI —
the *inner* mesh dims — and bandwidth-tolerant axes (data/pipeline
parallel) on DCN — the *outer* dims.  ``hybrid_mesh`` builds exactly that
split from per-slice and cross-slice shapes.

Simulated multi-host on one machine: ``python -m mpi_tpu.tpu.multihost
-n 2 --devices-per-host 2 script.py`` spawns one clean CPU process per
"host" (gloo cross-process collectives — jax's real multi-process runtime,
the same code path a DCN pod exercises, minus the wires).  Inside the
script, ``auto_init()`` + ``global_mesh()`` are all that is needed; the
same two calls are correct unchanged on a real pod.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import numpy as np

ENV_COORD = "MPI_TPU_COORD"
ENV_NPROCS = "MPI_TPU_NPROCS"
ENV_PROC_ID = "MPI_TPU_PROC_ID"


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Join the multi-process jax runtime (idempotent).

    On a real TPU pod all arguments are discovered from the environment —
    call with none.  On CPU (simulated hosts) pass coordinator/n/id, and
    cross-process collectives go through gloo."""
    import jax

    # N.B. nothing here may touch the backend (jax.devices/process_count/
    # default_backend all initialize it, and distributed init must come
    # first); decide the platform from config/env only.
    plat = (jax.config.jax_platforms or
            os.environ.get("JAX_PLATFORMS", ""))
    if "cpu" in plat.split(","):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(coordinator_address=coordinator_address,
                      num_processes=num_processes, process_id=process_id)
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:  # second call in one process: keep the first
        if "already" not in str(e):
            raise


def auto_init() -> bool:
    """``init_distributed`` from the env the simulated-host launcher sets
    (no-op when absent → single-host).  Returns True iff multi-process."""
    coord = os.environ.get(ENV_COORD)
    if not coord:
        return False
    init_distributed(coord, int(os.environ[ENV_NPROCS]),
                     int(os.environ[ENV_PROC_ID]))
    return True


def global_mesh(axis_name: str = "world"):
    """1-D Mesh over ALL hosts' devices (jax.devices() is global after
    ``init_distributed``) — MPI_COMM_WORLD for the whole pod."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), (axis_name,))


def hybrid_mesh(ici_shape: Sequence[int], dcn_shape: Sequence[int],
                axis_names: Tuple[str, ...]):
    """ICI×DCN mesh: ``ici_shape`` partitions each slice's devices (inner,
    fast), ``dcn_shape`` spans slices (outer, over the data-center
    network).  Heavy collectives belong on the ici axes."""
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if len(ici_shape) != len(dcn_shape) or len(ici_shape) != len(axis_names):
        raise ValueError(
            f"ici_shape {ici_shape}, dcn_shape {dcn_shape} and axis_names "
            f"{axis_names} must have one entry per mesh axis")
    if all(d == 1 for d in dcn_shape):
        # single slice/host: plain device mesh (hybrid helper requires >1
        # granule); same layout contract
        devs = mesh_utils.create_device_mesh(tuple(ici_shape),
                                             devices=jax.devices())
        return Mesh(devs, axis_names)
    # Multi-slice TPU devices carry distinct slice_index values (the DCN
    # granule).  CPU/sim devices all report slice 0, so there the process
    # is the granule — one simulated host == one DCN endpoint (matches
    # launch_sim_hosts' model).
    all_devs = jax.devices()
    slice_ids = {getattr(d, "slice_index", 0) for d in all_devs}
    devs = mesh_utils.create_hybrid_device_mesh(
        tuple(ici_shape), tuple(dcn_shape), devices=all_devs,
        process_is_granule=len(slice_ids) <= 1)
    return Mesh(devs, axis_names)


# ---- simulated-host launcher ---------------------------------------------


def launch_sim_hosts(nhosts: int, argv: Sequence[str],
                     devices_per_host: int = 2,
                     timeout: Optional[float] = None) -> int:
    """Spawn ``nhosts`` clean CPU processes running ``python argv...``,
    wired into one jax runtime (the user script calls ``auto_init()``).
    Returns the first nonzero exit code, else 0."""
    import socket
    import subprocess
    import sys
    import time

    with socket.socket() as s:  # free port for the coordinator
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    # a CPU environment whose PYTHONPATH is the directory this mpi_tpu
    # checkout lives in, so worker scripts can `import mpi_tpu` without
    # installing the package
    env = dict(os.environ)
    pkg_parent = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = pkg_parent
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_host}")
    env[ENV_COORD] = f"127.0.0.1:{port}"
    env[ENV_NPROCS] = str(nhosts)

    procs = []
    for pid in range(nhosts):
        penv = dict(env)
        penv[ENV_PROC_ID] = str(pid)
        procs.append(subprocess.Popen([sys.executable, *argv], env=penv))
    deadline = None if timeout is None else time.monotonic() + timeout
    try:
        while True:
            codes = [p.poll() for p in procs]
            bad = [c for c in codes if c not in (None, 0)]
            if bad:
                return bad[0]
            if all(c == 0 for c in codes):
                return 0
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(f"hosts still running after {timeout}s")
            time.sleep(0.02)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="mpi_tpu.tpu.multihost",
        description="simulated multi-host launcher (one CPU process per "
                    "'host', gloo cross-process collectives)")
    parser.add_argument("-n", "--hosts", type=int, required=True)
    parser.add_argument("--devices-per-host", type=int, default=2)
    parser.add_argument("--timeout", type=float, default=None)
    parser.add_argument("script")
    parser.add_argument("script_args", nargs="*")
    args = parser.parse_args(argv)
    return launch_sim_hosts(args.hosts, [args.script, *args.script_args],
                            devices_per_host=args.devices_per_host,
                            timeout=args.timeout)


if __name__ == "__main__":
    import sys

    sys.exit(main())
