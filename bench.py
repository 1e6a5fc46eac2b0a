#!/usr/bin/env python
"""Headline benchmark — prints ONE JSON line.

Measures the reference's config #1 (BASELINE.json:7: MPI_Allreduce(SUM) on
1K float32, 2 ranks) on BOTH transports on this host, same algorithm
(recursive halving), and reports the transport-swap speedup — the quantity
the north-star is about (socket/pickle path vs XLA-collective path):

* socket backend: 2 real rank processes over loopback TCP (the reference's
  architecture), p50 of 200 allreduce calls;
* SPMD backend: the same allreduce as one jitted shard_map program over 2
  devices, p50 of 200 dispatches.

On a host with >= 2 real TPU chips the SPMD leg runs over ICI and a second
north-star measurement (256 MB ring-allreduce bus-bandwidth, BASELINE.json:5)
is attempted; with one chip the SPMD leg uses 2 virtual CPU devices — an
apples-to-apples same-host comparison.  Details land in BENCH_DETAILS.json.
"""

import json
import os
import statistics
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.abspath(__file__))

SOCKET_PROG = """
import os, sys, time, statistics
sys.path.insert(0, {repo!r})
import numpy as np
import mpi_tpu

# pin ranks to distinct cores when the box has them (VERDICT r4 next #7:
# the socket leg's cross-run spread is scheduler contention); on a
# 1-core host this is a no-op and the min-of-N samples carry the story
ncpu = os.cpu_count() or 1
if ncpu >= 2 and hasattr(os, "sched_setaffinity"):  # Linux only
    try:
        os.sched_setaffinity(
            0, {{int(os.environ.get("MPI_TPU_RANK", 0)) % ncpu}})
    except OSError:
        pass

comm = mpi_tpu.init()
x = np.ones(1024, np.float32)
for _ in range(20):
    comm.allreduce(x, algorithm="recursive_halving")
ts = []
for _ in range(200):
    t0 = time.perf_counter()
    comm.allreduce(x, algorithm="recursive_halving")
    ts.append(time.perf_counter() - t0)
if comm.rank == 0:
    with open(os.environ["BENCH_OUT"], "w") as f:
        f.write(str(statistics.median(ts) * 1e6))
mpi_tpu.finalize()
"""

SPMD_PROG = """
import os, sys, time, statistics
sys.path.insert(0, {repo!r})
import jax
if {force_cpu!r} == "yes":
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from mpi_tpu.tpu import TpuCommunicator, default_mesh, enable_compile_cache

if jax.devices()[0].platform == "tpu":
    enable_compile_cache()
mesh = default_mesh(2)
comm = TpuCommunicator("world", mesh)
f = jax.jit(jax.shard_map(
    lambda x: comm.allreduce(x, algorithm="recursive_halving"),
    mesh=mesh, in_specs=P(), out_specs=P("world")))
# operand committed to its sharding up front, like any steady-state SPMD
# program's data — an uncommitted array pays per-call placement logic
# (~80us/call of pure dispatch overhead on this host, measured r3)
x = jax.device_put(jnp.ones(1024, jnp.float32), NamedSharding(mesh, P()))
f(x).block_until_ready()
ts = []
for _ in range(200):
    t0 = time.perf_counter()
    f(x).block_until_ready()
    ts.append(time.perf_counter() - t0)
with open(os.environ["BENCH_OUT"], "w") as fh:
    fh.write(str(statistics.median(ts) * 1e6))
"""

NORTHSTAR_PROG = """
import os, sys, time, statistics
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp, json
from jax.sharding import NamedSharding, PartitionSpec as P
from mpi_tpu.tpu import TpuCommunicator, default_mesh, enable_compile_cache

if jax.devices()[0].platform == "tpu":
    enable_compile_cache()
mesh = default_mesh()
P_ = len(jax.devices())
comm = TpuCommunicator("world", mesh)
# per-rank buffer size: 256MB on hardware, reduced in CPU-sim rehearsal
nbytes = int(os.environ.get("NS_BYTES", 256 * 1024 * 1024))
iters = int(os.environ.get("NS_ITERS", 10))
n = nbytes // 4
result = {{"nranks": P_, "nbytes": nbytes,
           "platform": jax.devices()[0].platform}}

# ICI line-rate probe: a saturating pure-ppermute ring of the same
# per-device payload — the denominator of the >=80%-of-line-rate
# north-star (BASELINE.json:5; SURVEY.md section 6)
try:
    ring_pairs = [(i, (i + 1) % P_) for i in range(P_)]
    probe = jax.jit(jax.shard_map(
        lambda x: jax.lax.ppermute(x, "world", ring_pairs),
        mesh=mesh, in_specs=P("world"), out_specs=P("world")),
        donate_argnums=0)
    xp = jnp.ones(n * P_, jnp.float32)  # nbytes per device
    xp = probe(xp)
    xp.block_until_ready()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        xp = probe(xp)
        xp.block_until_ready()
        ts.append(time.perf_counter() - t0)
    t = statistics.median(ts)
    result["ici_linerate_gbps_per_link"] = nbytes / t / 1e9
except Exception as e:
    result["linerate_error"] = str(e)[:300]

# The allreduce legs: every rank holds its OWN nbytes buffer.  The global
# [P, n] array is created ALREADY sharded one block per device (out-
# shardings on the init jit) — never replicated and never materialized on
# a single device first, the round-1 HBM-inflation trap.  Steady-state
# HBM per device: one input shard + one (replicated) result.
sharded = NamedSharding(mesh, P("world"))
make_sharded = jax.jit(lambda: jnp.ones((P_, n), jnp.float32),
                       out_shardings=sharded)
from mpi_tpu.tpu import pallas_ring as _pr

def _algo_fn(a):
    if a == "pallas_ring_unidir":
        return lambda x: _pr.pallas_ring_allreduce(
            x.reshape(-1), "world", P_, bidirectional=False,
            interpret=jax.devices()[0].platform == "cpu")
    return lambda x: comm.allreduce(x.reshape(-1), algorithm=a)

# per-direction traffic of the bidirectional kernel (counter-rotating
# rings split each chunk's tiles between the two ICI link directions)
result["pallas_ring_flows"] = _pr.flow_summary(n, P_)

for algo in ("ring", "fused", "pallas_ring", "pallas_ring_unidir"):
    try:
        # every algorithm runs under the default check_vma=True, EXCEPT
        # the pallas legs on the CPU sim: under interpret+vma the kernel
        # takes the vma-typed ppermute fallback, which would silently
        # measure the same code as the 'ring' leg — check_vma=False there
        # keeps the INTERPRETED KERNEL (the data path being rehearsed) in
        # the measurement.  On real chips (interpret=False) the compiled
        # kernel runs under check_vma=True like everything else.
        cv = (not algo.startswith("pallas_ring")
              or jax.devices()[0].platform != "cpu")
        f = jax.jit(jax.shard_map(
            lambda x, a=algo: _algo_fn(a)(x)[None],
            mesh=mesh, in_specs=P("world"), out_specs=P("world"),
            check_vma=cv))
        xg = make_sharded()
        f(xg).block_until_ready()
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            f(xg).block_until_ready()
            ts.append(time.perf_counter() - t0)
        t = statistics.median(ts)
        result[algo] = {{"busbw_gbps": nbytes * 2 * (P_ - 1) / P_ / t / 1e9,
                         "t_s": t}}
    except Exception as e:
        result[algo + "_error"] = str(e)[:300]
if ("ici_linerate_gbps_per_link" in result
        and isinstance(result.get("pallas_ring"), dict)):
    result["pallas_ring"]["pct_of_linerate"] = round(
        100 * result["pallas_ring"]["busbw_gbps"]
        / result["ici_linerate_gbps_per_link"], 1)
with open(os.environ["BENCH_OUT"], "w") as fh:
    json.dump(result, fh)
"""


ATTENTION_PROG = """
import os, sys, time, statistics, json
sys.path.insert(0, {repo!r})
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from mpi_tpu.tpu import default_mesh, enable_compile_cache
from mpi_tpu.tpu.pallas_attention import (pallas_ring_attention,
                                          _fallback_attention)

if jax.devices()[0].platform == "tpu":
    enable_compile_cache()

# Attention FLOPs accounting (VERDICT r4 next #4): exact ring attention
# over the global sequence S = P*Sb does 2*S*S*d MACs for QK^T plus
# 2*S*S*d for PV -> 4*S^2*d FLOPs total (the online-softmax exp/max
# bookkeeping is O(S^2) and excluded, as in flash-attention papers).
mesh = default_mesh()
P_ = len(jax.devices())
Sb = int(os.environ.get("ATT_SB", 512))
d = int(os.environ.get("ATT_D", 128))
iters = int(os.environ.get("ATT_ITERS", 5))
S = P_ * Sb
platform = jax.devices()[0].platform
interp = platform == "cpu"
flops = 4.0 * S * S * d
result = {{"nranks": P_, "sb": Sb, "d": d, "seq": S, "platform": platform,
           "flops_per_call": flops}}

rng = np.random.RandomState(0)
sharded = NamedSharding(mesh, P("world"))
q = jax.device_put(jnp.asarray(rng.randn(S, d), jnp.float32), sharded)

def bench(f, x):
    f(x).block_until_ready()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)

# pallas legs run check_vma=False on the CPU sim so the INTERPRETED
# KERNEL (serial data path) is measured, not the ppermute fallback —
# same reasoning as the northstar pallas legs; compiled kernel (vma
# typing ON) on chips.  The train leg is value_and_grad through the
# fused forward AND the fused ring backward (resident/tiled per the
# VMEM plan); its FLOPs factor: forward 2 matmuls (4*S^2*d) + backward
# 5 matmuls (s recompute, dP, dS*K, dS^T*Q, P^T*dO = 10*S^2*d) -> 3.5x.
def train(qb):
    def loss(qq, kk, vv):
        out = pallas_ring_attention(qq, kk, vv, "world", P_,
                                    interpret=interp)
        return jax.lax.psum(jnp.sum(out ** 2), "world")
    _, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(qb, qb, qb)
    return grads[0] + grads[1] + grads[2]

legs = {{
    "pallas_kernel": (
        lambda qb: pallas_ring_attention(qb, qb, qb, "world", P_,
                                         interpret=interp),
        not interp, 1.0),
    "pallas_kernel_train": (train, not interp, 3.5),
    "ppermute_ring": (
        lambda qb: _fallback_attention(qb, qb, qb, "world", P_,
                                       1.0 / d ** 0.5), True, 1.0),
}}
for name, (fn, cv, ff) in legs.items():
    try:
        f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("world"),
                                  out_specs=P("world"), check_vma=cv))
        t = bench(f, q)
        result[name] = {{"t_s": t, "gflops_per_s": ff * flops / t / 1e9,
                         "flops_per_call": ff * flops}}
    except Exception as e:
        result[name + "_error"] = str(e)[:300]

# plain dense attention on ONE device over the same global sequence —
# the no-parallelism baseline the ring is beating.  The dense [S, S]
# score matrix is the whole point of the comparison, so cap it at a
# size one device can hold instead of OOMing on large slices.
if 2 * S * S * 4 > 4 * 1024 ** 3:
    result["local_dense_1dev_skipped"] = (
        f"dense scores would need {{2 * S * S * 4 / 1e9:.1f}} GB")
else:
    try:
        def local(qf):
            s = (qf @ qf.T) / d ** 0.5
            return jax.nn.softmax(s, axis=-1) @ qf
        ql = jax.device_put(jnp.asarray(rng.randn(S, d), jnp.float32),
                            jax.devices()[0])
        t = bench(jax.jit(local), ql)
        result["local_dense_1dev"] = {{"t_s": t,
                                       "gflops_per_s": flops / t / 1e9}}
    except Exception as e:
        result["local_dense_1dev_error"] = str(e)[:300]

# MFU vs the chip's nominal f32 MXU peak (documented bf16 peak / 2 —
# the convention the module uses consistently so cross-round numbers
# compare; only computed when the device kind is recognized)
PEAKS_F32_TFLOPS = {{"TPU v4": 137.5, "TPU v5 lite": 98.5,
                     "TPU v5e": 98.5, "TPU v5p": 229.5, "TPU v6e": 459.0}}
kind = jax.devices()[0].device_kind
if platform == "tpu":
    for k, peak_tf in PEAKS_F32_TFLOPS.items():
        if kind.lower().startswith(k.lower()):
            result["mxu_peak_f32_tflops_per_chip"] = peak_tf
            for leg in ("pallas_kernel", "pallas_kernel_train",
                        "ppermute_ring", "local_dense_1dev"):
                if isinstance(result.get(leg), dict):
                    chips = 1 if leg == "local_dense_1dev" else P_
                    result[leg]["mfu_pct_f32"] = round(
                        100 * result[leg]["gflops_per_s"]
                        / (peak_tf * 1e3 * chips), 2)
            break
with open(os.environ["BENCH_OUT"], "w") as fh:
    json.dump(result, fh)
"""


def _cpu_env(ndev: int = 2) -> dict:
    """Child env that yields an ``ndev``-device CPU jax (the labelled
    CPU-sim rehearsal legs)."""
    import re

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={ndev}").strip()
    return env


def _run_sub(code: str, env_extra: dict, timeout: float = 600.0,
             env_base: dict | None = None) -> str:
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "out.txt")
        env = dict(os.environ) if env_base is None else dict(env_base)
        env["BENCH_OUT"] = out
        env.update(env_extra)
        script = os.path.join(td, "prog.py")
        with open(script, "w") as f:
            f.write(code)
        subprocess.run([sys.executable, script], env=env, check=True,
                       timeout=timeout, cwd=REPO)
        with open(out) as f:
            return f.read()


def measure_process_p50(backend: str) -> float:
    """p50 of the 2-rank 1K-f32 allreduce over real rank processes on the
    given transport ('socket' = the reference architecture, 'shm' = the
    native data plane)."""
    sys.path.insert(0, REPO)
    from mpi_tpu.launcher import launch

    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "out.txt")
        script = os.path.join(td, "prog.py")
        with open(script, "w") as f:
            f.write(SOCKET_PROG.format(repo=REPO))
        rc = launch(2, [script], env_extra={"BENCH_OUT": out}, timeout=300.0,
                    backend=backend)
        if rc != 0:
            raise RuntimeError(f"{backend} bench failed with exit code {rc}")
        with open(out) as f:
            return float(f.read())


def _shm_small_msg_diagnosis() -> dict:
    """Ground the shm-vs-socket small-message story with evidence
    (VERDICT r5 weak #1 / next-round #7: the r5 artifact showed
    shm-p50 >= socket-p50 at 1KB with no diagnosis attached).

    Runs the 1KB ping-pong on socket and on shm under three spin
    settings of the futex ring's receive path (MPI_TPU_SHM_SPIN_US:
    default, 0, 300).  The mechanism the legs separate: a blocked shm
    receiver pays a futex sleep + wakeup — two scheduler trips per
    message — unless it spins long enough for the sender to produce the
    frame, WHICH REQUIRES A SPARE CORE.  On a 1-core box the spin can
    never be satisfied (the sender only runs once the receiver yields),
    so every message eats the wakeup latency and shm's p50 can land
    above loopback TCP's, whose kernel wakeup overlaps its own syscall
    work — that is the r5 inversion.  With >=2 cores the long-spin leg
    removes the wakeup and shm beats socket by several x; the verdict
    field states which regime THIS run measured."""
    from benchmarks import host_sweep

    legs = {leg["leg"]: leg.get("p50_us")
            for leg in host_sweep.latency_diagnosis_legs()}
    diag = {"cpus": os.cpu_count(), "p50_us_by_leg": legs}
    sock, dflt, spin = (legs.get("socket"), legs.get("shm_default"),
                        legs.get("shm_spin_300us"))
    if None in (sock, dflt, spin):
        diag["verdict"] = "diagnosis leg failed; see p50_us_by_leg errors"
    elif dflt >= sock:
        diag["verdict"] = (
            f"inversion reproduced (shm {dflt:.0f}us >= socket "
            f"{sock:.0f}us): futex wakeup cost, not the transport — "
            f"spin=300us leg measures {spin:.0f}us, "
            f"{'removing' if spin < sock else 'NOT removing'} it on "
            f"{os.cpu_count()} core(s)")
    else:
        diag["verdict"] = (
            f"no inversion on this box ({os.cpu_count()} cores: the "
            f"receiver's spin can be satisfied while the sender runs): "
            f"shm {dflt:.0f}us < socket {sock:.0f}us, long-spin floor "
            f"{spin:.0f}us — the r5 inversion was the 1-core scheduler "
            f"(futex wakeup on every message), not the shm data plane")
    return diag


def _probe_devices() -> list:
    """Ask a SUBPROCESS what jax.devices() says: the parent stays off
    JAX, because a chip belongs to one process and the legs that need it
    run in children.  A probe that fails or hangs is an error — the
    benchmark never moves itself to the CPU."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print('\\n'.join(str(d) for d in jax.devices()))"],
        capture_output=True, text=True, timeout=180.0)
    devs = [l for l in out.stdout.splitlines() if l.strip()]
    if out.returncode != 0 or not devs:
        raise RuntimeError(
            f"device probe failed (rc {out.returncode}): "
            f"{out.stderr[-1000:]}")
    return devs


def main() -> None:
    # n_real comes from the PROBE, never from an in-process jax.devices():
    # the parent must not hold the chip — the legs that need devices run
    # in subprocesses
    devices = _probe_devices()
    n_real = len(devices)
    details = {"devices": devices}

    # best-of-7 per leg (VERDICT r4 next #7 raised 3→7): each sample is
    # already a p50 of 200 calls, but on this 1-core box cross-RUN
    # scheduler contention dominates the variance (observed r3/r4: the
    # ratio swung 1.4x-3.8x between rounds); the min is the
    # least-contended sample of each transport and stays the headline
    # for cross-round continuity, with the median + spread reported
    # alongside so a moved headline can be told apart from a lucky
    # draw.  ALL samples are persisted (VERDICT r3 next #6).
    n_samples = int(os.environ.get("BENCH_SAMPLES", 7))
    details["cpu_pinning"] = (
        "per-rank sched_setaffinity" if (os.cpu_count() or 1) >= 2
        else f"unavailable ({os.cpu_count()} core)")
    socket_samples = [measure_process_p50("socket")
                      for _ in range(n_samples)]
    socket_us = min(socket_samples)
    details["socket_2rank_1kf32_p50_us"] = socket_us
    details["socket_samples_us"] = socket_samples
    try:
        # full n_samples like every other leg (VERDICT r5 weak #1: the shm
        # leg was the one still at 3 samples, and its p50 was undiagnosed)
        shm_samples = [measure_process_p50("shm") for _ in range(n_samples)]
        details["shm_2rank_1kf32_p50_us"] = min(shm_samples)
        details["shm_samples_us"] = shm_samples
        details["shm_1kb_diagnosis"] = _shm_small_msg_diagnosis()
    except Exception as e:  # native toolchain may be absent
        details["shm_error"] = str(e)[:200]

    force_cpu = "yes" if n_real < 2 else "no"
    spmd_samples = [float(_run_sub(
        SPMD_PROG.format(repo=REPO, force_cpu=force_cpu), {},
        env_base=_cpu_env() if force_cpu == "yes" else None))
        for _ in range(n_samples)]
    spmd_us = min(spmd_samples)
    details["spmd_2rank_1kf32_p50_us"] = spmd_us
    details["spmd_samples_us"] = spmd_samples
    details["spmd_leg_platform"] = "cpu-sim" if force_cpu == "yes" else "tpu-ici"

    # North-star leg (BASELINE.json:5): the REAL measurement needs >=2
    # chips; the rehearsal leg runs the IDENTICAL program on an 8-device
    # CPU mesh at reduced size on every invocation, so the measurement
    # code is proven before hardware day (VERDICT round 1 next-step #1).
    if n_real >= 2:
        try:
            details["northstar_256mb_ring"] = json.loads(
                _run_sub(NORTHSTAR_PROG.format(repo=REPO), {})
            )
        except Exception as e:  # pragma: no cover - multichip only
            details["northstar_error"] = str(e)
    try:
        details["northstar_sim_8dev"] = json.loads(_run_sub(
            NORTHSTAR_PROG.format(repo=REPO),
            {"NS_BYTES": str(8 * 1024 * 1024), "NS_ITERS": "5"},
            env_base=_cpu_env(8)))
    except Exception as e:
        details["northstar_sim_error"] = str(e)[:500]

    # Attention leg (VERDICT r4 next #4): FLOPs-based accounting for
    # the fused ring-attention kernel vs the ppermute ring vs plain
    # single-device dense attention.  On >=2 chips the compiled kernel
    # runs over ICI with an MFU-style % of the MXU peak; on one chip
    # the local-dense MFU still measures; the CPU-sim rehearsal runs
    # the IDENTICAL program every invocation so the measurement path
    # is proven before hardware day (same discipline as the northstar).
    # "chip" means an actual accelerator in the probe — a CPU-only
    # host's single TFRT_CPU device must not masquerade as one (the
    # CPU-sim rehearsal below covers that case)
    has_chip = any("cpu" not in s.lower() for s in devices)
    if has_chip and n_real >= 2:
        try:
            details["attention_tpu"] = json.loads(_run_sub(
                ATTENTION_PROG.format(repo=REPO),
                {"ATT_SB": "2048", "ATT_ITERS": "10"}))
        except Exception as e:  # pragma: no cover - multichip only
            details["attention_tpu_error"] = str(e)[:500]
    elif has_chip:
        try:  # single chip: the local-dense MFU branch is still real
            details["attention_1chip"] = json.loads(_run_sub(
                ATTENTION_PROG.format(repo=REPO),
                {"ATT_SB": "2048", "ATT_ITERS": "10"}))
        except Exception as e:
            details["attention_1chip_error"] = str(e)[:500]
    try:
        details["attention_sim_8dev"] = json.loads(_run_sub(
            ATTENTION_PROG.format(repo=REPO),
            {"ATT_SB": "128", "ATT_ITERS": "3"}, env_base=_cpu_env(8)))
    except Exception as e:
        details["attention_sim_error"] = str(e)[:500]

    speedup = socket_us / spmd_us
    med_speedup = (statistics.median(socket_samples)
                   / statistics.median(spmd_samples))
    # ISSUE 4 satellite: every bench result JSON is oversubscription-
    # stamped (2 rank procs + the driver on this box's cores) so the
    # known ±2-3x noise cells are machine-identifiable
    details["oversubscribed"] = 3 > (os.cpu_count() or 1)
    with open(os.path.join(REPO, "BENCH_DETAILS.json"), "w") as f:
        json.dump(details, f, indent=2)

    print(json.dumps({
        "metric": "allreduce_1kf32_2rank_p50_speedup_spmd_over_socket",
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup, 3),
        "median_speedup": round(med_speedup, 3),
        "oversubscribed": 3 > (os.cpu_count() or 1),
        "socket_us_min_med_max": [round(min(socket_samples), 1),
                                  round(statistics.median(socket_samples),
                                        1),
                                  round(max(socket_samples), 1)],
        "spmd_us_min_med_max": [round(min(spmd_samples), 1),
                                round(statistics.median(spmd_samples), 1),
                                round(max(spmd_samples), 1)],
    }))


if __name__ == "__main__":
    if "--chaos" in sys.argv[1:]:
        # FaultyTransport drop/delay/duplicate sweep over the collective
        # family asserting diagnose-don't-hang (ISSUE 3 satellite);
        # --quick is the tier-1 smoke spelling, mirroring --sweep's.
        # --serve (ISSUE 7) swaps in the resident-pool leg: continuous
        # SIGKILL against a live world server, asserting worlds/sec
        # never reaches zero and every lease completes or raises a
        # named FT error.  --links (ISSUE 10) swaps in the link-fault
        # leg: connection resets against a 3-rank socket world under a
        # mixed-collective stream, asserting bit-parity with an
        # uninjected run, zero ProcFailedError, link_reconnects >=
        # resets, and that a genuine SIGKILL is still diagnosed within
        # the detection bound; --no-healing is the honest "pre" run
        # (link_retry_timeout_s=0, the same resets terminal).
        # --federation (ISSUE 15) swaps in the federated-serve leg:
        # SIGKILL servers of an N-server federation under an open-loop
        # client fleet — worlds/s never zero, every failure named,
        # orphans adopted, no leader-authority overlap, plus the
        # beyond-capacity admission-control leg; --pre is the honest
        # single-server baseline dying to zero (the committed
        # federation_{pre,post}.json artifacts).
        from benchmarks import chaos

        args = ["--quick"] if "--quick" in sys.argv[1:] else []
        if "--serve" in sys.argv[1:]:
            args.append("--serve")
        if "--links" in sys.argv[1:]:
            args.append("--links")
        if "--federation" in sys.argv[1:]:
            args.append("--federation")
        if "--partition" in sys.argv[1:]:
            # ISSUE 18: the replicated-store partition leg — a raft
            # fabric namespace (no shared dir), a store-level partition
            # isolating the raft leader, named NoQuorumError refusal on
            # the minority, stale-intent truncation on heal (the
            # committed federation_partition_{pre,post}.json artifacts)
            args.append("--partition")
        if "--pre" in sys.argv[1:]:
            args.append("--pre")
        if "--no-healing" in sys.argv[1:]:
            args.append("--no-healing")
        if "--trace-dir" in sys.argv[1:]:
            # ISSUE 13 satellite: run the links leg under the flight
            # recorder and merge the per-rank Chrome traces
            idx = sys.argv.index("--trace-dir")
            if idx + 1 >= len(sys.argv):
                sys.exit("bench.py: --trace-dir needs a directory")
            args += ["--trace-dir", sys.argv[idx + 1]]
        sys.exit(chaos.main(args))
    if "--hotpath" in sys.argv[1:]:
        # zero-copy hot-path leg (ISSUE 11): 16MB socket allreduce
        # under healing-off / eager-retain / zero-copy retention modes
        # (pvar-proven retention-without-copy + one sendmsg per frame)
        # plus the lease-rides-the-pooled-arena check; the full run
        # writes the committed hotpath_{pre,post}.json artifacts,
        # --quick is the tier-1 smoke spelling.
        from benchmarks import hotpath

        if "--quick" in sys.argv[1:]:
            sys.exit(hotpath.main(["--quick"]))
        sys.exit(hotpath.main(
            ["--out-pre", os.path.join(REPO, "benchmarks", "results",
                                       "hotpath_pre.json"),
             "--out-post", os.path.join(REPO, "benchmarks", "results",
                                        "hotpath_post.json")]))
    if "--serve-bench" in sys.argv[1:]:
        # world-churn leg (ISSUE 7): resident world server vs cold
        # launch() — worlds/sec + p99 world-acquire latency; the full
        # run writes the committed serve_{pre,post}.json artifacts.
        from benchmarks import serve_bench

        if "--quick" in sys.argv[1:]:
            sys.exit(serve_bench.main(["--quick"]))
        sys.exit(serve_bench.main(
            ["--out-pre", os.path.join(REPO, "benchmarks", "results",
                                       "serve_pre.json"),
             "--out-post", os.path.join(REPO, "benchmarks", "results",
                                        "serve_post.json")]))
    if "--compress" in sys.argv[1:]:
        # compressed-collectives leg (ISSUE 8): 64MB allreduce/
        # reduce_scatter under ring vs bf16/int8/top-k wire formats on
        # both host transports, byte-plane pvars recorded per call; the
        # full run writes the committed compress_{pre,post}.json
        # artifacts, --quick is the tier-1 smoke spelling.
        from benchmarks import compress_bench

        if "--quick" in sys.argv[1:]:
            sys.exit(compress_bench.main(["--quick"]))
        sys.exit(compress_bench.main(
            ["--out-pre", os.path.join(REPO, "benchmarks", "results",
                                       "compress_pre.json"),
             "--out-post", os.path.join(REPO, "benchmarks", "results",
                                        "compress_post.json")]))
    if "--verify-overhead" in sys.argv[1:]:
        # verifier cost leg (ISSUE 5): asserts the off-mode zero-cost
        # contract (pvar-identical hot path) and prices the on-mode.
        # --progress (ISSUE 6) adds the async-progress-engine leg:
        # same pvar contracts with the engine's thread running.
        # --trace (ISSUE 13) adds the flight-recorder leg: trace-off
        # asserts 0 trace events + unchanged wire accounting, trace-on
        # prices the ring buffer.
        from benchmarks import verify_overhead

        args = ["--quick"] if "--quick" in sys.argv[1:] else []
        if "--progress" in sys.argv[1:]:
            args.append("--progress")
        if "--trace" in sys.argv[1:]:
            args.append("--trace")
        sys.exit(verify_overhead.main(args))
    if "--tune" in sys.argv[1:]:
        # tuned-dispatch table generator (ISSUE 9): sweeps (transport x
        # P x payload x algorithm — including the arena as a measured
        # algorithm) and writes the per-machine tuning table under
        # benchmarks/results/tuning/ that algorithm='auto' consults
        # (mpi_tpu/tuning).  --quick is the tier-1 smoke spelling
        # (1KB, P=2, 1 sample, stdout only — no artifact written).
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import tune

        if "--quick" in sys.argv[1:]:
            sys.exit(tune.main(["--quick"]))
        sys.exit(tune.main([]))
    if "--recvpool" in sys.argv[1:] and "--shm" in sys.argv[1:]:
        # zero-copy-everywhere leg (ISSUE 19): the pvar-asserted steer
        # bench (shm ring steering + user irecv(buf=) rendezvous +
        # scatter-gather receives) on both host transports; the full
        # run writes the committed recvpool_shm_{pre,post}.json pair
        # ('pre' pins MPI_TPU_RECV_STEERING=0).  --quick is the tier-1
        # smoke spelling (64KB, 1 sample, stdout only).
        from benchmarks import host_sweep

        if "--quick" in sys.argv[1:]:
            sys.exit(host_sweep.main(["--recvpool", "--shm",
                                      "--label", "post", "--quick"]))
        rc = host_sweep.main(
            ["--recvpool", "--shm", "--label", "pre",
             "--out", os.path.join(REPO, "benchmarks", "results",
                                   "recvpool_shm_pre.json")])
        sys.exit(rc or host_sweep.main(
            ["--recvpool", "--shm", "--label", "post",
             "--out", os.path.join(REPO, "benchmarks", "results",
                                   "recvpool_shm_post.json")]))
    if "--persist" in sys.argv[1:]:
        # persistent-collective leg (ISSUE 12): osu_allreduce_persistent-
        # shaped fresh-call vs start() re-fire p50s at small payloads on
        # both host transports; writes BOTH committed artifacts —
        # persist_pre.json pins MPI_TPU_NBC=thread (the seed's per-call-
        # thread semantics) and persist_post.json nbc=auto (engine
        # schedule state machines).  --quick is the tier-1 smoke
        # spelling (stdout only).
        from benchmarks import host_sweep

        if "--quick" in sys.argv[1:]:
            sys.exit(host_sweep.main(["--persist", "--label", "post",
                                      "--quick"]))
        rc = host_sweep.main(
            ["--persist", "--label", "pre",
             "--out", os.path.join(REPO, "benchmarks", "results",
                                   "persist_pre.json")])
        sys.exit(rc or host_sweep.main(
            ["--persist", "--label", "post",
             "--out", os.path.join(REPO, "benchmarks", "results",
                                   "persist_post.json")]))
    if "--sweep" in sys.argv[1:]:
        # the OSU-style host data-plane size sweep (ISSUE 1 tentpole #4,
        # extended to alltoall/reduce_scatter/rabenseifner in ISSUE 2);
        # writes the post-change artifact next to the committed pre run.
        # --quick is the tier-1 smoke spelling (tiny sizes, 1 sample) that
        # keeps the sweep harness from bit-rotting between perf PRs.
        from benchmarks import host_sweep

        if "--quick" in sys.argv[1:]:
            # smoke run: stdout only, no artifact to leak or overwrite
            sys.exit(host_sweep.main(["--label", "post", "--quick"]))
        sys.exit(host_sweep.main(
            ["--label", "post",
             "--out", os.path.join(REPO, "benchmarks", "results",
                                   "host_sweep2_post.json")]))
    main()
