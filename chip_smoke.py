#!/usr/bin/env python
"""Smoke run of the TPU path on real chips, checked against references.

    python chip_smoke.py              # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4    # four chips: phases (d)-(g) only

One chip:
  (a) the Jacobi heat stencil through ``mpi_tpu.run(..., backend="tpu")``
      on an 8192x8192 f32 grid, 50 sweeps, against a NumPy loop;
  (b) ``TpuCommunicator`` allreduce (every algorithm), bcast, allgather and
      alltoall on a 256 MiB f32 buffer, against the P=1 values;
  (c) the long-context training block of examples/long_context_training.py
      at one shard, seq 8192, 3 SGD steps, against the dense oracle.
Four chips (what exists only across chips):
  (d) 256 MiB f32 allreduce per chip: fused, ring, recursive_halving,
      pallas_ring, pallas_ring unidirectional, against the NumPy sum;
  (e) pallas_ring allgather and reduce-scatter at 64 MiB per chip;
  (f) Pallas ring attention, causal forward and fused backward, Sb 2048
      per chip, Hq 8, d 128, bf16, against dense attention on one chip;
  (g) the dp x mp training step of ``__graft_entry__._build_step`` on the
      2x2 chips, 2 steps, ``dp_algorithm="pallas_ring"`` against "fused".

Every phase fails the run on a mismatch, on an exception, on a non-zero
``pallas_ring_fallbacks`` / ``attention_fallbacks`` pvar, or when any
``TpuCommunicator`` resolves to the Pallas interpreter.  A run without a
TPU fails before any phase.  Earlier lines report first-call seconds
(compile + one run, not a speed) and peak device memory; the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

MIB = 2 ** 20
LANES = 128

# sizes of the phases (see the module docstring)
JACOBI_N, JACOBI_SWEEPS = 8192, 50
COLL_BYTES = 256 * MIB           # (b) and (d), per chip
LC_SEQ, LC_D, LC_STEPS = 8192, 128, 3
AGRS_BYTES = 64 * MIB            # (e), per chip
ATT_HQ, ATT_SB, ATT_D = 8, 2048, 128


def log(msg: str) -> None:
    print(msg, flush=True)


def _timed(fn, *args):
    """→ (result, seconds) of a first call, blocked until ready."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# -- one chip ----------------------------------------------------------------


def phase_jacobi(mpi_tpu):
    """(a) 8192x8192 f32 heat stencil, 50 sweeps, vs a NumPy loop."""
    from examples.jacobi import jacobi_program

    rows = cols = JACOBI_N
    iters = JACOBI_SWEEPS
    out, secs = _timed(lambda: mpi_tpu.run(
        jacobi_program, backend="tpu", nranks=1, rows_per_rank=rows,
        cols=cols, iters=iters))
    grid = np.asarray(out[0][0])
    residual = float(np.asarray(out[1]).ravel()[0])

    # NumPy reference: same sweeps, same f32 sum order (n + s + w + e)
    u = np.zeros((rows, cols), np.float32)
    for _ in range(iters):
        new = np.empty_like(u)
        new[0] = 1.0                # hot top edge (rank 0's halo)
        new[1:] = u[:-1]            # north
        new[:-1] += u[1:]           # south (bottom halo is 0)
        new[:, 1:] += u[:, :-1]     # west
        new[:, :-1] += u[:, 1:]     # east
        new *= np.float32(0.25)
        new[:, 0] = 0.0
        new[:, -1] = 0.0
        u, prev = new, u
    ref_res = float(np.max(np.abs(u - prev)))
    err = float(np.max(np.abs(grid - u)))
    _check(grid.shape == (rows, cols), f"jacobi grid shape {grid.shape}")
    _check(err <= 1e-5, f"jacobi grid max |err| {err}")
    _check(abs(residual - ref_res) <= 1e-5,
           f"jacobi residual {residual} vs NumPy {ref_res}")
    return {"first_call_s": secs, "grid_max_abs_err": err,
            "residual": residual, "numpy_residual": ref_res}


def phase_collectives_1chip(mpi_tpu):
    """(b) every TpuCommunicator collective at P=1 on 256 MiB f32."""
    import jax
    import jax.numpy as jnp

    n = COLL_BYTES // 4
    x = jax.device_put(
        jnp.arange(n, dtype=jnp.float32).reshape(n // LANES, LANES),
        jax.devices()[0])
    cases = [(f"allreduce[{a}]", lambda c, v, a=a: c.allreduce(v, algorithm=a))
             for a in ("fused", "ring", "recursive_halving", "reduce_bcast",
                       "pallas_ring")]
    cases += [(f"bcast[{a}]", lambda c, v, a=a: c.bcast(v, 0, a))
              for a in ("fused", "tree")]
    cases += [(f"allgather[{a}]",
               lambda c, v, a=a: c.allgather(v, algorithm=a)[0])
              for a in ("fused", "ring", "doubling", "pallas_ring")]
    cases += [(f"alltoall[{a}]",
               lambda c, v, a=a: c.alltoall(v[None], algorithm=a)[0])
              for a in ("fused", "pairwise")]
    same = jax.jit(lambda a, b: jnp.array_equal(a, b))
    secs = {}
    for name, fn in cases:
        out, secs[name] = _timed(
            lambda: mpi_tpu.run(fn, x, backend="tpu", nranks=1))
        _check(bool(same(out[0], x)), f"{name} differs from its P=1 value")
    return {"first_call_s": secs}


def phase_long_context(mpi_tpu):
    """(c) the long-context block, one shard, seq 8192, 3 SGD steps,
    against the dense single-device oracle (f32 matmuls at full
    precision on both sides, so the comparison is tight)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from examples.long_context_training import (dense_train_step,
                                                init_params,
                                                sharded_train_step)
    from mpi_tpu.tpu import default_mesh

    seq, d, steps, lr = LC_SEQ, LC_D, LC_STEPS, 1e-2
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(seq, d), jnp.float32)
    y = jnp.asarray(rng.randn(seq, d), jnp.float32)
    mesh = default_mesh(1, axis_name="sp")
    with jax.default_matmul_precision("float32"):
        jstep = jax.jit(jax.shard_map(
            sharded_train_step(1, interpret=False), mesh=mesh,
            in_specs=(P(), P("sp"), P("sp")), out_specs=(P(), P()),
            check_vma=False))
        dstep = jax.jit(dense_train_step())
        p_s = p_d = init_params(d, 2 * d)
        losses, secs = [], {}
        for i in range(steps):
            (loss_s, g_s), t_s = _timed(jstep, p_s, x, y)
            (loss_d, g_d), t_d = _timed(dstep, p_d, x, y)
            if i == 0:
                secs = {"sharded_first_call_s": t_s,
                        "dense_first_call_s": t_d}
            losses.append((float(loss_s), float(loss_d)))
            _check(abs(float(loss_s) - float(loss_d))
                   <= 1e-4 * abs(float(loss_d)),
                   f"step {i} loss {float(loss_s)} vs dense {float(loss_d)}")
            for k in g_d:
                e = _rel_err(g_s[k], g_d[k])
                _check(e <= 1e-3, f"step {i} grad {k} rel err {e}")
            p_s = jax.tree.map(lambda p, g: p - lr * g, p_s, g_s)
            p_d = jax.tree.map(lambda p, g: p - lr * g, p_d, g_d)
    return dict(secs, losses=losses)


# -- four chips --------------------------------------------------------------


def _mesh4():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:4]), ("world",))


def _int_valued(mesh, rows_per_chip: int, modulus: int = 1021):
    """Global f32 [4*rows, 128] sharded by rows; integer values below
    2**14 so every sum of four is exact."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    def make():
        i = jnp.arange(4 * rows_per_chip * LANES, dtype=jnp.int32)
        chip = i // (rows_per_chip * LANES)
        return ((i % modulus) + 1000 * chip).astype(jnp.float32).reshape(
            4 * rows_per_chip, LANES)

    return jax.jit(make, out_shardings=NamedSharding(mesh, P("world")))()


def _mismatches(mesh, out, ref, out_spec, ref_spec):
    """Per-chip count of elements where ``out`` != ``ref``, on device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    f = jax.jit(jax.shard_map(
        lambda o, r: jnp.sum(o != r)[None], mesh=mesh,
        in_specs=(out_spec, ref_spec), out_specs=P("world")))
    return np.asarray(f(out, ref)).tolist()


def phase_allreduce4(mpi_tpu):
    """(d) 256 MiB f32 per chip, five algorithms, vs the NumPy sum."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_tpu.tpu import TpuCommunicator
    from mpi_tpu.tpu.pallas_ring import pallas_ring_allreduce

    mesh = _mesh4()
    comm = TpuCommunicator("world", mesh)
    rows = COLL_BYTES // (4 * LANES)
    x = _int_valued(mesh, rows)
    ref = np.asarray(x).reshape(4, rows, LANES).sum(axis=0)
    ref_d = jax.device_put(ref, NamedSharding(mesh, P()))
    algos = {a: (lambda v, a=a: comm.allreduce(v, algorithm=a))
             for a in ("fused", "ring", "recursive_halving", "pallas_ring")}
    algos["pallas_ring_unidir"] = lambda v: pallas_ring_allreduce(
        v, "world", 4, bidirectional=False, interpret=comm._pallas_interp)
    secs = {}
    for name, fn in algos.items():
        f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("world"),
                                  out_specs=P("world"), check_vma=False))
        out, secs[name] = _timed(f, x)
        bad = _mismatches(mesh, out, ref_d, P("world"), P())
        _check(sum(bad) == 0, f"allreduce[{name}] mismatches per chip {bad}")
    return {"first_call_s": secs}


def phase_ag_rs4(mpi_tpu):
    """(e) pallas_ring allgather and reduce-scatter, 64 MiB per chip."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_tpu.tpu import TpuCommunicator

    mesh = _mesh4()
    comm = TpuCommunicator("world", mesh)
    rows = AGRS_BYTES // (4 * LANES)
    x = _int_valued(mesh, rows)
    host = np.asarray(x)
    secs = {}

    ag = jax.jit(jax.shard_map(
        lambda v: comm.allgather(v, algorithm="pallas_ring")[None],
        mesh=mesh, in_specs=P("world"), out_specs=P("world"),
        check_vma=False))
    out, secs["allgather"] = _timed(ag, x)
    ref = jax.device_put(host.reshape(1, 4, rows, LANES),
                         NamedSharding(mesh, P()))
    bad = _mismatches(mesh, out, ref, P("world"), P())
    _check(sum(bad) == 0, f"allgather mismatches per chip {bad}")

    blk = rows // 4
    rs = jax.jit(jax.shard_map(
        lambda v: comm.reduce_scatter(v.reshape(4, blk, LANES),
                                      algorithm="pallas_ring"),
        mesh=mesh, in_specs=P("world"), out_specs=P("world"),
        check_vma=False))
    out, secs["reduce_scatter"] = _timed(rs, x)
    ref = host.reshape(4, 4, blk, LANES).sum(axis=0).reshape(4 * blk, LANES)
    ref = jax.device_put(ref, NamedSharding(mesh, P("world")))
    bad = _mismatches(mesh, out, ref, P("world"), P("world"))
    _check(sum(bad) == 0, f"reduce_scatter mismatches per chip {bad}")
    return {"first_call_s": secs}


def phase_attention4(mpi_tpu):
    """(f) causal ring attention fwd + fused bwd vs dense on one chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_tpu.tpu import TpuCommunicator
    from mpi_tpu.tpu.pallas_attention import pallas_ring_attention

    mesh = _mesh4()
    interp = TpuCommunicator("world", mesh)._pallas_interp
    hq, sb, d = ATT_HQ, ATT_SB, ATT_D
    S = 4 * sb
    rng = np.random.RandomState(7)
    host = [rng.randn(hq, S, d).astype(np.float32) for _ in range(4)]
    q, k, v, ct = (jnp.asarray(a, jnp.bfloat16) for a in host)
    spec = P(None, "world")
    shard = NamedSharding(mesh, spec)
    qs, ks, vs, cts = (jax.device_put(a, shard) for a in (q, k, v, ct))

    def ring(qb, kb, vb):
        return pallas_ring_attention(qb, kb, vb, "world", 4, causal=True,
                                     interpret=interp)

    def ring_grads(qb, kb, vb, cb):
        _, vjp = jax.vjp(ring, qb, kb, vb)
        return vjp(cb)

    fwd = jax.jit(jax.shard_map(ring, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec, check_vma=False))
    bwd = jax.jit(jax.shard_map(ring_grads, mesh=mesh,
                                in_specs=(spec,) * 4, out_specs=(spec,) * 3,
                                check_vma=False))
    out, t_fwd = _timed(fwd, qs, ks, vs)
    grads, t_bwd = _timed(bwd, qs, ks, vs, cts)

    def dense(qf, kf, vf):
        def head(a):
            qh, kh, vh = a
            s = (qh @ kh.T) / np.sqrt(d)
            s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ vh
        return jax.lax.map(head, (qf, kf, vf))

    def dense_fwd_bwd(qf, kf, vf, cf):
        o, vjp = jax.vjp(dense, qf, kf, vf)
        return o, vjp(cf)

    dev0 = jax.devices()[0]
    with jax.default_matmul_precision("float32"):
        ref_out, ref_grads = jax.jit(dense_fwd_bwd)(
            *(jax.device_put(a.astype(jnp.float32), dev0)
              for a in (q, k, v, ct)))
    errs = {"out": _rel_err(out.astype(jnp.float32), ref_out)}
    for name, g, r in zip(("dq", "dk", "dv"), grads, ref_grads):
        errs[name] = _rel_err(g.astype(jnp.float32), r)
    _check(errs["out"] <= 1e-2, f"attention forward rel err {errs['out']}")
    for name in ("dq", "dk", "dv"):
        _check(errs[name] <= 2e-2, f"attention {name} rel err {errs[name]}")
    return {"fwd_first_call_s": t_fwd, "bwd_first_call_s": t_bwd,
            "rel_err": errs}


def phase_dpmp4(mpi_tpu):
    """(g) dp x mp step on 2x2 chips, pallas_ring dp ring vs fused."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding

    import __graft_entry__ as ge

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
    rng = np.random.RandomState(0)
    shapes = ge._shapes(2, 2)
    init = [rng.randn(*s).astype(np.float32) * (0.1 if i >= 2 else 1.0)
            for i, s in enumerate(shapes)]
    results, secs = {}, {}
    for alg in ("fused", "pallas_ring"):
        step, in_specs, out_specs = ge._build_step(mesh, 2, 2,
                                                   dp_algorithm=alg)
        f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                                  out_specs=out_specs))
        x, y, w1, w2 = (jax.device_put(jnp.asarray(a), NamedSharding(mesh, s))
                        for a, s in zip(init, in_specs))
        outs = []
        for i in range(2):
            (w1, w2, loss, aux), t = _timed(f, x, y, w1, w2)
            if i == 0:
                secs[alg] = t
            outs.append([np.asarray(a) for a in (w1, w2, loss, aux)])
        results[alg] = outs
    for i, (a, b) in enumerate(zip(results["pallas_ring"], results["fused"])):
        for name, u, w in zip(("w1", "w2", "loss", "aux"), a, b):
            _check(np.all(np.isfinite(u)), f"step {i} {name} not finite")
            _check(np.allclose(u, w, rtol=1e-5, atol=1e-6),
                   f"step {i} {name}: pallas_ring differs from fused by "
                   f"{float(np.max(np.abs(u - w)))}")
    return {"first_call_s": secs,
            "loss": [float(np.ravel(o[2])[0]) for o in results["fused"]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    sys.path.insert(0, REPO)
    import mpi_tpu
    from mpi_tpu import mpit
    from mpi_tpu.tpu import TpuCommunicator, enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")

    # record every interpreter decision a communicator makes on the path
    resolved = []
    interp_prop = TpuCommunicator._pallas_interp

    def _recording(self):
        v = interp_prop.fget(self)
        resolved.append(v)
        return v

    TpuCommunicator._pallas_interp = property(_recording)

    phases = ([phase_jacobi, phase_collectives_1chip, phase_long_context]
              if args.chips == 1 else
              [phase_allreduce4, phase_ag_rs4, phase_attention4, phase_dpmp4])
    failed = []
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        before = {p: mpit.pvar_read(p)
                  for p in ("pallas_ring_fallbacks", "attention_fallbacks")}
        resolved.clear()
        t0 = time.perf_counter()
        try:
            facts = phase(mpi_tpu)
            for p, v in before.items():
                _check(mpit.pvar_read(p) == v,
                       f"pvar {p} rose {v} -> {mpit.pvar_read(p)}")
            _check(not any(resolved),
                   "a TpuCommunicator resolved to the Pallas interpreter")
        except Exception:
            failed.append(name)
            log(f"phase {name}: FAILED after "
                f"{time.perf_counter() - t0:.1f}s\n{traceback.format_exc()}")
            continue
        peak = devices[0].memory_stats() or {}
        facts["peak_bytes_in_use_dev0"] = peak.get("peak_bytes_in_use")
        log(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s "
            f"{json.dumps(facts)}")
    if failed:
        log(f"chip_smoke: failed phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
