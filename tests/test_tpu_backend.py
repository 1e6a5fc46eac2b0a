"""TPU backend semantic tests on the 8-device virtual CPU mesh
(SURVEY.md §4 items 2-3: all semantics validated against numpy oracles
multi-device without a TPU slice)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpi_tpu import ops
from mpi_tpu.tpu import SpmdSemanticsError, TpuCommunicator, default_mesh, run_spmd

P = 8


def data(n=P, shape=(5,), seed=0, dtype=np.float32):
    return np.asarray(np.random.RandomState(seed).randn(n, *shape), dtype)


# -- allreduce -------------------------------------------------------------


@pytest.mark.parametrize("algo", ["fused", "ring", "recursive_halving", "reduce_bcast"])
def test_allreduce_sum(algo):
    d = data(shape=(13,))  # 13 not divisible by 8: exercises padding

    def prog(comm, x):
        mine = x[comm.rank]
        return comm.allreduce(mine, op=ops.SUM, algorithm=algo)

    out = np.asarray(run_spmd(prog, d))
    for r in range(P):
        np.testing.assert_allclose(out[r], d.sum(0), rtol=1e-5)


@pytest.mark.parametrize("algo", ["fused", "ring", "recursive_halving"])
@pytest.mark.parametrize(
    "op,oracle",
    [
        (ops.MAX, lambda d: d.max(0)),
        (ops.MIN, lambda d: d.min(0)),
        (ops.PROD, lambda d: d.prod(0)),
    ],
)
def test_allreduce_ops(algo, op, oracle):
    d = data(shape=(6,), seed=3)

    def prog(comm, x):
        return comm.allreduce(x[comm.rank], op=op, algorithm=algo)

    out = np.asarray(run_spmd(prog, d))
    for r in range(P):
        np.testing.assert_allclose(out[r], oracle(d), rtol=1e-4)


def test_allreduce_int_dtype():
    d = np.arange(P * 4, dtype=np.int32).reshape(P, 4)

    def prog(comm, x):
        return comm.allreduce(x[comm.rank], algorithm="ring")

    out = np.asarray(run_spmd(prog, d))
    np.testing.assert_array_equal(out[0], d.sum(0))


# -- bcast / reduce --------------------------------------------------------


@pytest.mark.parametrize("algo", ["fused", "tree"])
@pytest.mark.parametrize("root", [0, 3, 7])
def test_bcast(algo, root):
    d = data(shape=(4,), seed=5)

    def prog(comm, x):
        mine = x[comm.rank]
        return comm.bcast(mine, root=root, algorithm=algo)

    out = np.asarray(run_spmd(prog, d))
    for r in range(P):
        np.testing.assert_allclose(out[r], d[root], rtol=1e-6)


@pytest.mark.parametrize("algo", ["fused", "tree"])
@pytest.mark.parametrize("root", [0, 5])
def test_reduce_sum_at_root(algo, root):
    d = data(shape=(4,), seed=6)

    def prog(comm, x):
        return comm.reduce(x[comm.rank], op=ops.SUM, root=root, algorithm=algo)

    out = np.asarray(run_spmd(prog, d))
    np.testing.assert_allclose(out[root], d.sum(0), rtol=1e-5)
    for r in range(P):
        if r != root:
            np.testing.assert_allclose(out[r], np.zeros(4), atol=1e-6)


@pytest.mark.parametrize("algo", ["fused", "tree"])
def test_reduce_max_identity_on_non_roots(algo):
    d = -np.abs(data(shape=(3,), seed=7))  # all negative: exposes zero-fill bugs

    def prog(comm, x):
        return comm.reduce(x[comm.rank], op=ops.MAX, root=2, algorithm=algo)

    out = np.asarray(run_spmd(prog, d))
    np.testing.assert_allclose(out[2], d.max(0), rtol=1e-5)
    assert np.all(out[[r for r in range(P) if r != 2]] == np.float32(-np.inf))


# -- allgather / alltoall --------------------------------------------------


@pytest.mark.parametrize("algo", ["fused", "ring", "doubling"])
def test_allgather(algo):
    d = data(shape=(3,), seed=8)

    def prog(comm, x):
        return comm.allgather(x[comm.rank], algorithm=algo)

    out = np.asarray(run_spmd(prog, d))
    for r in range(P):
        np.testing.assert_allclose(out[r], d, rtol=1e-6)


def test_gather_sharded_zero_comm(monkeypatch):
    """gather(sharded=True) (VERDICT r3 missing #3): each device returns
    only its [1, ...] slice; the out_spec assembles the global stack, so
    per-device HBM is O(payload) and the compiled program contains NO
    gather collective at all."""
    from jax.sharding import Mesh, PartitionSpec as P_

    mesh = default_mesh(P)
    comm = TpuCommunicator("world", mesh)
    d = data(shape=(6,), seed=31)

    def f(x):
        return comm.gather(x.reshape(6), sharded=True)

    jf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P_("world"),
                               out_specs=P_("world")))
    out = jf(jnp.asarray(d.reshape(-1)))
    np.testing.assert_allclose(np.asarray(out), d, rtol=1e-6)
    # each device holds exactly its own [1, 6] shard of the stack
    assert sorted(s.data.shape for s in out.addressable_shards) == \
        [(1, 6)] * P
    # zero communication: no collective op of any kind in the program
    hlo = jf.lower(jnp.asarray(d.reshape(-1))).as_text()
    for coll in ("all-gather", "all_gather", "all-reduce", "all_reduce",
                 "collective-permute", "all-to-all"):
        assert coll not in hlo, coll


def test_gather_sharded_misuse_fails_loudly():
    """VERDICT r4 weak #5: forgetting ``out_specs=P(axis)`` on a
    sharded-output gather must be a TYPED error, not a silently wrong
    [1, ...] slice — the slice is branded vma-varying over the axis
    even when the gathered VALUE is replicated (the contract is 'my
    slice of the stack', which is positional).  gatherv mirrors."""
    from jax.sharding import PartitionSpec as P_

    mesh = default_mesh(P)
    comm = TpuCommunicator("world", mesh)

    def f(x):
        return comm.gather(x * 0 + 1.0, sharded=True)  # replicated value

    with pytest.raises(Exception, match="(?i)vma|var[iy]|replicat|spec"):
        jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P_(),
                              out_specs=P_()))(jnp.ones(3))

    def g(x):
        return comm.gatherv(x * 0 + 1.0, [2] * P, sharded=True)

    with pytest.raises(Exception, match="(?i)vma|var[iy]|replicat|spec"):
        jax.jit(jax.shard_map(g, mesh=mesh, in_specs=P_(),
                              out_specs=P_()))(jnp.ones((2, 3)))


def test_gather_replicated_warns_above_cvar_threshold():
    """The replicated default warns (trace time) once size*payload
    exceeds the writable gather_replicated_warn_bytes cvar, naming the
    sharded spelling; igather inherits through gather."""
    from mpi_tpu import mpit

    d = data(shape=(64,), seed=32)
    old = mpit.cvar_read("gather_replicated_warn_bytes")
    mpit.cvar_write("gather_replicated_warn_bytes", 128)
    try:
        def prog(comm, x):
            return comm.gather(x[comm.rank])

        with pytest.warns(RuntimeWarning, match="sharded=True"):
            out = np.asarray(run_spmd(prog, d))
        for r in range(P):
            np.testing.assert_allclose(out[r], d, rtol=1e-6)
    finally:
        mpit.cvar_write("gather_replicated_warn_bytes", old)
    # silent below the threshold (restored default: 64 MiB)
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error", RuntimeWarning)
        np.asarray(run_spmd(prog, d))


def test_gatherv_sharded_padded_blocks_and_ragged_concat():
    """gatherv(sharded=True): per-device zero-padded own block; the
    assembled padded stack + ragged_concat equals the replicated
    gatherv's exact ragged concatenation."""
    from jax.sharding import PartitionSpec as P_

    counts = [3, 1, 2, 4, 2, 3, 1, 2]
    maxc = max(counts)
    mesh = default_mesh(P)
    comm = TpuCommunicator("world", mesh)
    rng = np.random.RandomState(33)
    # per-rank padded payloads [P, maxc, 2]
    d = np.asarray(rng.randn(P, maxc, 2), np.float32)

    def f(x):
        return comm.gatherv(x.reshape(maxc, 2), counts, sharded=True)

    jf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P_("world"),
                               out_specs=P_("world")))
    stack = np.asarray(jf(jnp.asarray(d.reshape(P * maxc, 2))))
    got = TpuCommunicator.ragged_concat(stack, counts)
    want = np.concatenate([d[r, : counts[r]] for r in range(P)], axis=0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # padding rows of each per-device block came back zeroed
    blocks = stack.reshape(P, maxc, 2)
    for r in range(P):
        np.testing.assert_array_equal(blocks[r, counts[r]:], 0.0)
    # replicated spelling agrees
    def prog(comm_, x):
        return comm_.gatherv(x[comm_.rank], counts)

    rep = np.asarray(run_spmd(prog, d))[0]
    np.testing.assert_allclose(rep, want, rtol=1e-6)


@pytest.mark.parametrize("algo", ["fused", "pairwise"])
def test_alltoall(algo):
    # block (src, dst) encoded as value src*100 + dst
    d = np.asarray(
        [[src * 100 + dst for dst in range(P)] for src in range(P)], np.float32
    )[..., None]

    def prog(comm, x):
        blocks = x[comm.rank]  # [P, 1] block dst for every dst
        return comm.alltoall(blocks, algorithm=algo)[:, 0]

    out = np.asarray(run_spmd(prog, d))
    for dst in range(P):
        np.testing.assert_array_equal(out[dst], [src * 100 + dst for src in range(P)])


# -- p2p -------------------------------------------------------------------


def test_shift_wrap():
    def prog(comm):
        return comm.shift(comm.rank.astype(jnp.float32), offset=1, wrap=True)

    out = np.asarray(run_spmd(prog)).ravel()
    np.testing.assert_array_equal(out, [(r - 1) % P for r in range(P)])


def test_shift_no_wrap_fill():
    def prog(comm):
        return comm.shift(comm.rank.astype(jnp.float32), offset=1, wrap=False, fill=-99.0)

    out = np.asarray(run_spmd(prog)).ravel()
    np.testing.assert_array_equal(out, [-99.0] + [float(r) for r in range(P - 1)])


def test_shift_negative_offset():
    def prog(comm):
        return comm.shift(comm.rank.astype(jnp.float32), offset=-1, wrap=True)

    out = np.asarray(run_spmd(prog)).ravel()
    np.testing.assert_array_equal(out, [(r + 1) % P for r in range(P)])


def test_exchange_static_pattern():
    def prog(comm):
        # 0→7 and 3→4, everyone else receives zeros
        return comm.exchange(comm.rank.astype(jnp.float32) + 1, [(0, 7), (3, 4)])

    out = np.asarray(run_spmd(prog)).ravel()
    expect = np.zeros(P)
    expect[7], expect[4] = 1.0, 4.0
    np.testing.assert_array_equal(out, expect)


def test_shift_no_wrap_requires_fill():
    comm = TpuCommunicator("world", default_mesh())
    with pytest.raises(SpmdSemanticsError, match="fill"):
        comm.shift(jnp.zeros(3), offset=1, wrap=False)  # fill=None: CPU gives None


def test_bcast_reduce_algorithm_portable():
    """algorithm= must be accepted with the same names on every backend."""
    from mpi_tpu.transport.local import run_local

    def prog(comm):
        a = comm.bcast(np.arange(3.0) if comm.rank == 0 else None, root=0,
                       algorithm="fused")
        b = comm.reduce(np.float32(comm.rank), root=0, algorithm="fused")
        return a, b

    res = run_local(prog, 4)
    np.testing.assert_array_equal(res[1][0], np.arange(3.0))
    assert float(res[0][1]) == 6.0


def test_send_raises_spmd_diagnostic():
    comm = TpuCommunicator("world", default_mesh())
    with pytest.raises(SpmdSemanticsError, match="shift"):
        comm.send(1, dest=0)
    with pytest.raises(SpmdSemanticsError):
        comm.recv()
    with pytest.raises(SpmdSemanticsError):
        comm.sendrecv(1, dest=0)
    with pytest.raises(SpmdSemanticsError):
        comm.split(color=0)


# -- split -----------------------------------------------------------------


def test_split_parity_groups():
    mesh = default_mesh()
    world = TpuCommunicator("world", mesh)
    sub = world.split_by(lambda i: i % 2)
    assert sub.size == 4
    assert sub.axis_index_groups == [[0, 2, 4, 6], [1, 3, 5, 7]]

    def prog(comm):
        # comm is the world; use the pre-split sub inside the same trace
        return sub.allreduce(comm.rank.astype(jnp.float32), algorithm="ring")

    out = np.asarray(run_spmd(prog, mesh=mesh)).ravel()
    np.testing.assert_array_equal(out, [12.0, 16.0] * 4)


@pytest.mark.parametrize("algo", ["fused", "ring", "recursive_halving"])
def test_split_grouped_collectives(algo):
    mesh = default_mesh()
    world = TpuCommunicator("world", mesh)
    rows = world.split_by(lambda i: i // 4)  # [[0,1,2,3],[4,5,6,7]]
    d = data(shape=(9,), seed=11)

    def prog(comm, x):
        return rows.allreduce(x[comm.rank], op=ops.SUM, algorithm=algo)

    out = np.asarray(run_spmd(prog, d, mesh=mesh))
    for r in range(P):
        grp = range(0, 4) if r < 4 else range(4, 8)
        np.testing.assert_allclose(out[r], d[list(grp)].sum(0), rtol=1e-4, atol=1e-6)


def test_split_key_reorders():
    world = TpuCommunicator("world", default_mesh())
    sub = world.split_all([0] * P, keys=list(range(P - 1, -1, -1)))
    assert sub.axis_index_groups == [[7, 6, 5, 4, 3, 2, 1, 0]]


def test_nested_split():
    world = TpuCommunicator("world", default_mesh())
    rows = world.split_by(lambda i: i // 4)
    cols_of_rows = rows.split_by(lambda i: i % 2)
    assert cols_of_rows.axis_index_groups == [[0, 2], [1, 3], [4, 6], [5, 7]]

    def prog(comm):
        return cols_of_rows.allgather(comm.rank.astype(jnp.float32))

    out = np.asarray(run_spmd(prog))
    np.testing.assert_array_equal(out[0], [0.0, 2.0])
    np.testing.assert_array_equal(out[5], [5.0, 7.0])


def test_split_unequal_groups_rejected():
    world = TpuCommunicator("world", default_mesh())
    with pytest.raises(ValueError, match="equal-sized"):
        world.split_all([0, 0, 0, 1, 1, 1, 1, 1])


def test_split_none_color_rejected():
    world = TpuCommunicator("world", default_mesh())
    with pytest.raises(ValueError, match="color"):
        world.split_all([None, 0, 0, 0, 0, 0, 0, 1])


# -- misc ------------------------------------------------------------------


def test_barrier_traces():
    def prog(comm):
        comm.barrier()
        return comm.rank

    out = np.asarray(run_spmd(prog)).ravel()
    np.testing.assert_array_equal(out, np.arange(P))


def test_scatter():
    d = np.arange(P * P, dtype=np.float32).reshape(P, P)

    def prog(comm, x):
        blocks = jnp.where(comm.rank == 3, x, jnp.zeros_like(x))  # only root has data
        return comm.scatter(blocks, root=3)

    out = np.asarray(run_spmd(prog, d))
    np.testing.assert_array_equal(out.ravel(), d.ravel())


def test_run_spmd_requires_enough_devices():
    with pytest.raises(ValueError, match="devices"):
        default_mesh(100)


def test_grouped_shift_stays_in_group():
    world = TpuCommunicator("world", default_mesh())
    rows = world.split_by(lambda i: i // 4)

    def prog(comm):
        return rows.shift(comm.rank.astype(jnp.float32), offset=1, wrap=True)

    out = np.asarray(run_spmd(prog)).ravel()
    # within [0..3]: comes from (grank-1)%4 of same group; same for [4..7]
    np.testing.assert_array_equal(out, [3, 0, 1, 2, 7, 4, 5, 6])


def test_enable_compile_cache_placement(tmp_path):
    """Chip programs' compile cache: JAX_COMPILATION_CACHE_DIR where set
    (and no other directory), else the fixed ``.jax_cache`` of the
    checkout.  Runs in children: the suite itself keeps the cache off."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import jax, jax.numpy as jnp\n"
            "from mpi_tpu.tpu import enable_compile_cache\n"
            "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.ones(3)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                         capture_output=True, text=True, check=True)
    assert out.stdout.split() == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir())
    del env["JAX_COMPILATION_CACHE_DIR"]
    out = subprocess.run(
        [sys.executable, "-c", code.rsplit("jax.jit", 1)[0]], env=env,
        cwd=repo, capture_output=True, text=True, check=True)
    assert out.stdout.split() == [os.path.join(repo, ".jax_cache")] * 2
