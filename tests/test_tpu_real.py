"""Real-TPU test tier (SURVEY.md §4 item 3; VERDICT round 1 next-step #2).

Run with::

    MPI_TPU_TEST_TPU=1 python -m pytest -m tpu tests/test_tpu_real.py

(the env var stops conftest.py pinning the CPU platform).  Two families:

* **P=1 degenerate semantics** — every collective × algorithm executes on
  the single real chip and returns the mathematically-degenerate result.
* **AOT lowering for P=8** — the 8-device SPMD programs (every hand
  schedule AND the pipelined Pallas ring, ``interpret=False``) are traced
  and lowered against an 8-device AbstractMesh on the TPU backend.  This
  exercises the pallas→Mosaic lowering of the pipelined path — the code
  the interpreter tier never touches — without needing 8 chips.

Without a TPU attached every test here self-skips.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, Mesh, PartitionSpec as P

from mpi_tpu import ops
from mpi_tpu.tpu import TpuCommunicator

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module", autouse=True)
def _real_tpu():
    """Every test here needs the chip.  Asked in a fixture, not at import,
    so that collecting this file never initializes a backend."""
    if not any(d.platform == "tpu" for d in jax.devices()):
        pytest.skip("no real TPU attached (run with MPI_TPU_TEST_TPU=1)")


def _mesh1() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]), ("world",))


def _run1(fn):
    """Run fn(comm, x) on the real chip, P=1."""
    mesh = _mesh1()
    comm = TpuCommunicator("world", mesh)
    x = jnp.arange(8.0, dtype=jnp.float32)
    f = jax.jit(jax.shard_map(lambda v: fn(comm, v), mesh=mesh,
                              in_specs=P(), out_specs=P("world")))
    return np.asarray(f(x)), np.arange(8.0, dtype=np.float32)


# ---- P=1 degenerate semantics on the real chip ---------------------------


@pytest.mark.parametrize("algorithm", ["fused", "ring", "recursive_halving",
                                       "reduce_bcast"])
def test_allreduce_degenerate(algorithm):
    got, x = _run1(lambda c, v: c.allreduce(v, algorithm=algorithm)[None])
    np.testing.assert_allclose(got[0], x)


@pytest.mark.parametrize("algorithm", ["fused", "tree"])
def test_bcast_reduce_degenerate(algorithm):
    got, x = _run1(lambda c, v: c.bcast(v, 0, algorithm)[None])
    np.testing.assert_allclose(got[0], x)
    got, x = _run1(lambda c, v: c.reduce(v, ops.MAX, 0, algorithm)[None])
    np.testing.assert_allclose(got[0], x)


@pytest.mark.parametrize("algorithm", ["fused", "ring", "doubling"])
def test_allgather_degenerate(algorithm):
    got, x = _run1(lambda c, v: c.allgather(v, algorithm=algorithm))
    np.testing.assert_allclose(got.reshape(-1), x)


@pytest.mark.parametrize("algorithm", ["fused", "pairwise"])
def test_alltoall_degenerate(algorithm):
    got, x = _run1(
        lambda c, v: c.alltoall(v.reshape(1, 8), algorithm=algorithm))
    np.testing.assert_allclose(got.reshape(-1), x)


def test_reduce_scatter_scan_degenerate():
    got, x = _run1(lambda c, v: c.reduce_scatter(v.reshape(1, 8))[None])
    np.testing.assert_allclose(got[0], x)
    got, x = _run1(lambda c, v: c.scan(v)[None])
    np.testing.assert_allclose(got[0], x)


def test_entry_compiles_on_chip():
    """The driver's single-chip compile check, as a test."""
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)


# ---- AOT lowering of the 8-device programs (1 chip is enough) ------------


def _lower8(fn, *avals, check_vma=True):
    """Trace + lower an 8-device shard_map program for the TPU backend."""
    amesh = AbstractMesh((8,), ("world",))
    comm = TpuCommunicator("world", amesh)
    f = jax.jit(jax.shard_map(lambda *a: fn(comm, *a), mesh=amesh,
                              in_specs=P("world"), out_specs=P("world"),
                              check_vma=check_vma))
    return f.lower(*avals)


@pytest.mark.parametrize("algorithm", ["fused", "ring", "recursive_halving"])
def test_allreduce8_lowers(algorithm):
    _lower8(lambda c, v: c.allreduce(v, algorithm=algorithm),
            jax.ShapeDtypeStruct((8, 1024), jnp.float32))


@pytest.mark.parametrize("algorithm", ["tree", "fused"])
def test_tree8_lowers(algorithm):
    _lower8(lambda c, v: c.bcast(v, 3, algorithm),
            jax.ShapeDtypeStruct((8, 256), jnp.float32))
    _lower8(lambda c, v: c.reduce(v, ops.SUM, 2, algorithm),
            jax.ShapeDtypeStruct((8, 256), jnp.float32))


@pytest.mark.parametrize("algorithm", ["pairwise", "fused"])
def test_alltoall8_lowers(algorithm):
    _lower8(lambda c, v: c.alltoall(v.reshape(8, 32), algorithm=algorithm),
            jax.ShapeDtypeStruct((8, 8 * 32), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_ring8_lowers_pipelined(dtype):
    """THE coverage the interpreter tier cannot give: the pipelined
    (interpret=False) Pallas kernel — credits, wait_send hygiene, segment
    RDMAs — lowers through Mosaic for an 8-device ring."""
    from mpi_tpu.tpu import pallas_ring as pr
    from mpi_tpu.tpu.pallas_ring import pallas_ring_allreduce

    # per-rank 32768 elems → 256 rows → 4 tiles of 64 → 4 SEGMENTS, so the
    # per-(parity, seg) semaphore indexing and cross-segment credits all
    # go through Mosaic (a 1-segment shape would skip that machinery)
    n = 8 * 256 * 128
    rows = pr._geometry(n, 8, 64)[0]
    assert len(pr._segments(rows // 64)) == 4, "shape no longer multi-segment"
    for check_vma in (False, True):
        _lower8(lambda c, v: pallas_ring_allreduce(v.reshape(-1), "world", 8,
                                                   tile_rows=64),
                jax.ShapeDtypeStruct((8, n // 8), dtype),
                check_vma=check_vma)


def test_pallas_reduce_scatter8_lowers_pipelined():
    from mpi_tpu.tpu.pallas_ring import pallas_ring_reduce_scatter

    for check_vma in (False, True):
        _lower8(lambda c, v: pallas_ring_reduce_scatter(
                    v.reshape(8, 1024), "world", 8),
                jax.ShapeDtypeStruct((8, 8 * 1024), jnp.float32),
                check_vma=check_vma)


def test_dryrun_step8_lowers():
    """The driver's multichip dryrun program lowers for 8 TPU devices."""
    import __graft_entry__ as ge

    lowered = ge.lower_multichip(8)
    assert lowered is not None


def test_pallas_ring8_grouped_lowers_pipelined():
    """The grouped (split-communicator) pipelined kernel — SMEM neighbor
    params, per-group rings — lowers through Mosaic."""
    from mpi_tpu.tpu.pallas_ring import pallas_ring_allreduce

    groups = [[0, 2, 4, 6], [1, 3, 5, 7]]
    _lower8(lambda c, v: pallas_ring_allreduce(
                v.reshape(-1), "world", 4, tile_rows=64, groups=groups),
            jax.ShapeDtypeStruct((8, 64 * 128), jnp.float32),
            check_vma=False)


def test_pallas_ring8_max_lowers_pipelined():
    """The swapped-combiner (MAX) pipelined kernel lowers through Mosaic."""
    from mpi_tpu.tpu.pallas_ring import pallas_ring_allreduce

    _lower8(lambda c, v: pallas_ring_allreduce(
                v.reshape(-1), "world", 8, tile_rows=64, op="max"),
            jax.ShapeDtypeStruct((8, 64 * 128), jnp.float32),
            check_vma=False)


def test_pallas_allgather8_lowers_pipelined():
    """The allgather-only kernel mode (rs=False: zero RS steps, P-1
    land-direct steps) lowers through Mosaic for an 8-device ring."""
    from mpi_tpu.tpu.pallas_ring import pallas_ring_allgather

    for check_vma in (False, True):
        _lower8(lambda c, v: pallas_ring_allgather(
                    v.reshape(-1), "world", 8, tile_rows=64),
                jax.ShapeDtypeStruct((8, 64 * 128 * 4), jnp.float32),
                check_vma=check_vma)


@pytest.mark.parametrize("ring_axis", ["mp", "dp"])
def test_pallas_ring_multiaxis_lowers_on_tpu_backend(ring_axis):
    """Round 4 (VERDICT r3 missing #2): the multi-axis kernel —
    dict-MESH RDMA addressing over one axis of a 2-D (dp×mp) mesh —
    lowers through Mosaic ON THE TPU BACKEND (the CPU tier proves the
    same via cross-platform jax.export; this is the silicon-side twin)."""
    from mpi_tpu.tpu.pallas_ring import pallas_ring_allreduce

    amesh = AbstractMesh((2, 4), ("dp", "mp"))
    size = dict(zip(amesh.axis_names, amesh.axis_sizes))[ring_axis]
    f = jax.jit(jax.shard_map(
        lambda v: pallas_ring_allreduce(v, ring_axis, size, tile_rows=64),
        mesh=amesh, in_specs=P("dp", "mp"), out_specs=P("dp", "mp"),
        check_vma=False))
    f.lower(jax.ShapeDtypeStruct((16, 4 * 64 * 128), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_attention8_lowers_pipelined(dtype):
    """The fused ring-attention kernel (round 4: K/V circulation with
    slot credits + in-kernel online-softmax folds) lowers through
    Mosaic for an 8-device ring on the TPU backend."""
    from mpi_tpu.tpu.pallas_attention import pallas_ring_attention

    amesh = AbstractMesh((8,), ("s",))
    for check_vma in (False, True):
        f = jax.jit(jax.shard_map(
            lambda q, k, v: pallas_ring_attention(q, k, v, "s", 8,
                                                  interpret=False),
            mesh=amesh, in_specs=(P("s"),) * 3, out_specs=P("s"),
            check_vma=check_vma))
        aval = jax.ShapeDtypeStruct((8 * 64, 128), dtype)
        f.lower(aval, aval, aval)


def test_pallas_attention_size1_executes_on_chip():
    """P=1 degenerate ring attention executes on the real chip and
    matches local attention."""
    from mpi_tpu.tpu.pallas_attention import pallas_ring_attention

    mesh = _mesh1()
    rng = np.random.RandomState(2)
    q = rng.randn(8, 128).astype(np.float32)
    f = jax.jit(jax.shard_map(
        lambda qb: pallas_ring_attention(qb, qb, qb, "world", 1),
        mesh=mesh, in_specs=P("world"), out_specs=P("world")))
    got = np.asarray(f(jnp.asarray(q)))
    s = (q @ q.T) / np.sqrt(128)
    p = np.exp(s - s.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    np.testing.assert_allclose(got, p @ q, rtol=2e-4, atol=2e-5)


def test_dryrun_step8_pallas_ring_lowers():
    """The multichip dryrun variant whose dp gradient ring runs the
    in-kernel RDMA pallas_ring lowers for 8 TPU devices — the VERDICT
    r3 done-criterion, on the real backend."""
    import __graft_entry__ as ge

    lowered = ge.lower_multichip(8, dp_algorithm="pallas_ring")
    assert lowered is not None
