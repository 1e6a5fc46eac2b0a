"""Compile (not export) the TPU path for a described v5e:2x2 host.

``jax.export`` stops before Mosaic compiles a kernel, so export tests pass
for kernels the chip's compiler refuses (ISSUE 21: the HBM scratch buffers
of both ring kernels).  These tests run the real compiler at real sizes
against a topology that is described, not attached (on-chip-measurement
guide §2): a refusal here costs no chip time.  Nothing executes, so they
say nothing about results or speed.

The topology is described inside a module fixture — never at import — so
collecting this file loads no TPU library, and the tests skip where no
topology can be described.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_tpu.tpu import TpuCommunicator
from mpi_tpu.tpu import pallas_ring as pr
from mpi_tpu.tpu.pallas_attention import (attention_vmem_plan,
                                          pallas_ring_attention)

MIB = 2 ** 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("world",))


def _compile(fn, *avals):
    return jax.jit(fn).lower(*avals).compile()


def _rows(mesh, nbytes_per_chip):
    """f32 [4*rows, 128] global aval, one 2-D block of rows per chip."""
    rows = nbytes_per_chip // (4 * 128)
    return rows, jax.ShapeDtypeStruct(
        (4 * rows, 128), jnp.float32,
        sharding=NamedSharding(mesh, P("world")))


def _smap(mesh, fn):
    return jax.shard_map(fn, mesh=mesh, in_specs=P("world"),
                         out_specs=P("world"))


@pytest.mark.parametrize("bidirectional", [True, False])
def test_pallas_ring_allreduce_256mib_compiles(mesh4, bidirectional):
    comm = TpuCommunicator("world", mesh4)
    assert comm._pallas_interp is False  # described chips are not a CPU
    _, x = _rows(mesh4, 256 * MIB)
    if bidirectional:
        def fn(v):
            return comm.allreduce(v, algorithm="pallas_ring")
    else:
        def fn(v):
            return pr.pallas_ring_allreduce(v, "world", 4,
                                            bidirectional=False)
    assert "tpu_custom_call" in _compile(_smap(mesh4, fn), x).as_text()


@pytest.mark.parametrize("bidirectional", [True, False])
def test_pallas_ring_allgather_64mib_compiles(mesh4, bidirectional):
    _, x = _rows(mesh4, 64 * MIB)
    compiled = _compile(_smap(mesh4, lambda v: pr.pallas_ring_allgather(
        v, "world", 4, bidirectional=bidirectional)[None]), x)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bidirectional", [True, False])
def test_pallas_ring_reduce_scatter_64mib_compiles(mesh4, bidirectional):
    rows, x = _rows(mesh4, 64 * MIB)
    compiled = _compile(_smap(mesh4, lambda v: pr.pallas_ring_reduce_scatter(
        v.reshape(4, rows // 4, 128), "world", 4,
        bidirectional=bidirectional)), x)
    assert "tpu_custom_call" in compiled.as_text()


# (plan, Sb per chip, vmem_limit_bytes): the resident plan at Sb 2048
# needs a raised VMEM limit, which the kernels hand to Mosaic; the tiled
# plan is forced at Sb 8192 by a tight one
_ATTENTION = {"resident": (2048, 100 * MIB), "tiled": (8192, 8 * MIB)}


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "fused_backward"])
@pytest.mark.parametrize("plan", sorted(_ATTENTION))
def test_ring_attention_compiles(mesh4, plan, backward):
    sb, limit = _ATTENTION[plan]
    d = 128
    assert attention_vmem_plan(sb, d, 1, 1, jnp.bfloat16, limit,
                               for_backward=backward)[0] == plan
    spec = P(None, "world")
    q = jax.ShapeDtypeStruct((1, 4 * sb, d), jnp.bfloat16,
                             sharding=NamedSharding(mesh4, spec))

    def attn(qb, kb, vb):
        return pallas_ring_attention(qb, kb, vb, "world", 4, causal=True,
                                     vmem_limit_bytes=limit)

    if backward:
        def fn(qb, kb, vb):
            _, vjp = jax.vjp(attn, qb, kb, vb)
            return vjp(jnp.ones_like(qb))
        out_specs = (spec,) * 3
    else:
        fn, out_specs = attn, spec
    compiled = _compile(jax.shard_map(fn, mesh=mesh4, in_specs=(spec,) * 3,
                                      out_specs=out_specs), q, q, q)
    assert "tpu_custom_call" in compiled.as_text()


def test_jacobi_step_8192_compiles_for_one_chip(topo):
    """The single-chip entry program (``__graft_entry__.entry``'s step) at
    the chip_smoke size: an 8192² f32 grid on one described v5e."""
    from examples.jacobi import jacobi_step
    from mpi_tpu import ops

    mesh = Mesh(np.array(topo.devices[:1]), ("world",))
    comm = TpuCommunicator("world", mesh)

    def fwd(grid):
        new = jacobi_step(comm, grid)
        return new, comm.allreduce(jnp.max(jnp.abs(new - grid)), op=ops.MAX)

    grid = jax.ShapeDtypeStruct((8192, 8192), jnp.float32,
                                sharding=NamedSharding(mesh, P("world")))
    compiled = _compile(jax.shard_map(fwd, mesh=mesh, in_specs=P("world"),
                                      out_specs=(P("world"), P())), grid)
    # in + out grids and the stencil's temporaries fit one v5e's HBM
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 30


def test_dp_mp_step_with_pallas_ring_compiles_for_2x2(topo):
    """``__graft_entry__``'s dp×mp training step with the dp gradient ring
    on the RDMA kernel (mesh-coordinate device ids) and the ring-attention
    kernel over mp, compiled for the 2x2 chips."""
    import __graft_entry__ as ge

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
    step, in_specs, out_specs = ge._build_step(mesh, 2, 2,
                                               dp_algorithm="pallas_ring")
    avals = [jax.ShapeDtypeStruct(s, jnp.float32,
                                  sharding=NamedSharding(mesh, spec))
             for s, spec in zip(ge._shapes(2, 2), in_specs)]
    compiled = _compile(jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                                      out_specs=out_specs), *avals)
    assert "tpu_custom_call" in compiled.as_text()
