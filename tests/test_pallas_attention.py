"""Fused Pallas ring attention (mpi_tpu/tpu/pallas_attention.py):
interpreter parity vs a dense-softmax oracle, loud fallbacks, and
cross-platform TPU export of the RDMA kernel (1-D + multi-axis meshes,
f32/bf16, vma on/off).  The circulation protocol itself is verified by
ring_model.AttentionSim (tests/test_pallas_protocol.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from mpi_tpu.tpu import default_mesh
from mpi_tpu.tpu.pallas_attention import pallas_ring_attention


def _oracle(q, k, v, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[1])
    s = (q.astype(np.float32) @ k.astype(np.float32).T) * scale
    p = np.exp(s - s.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    return p @ v.astype(np.float32)


def _run(Pn, Sb, d, dtype=np.float32, seed=0, **kw):
    rng = np.random.RandomState(seed)
    q = rng.randn(Pn * Sb, d).astype(dtype)
    k = rng.randn(Pn * Sb, d).astype(dtype)
    v = rng.randn(Pn * Sb, d).astype(dtype)
    mesh = default_mesh(Pn)

    def f(qb, kb, vb):
        return pallas_ring_attention(qb, kb, vb, "world", Pn,
                                     interpret=True, **kw)

    jf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("world"),) * 3,
                               out_specs=P("world"), check_vma=False))
    got = np.asarray(jf(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)),
                     np.float32)
    return got, _oracle(q, k, v, kw.get("scale"))


@pytest.mark.parametrize("Pn,Sb,d", [(2, 8, 128), (3, 8, 128),
                                     (4, 16, 128), (8, 8, 256)])
def test_interpreter_parity(Pn, Sb, d):
    """The kernel's serial-RDMA interpreter path is EXACT attention:
    online-softmax over circulating K/V blocks == dense softmax."""
    got, want = _run(Pn, Sb, d)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_interpreter_parity_bf16():
    got, want = _run(4, 16, 128, dtype=jnp.bfloat16)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


def test_custom_scale():
    got, want = _run(2, 8, 128, scale=0.25)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_size_one_degenerates_to_local_attention():
    rng = np.random.RandomState(3)
    q = rng.randn(8, 128).astype(np.float32)
    mesh = default_mesh(1)

    def f(qb):
        return pallas_ring_attention(qb, qb, qb, "world", 1, interpret=True)

    got = np.asarray(jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("world"), out_specs=P("world"),
        check_vma=False))(jnp.asarray(q)))
    np.testing.assert_allclose(got, _oracle(q, q, q), rtol=2e-4, atol=2e-5)


def test_vma_fallback_warns_and_matches():
    """Under the default check_vma=True the interpreter takes the
    ppermute online-softmax fallback — loudly, and numerically
    identically."""
    Pn, Sb, d = 4, 8, 128
    rng = np.random.RandomState(5)
    q = rng.randn(Pn * Sb, d).astype(np.float32)
    mesh = default_mesh(Pn)

    def f(qb):
        return pallas_ring_attention(qb, qb, qb, "world", Pn,
                                     interpret=True)

    jf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("world"),
                               out_specs=P("world")))  # check_vma default
    with pytest.warns(RuntimeWarning, match="ppermute ring fallback"):
        got = np.asarray(jf(jnp.asarray(q)))
    np.testing.assert_allclose(got, _oracle(q, q, q), rtol=2e-4, atol=2e-5)


def test_multiaxis_interpreter_fallback_parity():
    """Ring over the sp axis of a 2-D (dp×sp) mesh on the interpreter:
    the fallback reduces per-dp-slice, matching a per-slice oracle."""
    import numpy as np_

    from jax.sharding import Mesh

    devs = np_.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dp", "sp"))
    Sb, d = 8, 128
    rng = np_.random.RandomState(7)
    # [dp=2 slices, sp-sharded sequence of 4*Sb rows, d]
    q = rng.randn(2, 4 * Sb, d).astype(np_.float32)
    k = rng.randn(2, 4 * Sb, d).astype(np_.float32)
    v = rng.randn(2, 4 * Sb, d).astype(np_.float32)

    def f(qb, kb, vb):
        return pallas_ring_attention(qb[0], kb[0], vb[0], "sp", 4,
                                     interpret=True)[None]

    jf = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P("dp", "sp", None),) * 3,
        out_specs=P("dp", "sp", None), check_vma=False))
    with pytest.warns(RuntimeWarning, match="ppermute ring fallback"):
        got = np.asarray(jf(*(jnp.asarray(a) for a in (q, k, v))))
    for sl in range(2):
        np.testing.assert_allclose(
            got[sl], _oracle(q[sl], k[sl], v[sl]), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("dtype,vma", [(jnp.float32, False),
                                       (jnp.float32, True),
                                       (jnp.bfloat16, False)])
def test_export_tpu_1d(dtype, vma):
    """The compiled RDMA kernel (credits, slot circulation, online fold)
    lowers through Mosaic for the TPU target from this host."""
    mesh = AbstractMesh((8,), ("s",))

    def f(q, k, v):
        return pallas_ring_attention(q, k, v, "s", 8, interpret=False)

    jf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("s"),) * 3,
                               out_specs=P("s"), check_vma=vma))
    aval = jax.ShapeDtypeStruct((8 * 64, 128), dtype)
    exp = jax.export.export(jf, platforms=["tpu"])(aval, aval, aval)
    assert "tpu_custom_call" in exp.mlir_module()


def test_export_tpu_multiaxis():
    """Sequence parallelism inside a 2-D training mesh: the kernel
    addresses its ring neighbors by mesh coordinate (same dict-MESH
    scheme as pallas_ring) and lowers for TPU."""
    mesh = AbstractMesh((2, 4), ("dp", "sp"))

    def f(q, k, v):
        return pallas_ring_attention(q, k, v, "sp", 4, interpret=False)

    jf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(("dp", "sp")),) * 3,
                               out_specs=P(("dp", "sp")), check_vma=False))
    aval = jax.ShapeDtypeStruct((8 * 64, 128), jnp.float32)
    exp = jax.export.export(jf, platforms=["tpu"])(aval, aval, aval)
    assert "tpu_custom_call" in exp.mlir_module()


def test_shape_diagnostics():
    mesh = default_mesh(2)

    def run(q_shape, kv_shape=None, **kw):
        kv_shape = kv_shape or q_shape

        def f(qb):
            q = jnp.zeros(q_shape, jnp.float32)
            kv = jnp.zeros(kv_shape, jnp.float32)
            return pallas_ring_attention(q, kv, kv, "world", 2,
                                         interpret=True, **kw)

        jax.jit(jax.shard_map(lambda x: f(x)[:0], mesh=mesh,
                              in_specs=P("world"), out_specs=P("world"),
                              check_vma=False))(jnp.zeros(2, jnp.float32))

    with pytest.raises(NotImplementedError, match="multiple of 128"):
        run((8, 64))
    with pytest.raises(NotImplementedError, match="sublane"):
        run((9, 128))
    with pytest.raises(ValueError, match="equal"):
        run((8, 128), (16, 128))


def test_mixed_dtype_diagnosed():
    mesh = default_mesh(2)

    def f(x):
        q = jnp.zeros((8, 128), jnp.float32)
        k = jnp.zeros((8, 128), jnp.bfloat16)
        return pallas_ring_attention(q, k, k, "world", 2, interpret=True)

    with pytest.raises(ValueError, match="one dtype"):
        jax.jit(jax.shard_map(lambda x: f(x)[:0], mesh=mesh,
                              in_specs=P("world"), out_specs=P("world"),
                              check_vma=False))(jnp.zeros(2, jnp.float32))


def _causal_oracle(q, k, v):
    s = (q.astype(np.float32) @ k.astype(np.float32).T) / np.sqrt(q.shape[1])
    n = s.shape[0]
    s = np.where(np.tril(np.ones((n, n), bool)), s, -np.inf)
    p = np.exp(s - s.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    return p @ v.astype(np.float32)


@pytest.mark.parametrize("Pn,Sb,d", [(2, 8, 128), (4, 16, 128),
                                     (3, 8, 128)])
def test_causal_interpreter_parity(Pn, Sb, d):
    """causal=True masks by GLOBAL position across the sharded sequence
    (block indices from the SMEM params): kernel == dense causal
    oracle.  The first fold is the own (diagonal) block, so the running
    max is finite from step 0 — no NaN path through the -1e30 mask."""
    rng = np.random.RandomState(Pn)
    q = rng.randn(Pn * Sb, d).astype(np.float32)
    k = rng.randn(Pn * Sb, d).astype(np.float32)
    v = rng.randn(Pn * Sb, d).astype(np.float32)
    mesh = default_mesh(Pn)
    jf = jax.jit(jax.shard_map(
        lambda qb, kb, vb: pallas_ring_attention(
            qb, kb, vb, "world", Pn, causal=True, interpret=True),
        mesh=mesh, in_specs=(P("world"),) * 3, out_specs=P("world"),
        check_vma=False))
    got = np.asarray(jf(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(got, _causal_oracle(q, k, v), rtol=2e-4,
                               atol=2e-5)


def test_causal_fallback_and_size1():
    """The ppermute fallback (vma on) and the P=1 degenerate path apply
    the same causal mask."""
    Pn, Sb, d = 4, 8, 128
    rng = np.random.RandomState(11)
    q = rng.randn(Pn * Sb, d).astype(np.float32)
    mesh = default_mesh(Pn)
    jf = jax.jit(jax.shard_map(
        lambda qb: pallas_ring_attention(qb, qb, qb, "world", Pn,
                                         causal=True, interpret=True),
        mesh=mesh, in_specs=P("world"), out_specs=P("world")))
    with pytest.warns(RuntimeWarning, match="ppermute ring fallback"):
        got = np.asarray(jf(jnp.asarray(q)))
    np.testing.assert_allclose(got, _causal_oracle(q, q, q), rtol=2e-4,
                               atol=2e-5)

    q1 = q[:Sb]
    mesh1 = default_mesh(1)
    got1 = np.asarray(jax.jit(jax.shard_map(
        lambda qb: pallas_ring_attention(qb, qb, qb, "world", 1,
                                         causal=True, interpret=True),
        mesh=mesh1, in_specs=P("world"), out_specs=P("world"),
        check_vma=False))(jnp.asarray(q1)))
    np.testing.assert_allclose(got1, _causal_oracle(q1, q1, q1), rtol=2e-4,
                               atol=2e-5)


def test_causal_export_tpu():
    mesh = AbstractMesh((8,), ("s",))
    jf = jax.jit(jax.shard_map(
        lambda q, k, v: pallas_ring_attention(q, k, v, "s", 8, causal=True,
                                              interpret=False),
        mesh=mesh, in_specs=(P("s"),) * 3, out_specs=P("s"),
        check_vma=False))
    aval = jax.ShapeDtypeStruct((8 * 64, 128), jnp.float32)
    exp = jax.export.export(jf, platforms=["tpu"])(aval, aval, aval)
    assert "tpu_custom_call" in exp.mlir_module()


# -- multi-head / GQA --------------------------------------------------------


@pytest.mark.parametrize("Hq,Hkv", [(2, 2), (4, 2), (4, 1)])
@pytest.mark.parametrize("causal", [False, True])
def test_multihead_gqa_parity(Hq, Hkv, causal):
    """[H, Sb, dh] blocks: query head h attends K/V head h//(Hq//Hkv);
    all heads ride ONE circulating RDMA.  Kernel == per-head dense
    oracle, full and causal, MHA/GQA/MQA layouts."""
    Pn, Sb, d = 4, 8, 128
    rng = np.random.RandomState(Hq * 10 + Hkv)
    q = rng.randn(Hq, Pn * Sb, d).astype(np.float32)
    k = rng.randn(Hkv, Pn * Sb, d).astype(np.float32)
    v = rng.randn(Hkv, Pn * Sb, d).astype(np.float32)
    mesh = default_mesh(Pn)
    jf = jax.jit(jax.shard_map(
        lambda qb, kb, vb: pallas_ring_attention(
            qb, kb, vb, "world", Pn, causal=causal, interpret=True),
        mesh=mesh, in_specs=(P(None, "world"),) * 3,
        out_specs=P(None, "world"), check_vma=False))
    got = np.asarray(jf(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    g = Hq // Hkv
    orc = _causal_oracle if causal else _oracle
    for h in range(Hq):
        np.testing.assert_allclose(got[h], orc(q[h], k[h // g], v[h // g]),
                                   rtol=2e-4, atol=2e-5)


def test_multihead_fallback_and_size1():
    """The vma/multi-axis fallback and P=1 path honor the GQA head
    mapping too."""
    Hq, Hkv, Sb, d = 4, 2, 8, 128
    rng = np.random.RandomState(21)
    q = rng.randn(Hq, 4 * Sb, d).astype(np.float32)
    k = rng.randn(Hkv, 4 * Sb, d).astype(np.float32)
    v = rng.randn(Hkv, 4 * Sb, d).astype(np.float32)
    mesh = default_mesh(4)
    jf = jax.jit(jax.shard_map(
        lambda qb, kb, vb: pallas_ring_attention(qb, kb, vb, "world", 4,
                                                 interpret=True),
        mesh=mesh, in_specs=(P(None, "world"),) * 3,
        out_specs=P(None, "world")))  # check_vma default → fallback
    with pytest.warns(RuntimeWarning, match="ppermute ring fallback"):
        got = np.asarray(jf(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    for h in range(Hq):
        np.testing.assert_allclose(got[h], _oracle(q[h], k[h // 2], v[h // 2]),
                                   rtol=2e-4, atol=2e-5)

    mesh1 = default_mesh(1)
    q1, k1, v1 = q[:, :Sb], k[:, :Sb], v[:, :Sb]
    got1 = np.asarray(jax.jit(jax.shard_map(
        lambda qb, kb, vb: pallas_ring_attention(qb, kb, vb, "world", 1,
                                                 interpret=True),
        mesh=mesh1, in_specs=(P(None, "world"),) * 3,
        out_specs=P(None, "world"), check_vma=False))(
        jnp.asarray(q1), jnp.asarray(k1), jnp.asarray(v1)))
    for h in range(Hq):
        np.testing.assert_allclose(got1[h], _oracle(q1[h], k1[h // 2],
                                                    v1[h // 2]),
                                   rtol=2e-4, atol=2e-5)


def test_multihead_export_tpu():
    mesh = AbstractMesh((8,), ("s",))
    jf = jax.jit(jax.shard_map(
        lambda q, k, v: pallas_ring_attention(q, k, v, "s", 8, causal=True,
                                              interpret=False),
        mesh=mesh, in_specs=(P(None, "s"),) * 3, out_specs=P(None, "s"),
        check_vma=False))
    a_q = jax.ShapeDtypeStruct((4, 8 * 32, 128), jnp.float32)
    a_kv = jax.ShapeDtypeStruct((2, 8 * 32, 128), jnp.float32)
    exp = jax.export.export(jf, platforms=["tpu"])(a_q, a_kv, a_kv)
    assert "tpu_custom_call" in exp.mlir_module()


def test_gqa_shape_diagnostics():
    mesh = default_mesh(2)

    def run(qs, kvs):
        def f(x):
            q = jnp.zeros(qs, jnp.float32)
            kv = jnp.zeros(kvs, jnp.float32)
            return pallas_ring_attention(q, kv, kv, "world", 2,
                                         interpret=True)

        jax.jit(jax.shard_map(lambda x: jnp.ravel(f(x))[:0], mesh=mesh,
                              in_specs=P("world"), out_specs=P("world"),
                              check_vma=False))(jnp.zeros(2, jnp.float32))

    with pytest.raises(ValueError, match="multiple of Hkv"):
        run((3, 8, 128), (2, 8, 128))
    with pytest.raises(ValueError, match="multiple of Hkv"):
        run((2, 8, 128), (4, 8, 128))  # more kv heads than q heads


# -- differentiability (custom_vjp: fused forward, recompute backward) -------


@pytest.mark.parametrize("causal", [False, True])
def test_grad_matches_reference(causal):
    """jax.grad flows through the KERNEL path (custom_vjp: backward
    recomputes via the pure-jax ring): gradients equal those of the
    reference implementation differentiated directly."""
    from mpi_tpu.tpu.pallas_attention import _fallback_attention

    Pn, Sb, d = 4, 8, 128
    rng = np.random.RandomState(13)
    q = rng.randn(Pn * Sb, d).astype(np.float32)
    k = rng.randn(Pn * Sb, d).astype(np.float32)
    v = rng.randn(Pn * Sb, d).astype(np.float32)
    ct = rng.randn(Pn * Sb, d).astype(np.float32)  # nontrivial cotangent
    mesh = default_mesh(Pn)

    def loss_kernel(qb, kb, vb, ctb):
        out = pallas_ring_attention(qb, kb, vb, "world", Pn,
                                    causal=causal, interpret=True)
        return jnp.sum(out * ctb)

    def loss_ref(qb, kb, vb, ctb):
        out = _fallback_attention(qb, kb, vb, "world", Pn,
                                  1.0 / np.sqrt(d), causal)
        return jnp.sum(out * ctb)

    grads = {}
    for name, fn in (("kernel", loss_kernel), ("ref", loss_ref)):
        g = jax.jit(jax.shard_map(
            jax.grad(fn, argnums=(0, 1, 2)), mesh=mesh,
            in_specs=(P("world"),) * 4, out_specs=(P("world"),) * 3,
            check_vma=False))(*map(jnp.asarray, (q, k, v, ct)))
        grads[name] = [np.asarray(x) for x in g]
    for gk, gr in zip(grads["kernel"], grads["ref"]):
        np.testing.assert_allclose(gk, gr, rtol=2e-4, atol=2e-5)
    assert any(np.abs(g).max() > 0 for g in grads["kernel"])


def test_grad_gqa_accumulates_over_group():
    """GQA backward: dK/dV for one K/V head accumulate contributions
    from every query head in its group (jax.vjp does the summing)."""
    Pn, Hq, Hkv, Sb, d = 2, 4, 2, 8, 128
    rng = np.random.RandomState(17)
    q = rng.randn(Hq, Pn * Sb, d).astype(np.float32)
    k = rng.randn(Hkv, Pn * Sb, d).astype(np.float32)
    v = rng.randn(Hkv, Pn * Sb, d).astype(np.float32)
    mesh = default_mesh(Pn)

    def loss(qb, kb, vb):
        out = pallas_ring_attention(qb, kb, vb, "world", Pn,
                                    interpret=True)
        return jnp.sum(out ** 2)

    gq, gk, gv = jax.jit(jax.shard_map(
        jax.grad(loss, argnums=(0, 1, 2)), mesh=mesh,
        in_specs=(P(None, "world"),) * 3,
        out_specs=(P(None, "world"),) * 3,
        check_vma=False))(*map(jnp.asarray, (q, k, v)))
    assert np.asarray(gq).shape == q.shape
    assert np.asarray(gk).shape == k.shape
    assert np.abs(np.asarray(gk)).max() > 0
    assert np.abs(np.asarray(gv)).max() > 0


def test_grad_export_tpu():
    """value_and_grad of the kernel path lowers for TPU: fused Mosaic
    forward + XLA-collective backward in one exported program."""
    mesh = AbstractMesh((8,), ("s",))

    def loss(q, k, v):
        out = pallas_ring_attention(q, k, v, "s", 8, causal=True,
                                    interpret=False)
        return jnp.sum(out ** 2)

    jf = jax.jit(jax.shard_map(
        lambda q, k, v: jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v),
        mesh=mesh, in_specs=(P("s"),) * 3,
        out_specs=(P(), (P("s"),) * 3), check_vma=False))
    aval = jax.ShapeDtypeStruct((8 * 32, 128), jnp.float32)
    exp = jax.export.export(jf, platforms=["tpu"])(aval, aval, aval)
    assert "tpu_custom_call" in exp.mlir_module()


def test_zero_kv_heads_diagnosed():
    mesh = default_mesh(2)

    def f(x):
        q = jnp.zeros((4, 8, 128), jnp.float32)
        kv = jnp.zeros((0, 8, 128), jnp.float32)
        return pallas_ring_attention(q, kv, kv, "world", 2, interpret=True)

    with pytest.raises(ValueError, match="positive multiple"):
        jax.jit(jax.shard_map(lambda x: jnp.ravel(f(x))[:0], mesh=mesh,
                              in_specs=P("world"), out_specs=P("world"),
                              check_vma=False))(jnp.zeros(2, jnp.float32))


# -- VMEM planning: tiled fold + fused backward (round 5) --------------------


def test_vmem_plan_modes():
    """attention_vmem_plan: small blocks → resident; big blocks → the
    largest sublane-aligned divisor tile that fits; impossible budgets
    → a diagnostic with the arithmetic."""
    from mpi_tpu.tpu.pallas_attention import attention_vmem_plan

    mode, tiles = attention_vmem_plan(64, 128, 1, 1, jnp.float32)
    assert mode == "resident" and tiles is None
    mode, tiles = attention_vmem_plan(4096, 128, 1, 1, jnp.float32)
    assert mode == "tiled"
    tq, tk = tiles
    assert tq == tk and 4096 % tq == 0 and tq % 8 == 0
    # the chosen tile really is the largest fitting divisor
    assert tq >= 256
    # the backward tiles too (round 5): big blocks stay on the fused
    # ring kernel instead of falling back to the ppermute recompute
    mode, bt = attention_vmem_plan(4096, 128, 1, 1, jnp.float32,
                                   for_backward=True)
    assert mode == "tiled" and 4096 % bt[0] == 0 and bt[0] % 8 == 0
    # only an impossible budget forces the recompute fallback
    mode, _ = attention_vmem_plan(4096, 128, 1, 1, jnp.float32,
                                  vmem_limit_bytes=30_000,
                                  for_backward=True)
    assert mode == "fallback"
    with pytest.raises(NotImplementedError, match="VMEM budget"):
        attention_vmem_plan(64, 128, 1, 1, jnp.float32,
                            vmem_limit_bytes=1024)


@pytest.mark.parametrize("causal", [False, True])
def test_tiled_parity_forced(causal):
    """A small vmem_limit_bytes forces the tiled fold (state in HBM,
    [tq,tk] inner loop) at test-friendly sizes: parity with the dense
    oracle, full and causal."""
    Pn, Sb, d = 4, 32, 128
    rng = np.random.RandomState(23)
    q = rng.randn(Pn * Sb, d).astype(np.float32)
    k = rng.randn(Pn * Sb, d).astype(np.float32)
    v = rng.randn(Pn * Sb, d).astype(np.float32)
    mesh = default_mesh(Pn)
    from mpi_tpu.tpu.pallas_attention import attention_vmem_plan

    limit = 100_000  # forces tiling at Sb=32 (score = 32*32*4 fits, but
    # resident staging of Q+KV+o does not)
    mode, tiles = attention_vmem_plan(Sb, d, 1, 1, jnp.float32,
                                      vmem_limit_bytes=limit)
    assert mode == "tiled" and tiles[0] < Sb, (mode, tiles)
    jf = jax.jit(jax.shard_map(
        lambda qb, kb, vb: pallas_ring_attention(
            qb, kb, vb, "world", Pn, causal=causal, interpret=True,
            vmem_limit_bytes=limit),
        mesh=mesh, in_specs=(P("world"),) * 3, out_specs=P("world"),
        check_vma=False))
    got = np.asarray(jf(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    want = (_causal_oracle if causal else _oracle)(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_tiled_parity_gqa_bf16():
    """Tiled fold with multi-head GQA layout and bf16 inputs (16-row
    sublane tiles): parity per head."""
    Pn, Hq, Hkv, Sb, d = 2, 4, 2, 32, 128
    rng = np.random.RandomState(29)
    q = rng.randn(Hq, Pn * Sb, d).astype(np.float32)
    k = rng.randn(Hkv, Pn * Sb, d).astype(np.float32)
    v = rng.randn(Hkv, Pn * Sb, d).astype(np.float32)
    mesh = default_mesh(Pn)
    limit = 100_000
    jf = jax.jit(jax.shard_map(
        lambda qb, kb, vb: pallas_ring_attention(
            qb.astype(jnp.bfloat16), kb.astype(jnp.bfloat16),
            vb.astype(jnp.bfloat16), "world", Pn, interpret=True,
            vmem_limit_bytes=limit),
        mesh=mesh, in_specs=(P(None, "world"),) * 3,
        out_specs=P(None, "world"), check_vma=False))
    got = np.asarray(jf(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)),
                     np.float32)
    for h in range(Hq):
        np.testing.assert_allclose(got[h], _oracle(q[h], k[h // 2],
                                                   v[h // 2]),
                                   rtol=5e-2, atol=5e-2)


def test_tiled_parity_large_block():
    """The VERDICT r4 ask: Sb >= 4096 f32 green on the interpreter —
    the default budget picks the tiled fold (the resident score matrix
    alone would be 64 MB) and the result still matches the dense
    oracle.  P=2, global sequence 8192."""
    Pn, Sb, d = 2, 4096, 128
    from mpi_tpu.tpu.pallas_attention import attention_vmem_plan

    mode, tiles = attention_vmem_plan(Sb, d, 1, 1, jnp.float32)
    assert mode == "tiled", (mode, tiles)
    rng = np.random.RandomState(31)
    q = rng.randn(Pn * Sb, d).astype(np.float32)
    k = rng.randn(Pn * Sb, d).astype(np.float32)
    v = rng.randn(Pn * Sb, d).astype(np.float32)
    mesh = default_mesh(Pn)
    jf = jax.jit(jax.shard_map(
        lambda qb, kb, vb: pallas_ring_attention(qb, kb, vb, "world", Pn,
                                                 interpret=True),
        mesh=mesh, in_specs=(P("world"),) * 3, out_specs=P("world"),
        check_vma=False))
    got = np.asarray(jf(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(got, _oracle(q, k, v), rtol=2e-4, atol=2e-3)


def test_tiled_export_tpu():
    """The tiled fold (fori_loop over HBM-state tiles) lowers through
    Mosaic for TPU at a block size the resident mode could never hold
    (Sb=8192 per device: a 256 MB score matrix resident)."""
    mesh = AbstractMesh((8,), ("s",))

    def f(q, k, v):
        return pallas_ring_attention(q, k, v, "s", 8, causal=True,
                                     interpret=False)

    jf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("s"),) * 3,
                               out_specs=P("s"), check_vma=False))
    aval = jax.ShapeDtypeStruct((8 * 8192, 128), jnp.float32)
    exp = jax.export.export(jf, platforms=["tpu"])(aval, aval, aval)
    assert "tpu_custom_call" in exp.mlir_module()


def test_bwd_kernel_export_tpu():
    """value_and_grad lowers BOTH rings through Mosaic: the forward
    kernel and the fused [K,V,dK,dV] backward kernel appear as two
    tpu_custom_calls in the exported module (VERDICT r4 missing #3 —
    the backward is fused, not a ppermute recompute)."""
    mesh = AbstractMesh((8,), ("s",))

    def loss(q, k, v):
        out = pallas_ring_attention(q, k, v, "s", 8, causal=True,
                                    interpret=False)
        return jnp.sum(out ** 2)

    jf = jax.jit(jax.shard_map(
        lambda q, k, v: jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v),
        mesh=mesh, in_specs=(P("s"),) * 3,
        out_specs=(P(), (P("s"),) * 3), check_vma=False))
    aval = jax.ShapeDtypeStruct((8 * 32, 128), jnp.float32)
    exp = jax.export.export(jf, platforms=["tpu"])(aval, aval, aval)
    assert exp.mlir_module().count("tpu_custom_call") >= 2
    # no ppermute ring in the backward: the recompute fallback would
    # show up as collective-permute ops
    assert "collective_permute" not in exp.mlir_module()


@pytest.mark.parametrize("causal", [False, True])
def test_bwd_kernel_matches_reference_multihead(causal):
    """The fused backward kernel (serial interpreter mode) against the
    differentiated pure-jax ring, GQA layout + nontrivial cotangent —
    dQ/dK/dV all match, causal and full."""
    from mpi_tpu.tpu.pallas_attention import _fallback_attention

    Pn, Hq, Hkv, Sb, d = 4, 4, 2, 8, 128
    rng = np.random.RandomState(37)
    q = rng.randn(Hq, Pn * Sb, d).astype(np.float32)
    k = rng.randn(Hkv, Pn * Sb, d).astype(np.float32)
    v = rng.randn(Hkv, Pn * Sb, d).astype(np.float32)
    ct = rng.randn(Hq, Pn * Sb, d).astype(np.float32)
    mesh = default_mesh(Pn)

    def loss_kernel(qb, kb, vb, ctb):
        out = pallas_ring_attention(qb, kb, vb, "world", Pn,
                                    causal=causal, interpret=True)
        return jnp.sum(out * ctb)

    def loss_ref(qb, kb, vb, ctb):
        out = _fallback_attention(qb, kb, vb, "world", Pn,
                                  1.0 / np.sqrt(d), causal)
        return jnp.sum(out * ctb)

    grads = {}
    for name, fn in (("kernel", loss_kernel), ("ref", loss_ref)):
        g = jax.jit(jax.shard_map(
            jax.grad(fn, argnums=(0, 1, 2)), mesh=mesh,
            in_specs=(P(None, "world"),) * 4,
            out_specs=(P(None, "world"),) * 3,
            check_vma=False))(*map(jnp.asarray, (q, k, v, ct)))
        grads[name] = [np.asarray(x) for x in g]
    for gk, gr in zip(grads["kernel"], grads["ref"]):
        np.testing.assert_allclose(gk, gr, rtol=2e-4, atol=2e-5)
    assert all(np.abs(g).max() > 0 for g in grads["kernel"])


@pytest.mark.parametrize("causal", [False, True])
def test_bwd_tiled_parity(causal):
    """Forced-tiled FUSED backward (round 5): with a budget that rules
    out the resident plan but admits backward tiles, grads from the
    tiled [K,V,dK,dV] ring kernel (dQ in HBM, per-tile staging, dK/dV
    carried through the inner loop, diagonal tile-skip) match the
    differentiated reference, full and causal."""
    from mpi_tpu.tpu.pallas_attention import (_fallback_attention,
                                              attention_vmem_plan)

    Pn, Sb, d = 3, 32, 128
    limit = 100_000
    mode, bt = attention_vmem_plan(Sb, d, 1, 1, jnp.float32,
                                   vmem_limit_bytes=limit,
                                   for_backward=True)
    assert mode == "tiled" and bt[0] < Sb, (mode, bt)
    rng = np.random.RandomState(43)
    q = rng.randn(Pn * Sb, d).astype(np.float32)
    k = rng.randn(Pn * Sb, d).astype(np.float32)
    v = rng.randn(Pn * Sb, d).astype(np.float32)
    ct = rng.randn(Pn * Sb, d).astype(np.float32)
    mesh = default_mesh(Pn)

    def loss_kernel(qb, kb, vb, ctb):
        out = pallas_ring_attention(qb, kb, vb, "world", Pn,
                                    causal=causal, interpret=True,
                                    vmem_limit_bytes=limit)
        return jnp.sum(out * ctb)

    def loss_ref(qb, kb, vb, ctb):
        out = _fallback_attention(qb, kb, vb, "world", Pn,
                                  1.0 / np.sqrt(d), causal)
        return jnp.sum(out * ctb)

    grads = {}
    for name, fn in (("kernel", loss_kernel), ("ref", loss_ref)):
        g = jax.jit(jax.shard_map(
            jax.grad(fn, argnums=(0, 1, 2)), mesh=mesh,
            in_specs=(P("world"),) * 4, out_specs=(P("world"),) * 3,
            check_vma=False))(*map(jnp.asarray, (q, k, v, ct)))
        grads[name] = [np.asarray(x) for x in g]
    for gk, gr in zip(grads["kernel"], grads["ref"]):
        np.testing.assert_allclose(gk, gr, rtol=2e-4, atol=1e-4)
    assert all(np.abs(g).max() > 0 for g in grads["kernel"])


def test_bwd_fallback_out_of_budget():
    """When even the minimal backward tile exceeds the budget the
    custom-vjp recomputes through the pure-jax ring — gradients still
    match the reference (the forward stays on the tiled kernel)."""
    from mpi_tpu.tpu.pallas_attention import (_fallback_attention,
                                              attention_vmem_plan)

    Pn, Sb, d = 2, 32, 128
    limit = 40_000  # tiled forward fits; no backward tile does
    assert attention_vmem_plan(Sb, d, 1, 1, jnp.float32,
                               vmem_limit_bytes=limit)[0] == "tiled"
    assert attention_vmem_plan(Sb, d, 1, 1, jnp.float32,
                               vmem_limit_bytes=limit,
                               for_backward=True)[0] == "fallback"
    rng = np.random.RandomState(41)
    q = rng.randn(Pn * Sb, d).astype(np.float32)
    k = rng.randn(Pn * Sb, d).astype(np.float32)
    v = rng.randn(Pn * Sb, d).astype(np.float32)
    mesh = default_mesh(Pn)

    def loss_kernel(qb, kb, vb):
        out = pallas_ring_attention(qb, kb, vb, "world", Pn,
                                    interpret=True,
                                    vmem_limit_bytes=limit)
        return jnp.sum(out ** 2)

    def loss_ref(qb, kb, vb):
        out = _fallback_attention(qb, kb, vb, "world", Pn,
                                  1.0 / np.sqrt(d))
        return jnp.sum(out ** 2)

    gk = jax.jit(jax.shard_map(
        jax.grad(loss_kernel, argnums=(0, 1, 2)), mesh=mesh,
        in_specs=(P("world"),) * 3, out_specs=(P("world"),) * 3,
        check_vma=False))(*map(jnp.asarray, (q, k, v)))
    gr = jax.jit(jax.shard_map(
        jax.grad(loss_ref, argnums=(0, 1, 2)), mesh=mesh,
        in_specs=(P("world"),) * 3, out_specs=(P("world"),) * 3,
        check_vma=False))(*map(jnp.asarray, (q, k, v)))
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_bwd_tiled_export_tpu():
    """The TILED fused backward lowers through Mosaic at a block size
    the resident plan could never hold (Sb=2048/device: s/p/dp/ds
    temporaries alone would be 64 MB) — long-context training stays on
    the fused ring kernels, no ppermute recompute in the module."""
    from mpi_tpu.tpu.pallas_attention import attention_vmem_plan

    assert attention_vmem_plan(2048, 128, 1, 1, jnp.float32,
                               for_backward=True)[0] == "tiled"
    mesh = AbstractMesh((8,), ("s",))

    def loss(q, k, v):
        out = pallas_ring_attention(q, k, v, "s", 8, causal=True,
                                    interpret=False)
        return jax.lax.psum(jnp.sum(out ** 2), "s")

    jf = jax.jit(jax.shard_map(
        lambda q, k, v: jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v),
        mesh=mesh, in_specs=(P("s"),) * 3,
        out_specs=(P(), (P("s"),) * 3), check_vma=False))
    aval = jax.ShapeDtypeStruct((8 * 2048, 128), jnp.float32)
    exp = jax.export.export(jf, platforms=["tpu"])(aval, aval, aval)
    assert exp.mlir_module().count("tpu_custom_call") >= 2
    assert "collective_permute" not in exp.mlir_module()


@pytest.mark.parametrize("Hq,Hkv,causal", [(2, 1, False), (4, 2, False),
                                           (2, 1, True), (4, 2, True)])
def test_bwd_tiled_parity_gqa(Hq, Hkv, causal):
    """GQA through the TILED fused backward: dK/dV tiles must
    ACCUMULATE across the query heads of one K/V group (review round
    5: per-head re-zeroing dropped all but the last head's own-block
    contribution) — including under causal masking, where the diagonal
    i_lo tile-skip interacts with the per-group zeroing."""
    from mpi_tpu.tpu.pallas_attention import (_fallback_attention,
                                              attention_vmem_plan)

    Pn, Sb, d = 2, 32, 128
    limit = 250_000
    mode, bt = attention_vmem_plan(Sb, d, Hq, Hkv, jnp.float32,
                                   vmem_limit_bytes=limit,
                                   for_backward=True)
    assert mode == "tiled", (mode, bt)
    rng = np.random.RandomState(47)
    q = rng.randn(Hq, Pn * Sb, d).astype(np.float32)
    k = rng.randn(Hkv, Pn * Sb, d).astype(np.float32)
    v = rng.randn(Hkv, Pn * Sb, d).astype(np.float32)
    ct = rng.randn(Hq, Pn * Sb, d).astype(np.float32)
    mesh = default_mesh(Pn)

    def loss_kernel(qb, kb, vb, ctb):
        out = pallas_ring_attention(qb, kb, vb, "world", Pn,
                                    causal=causal, interpret=True,
                                    vmem_limit_bytes=limit)
        return jnp.sum(out * ctb)

    def loss_ref(qb, kb, vb, ctb):
        out = _fallback_attention(qb, kb, vb, "world", Pn,
                                  1.0 / np.sqrt(d), causal)
        return jnp.sum(out * ctb)

    grads = {}
    for name, fn in (("kernel", loss_kernel), ("ref", loss_ref)):
        g = jax.jit(jax.shard_map(
            jax.grad(fn, argnums=(0, 1, 2)), mesh=mesh,
            in_specs=(P(None, "world"),) * 4,
            out_specs=(P(None, "world"),) * 3,
            check_vma=False))(*map(jnp.asarray, (q, k, v, ct)))
        grads[name] = [np.asarray(x) for x in g]
    for gk, gr in zip(grads["kernel"], grads["ref"]):
        np.testing.assert_allclose(gk, gr, rtol=2e-4, atol=1e-4)


def test_forward_oob_falls_back_loudly():
    """An un-tileable FORWARD budget degrades to the ppermute ring
    (ROADMAP r5 #4 graceful degradation) — numerically exact, with the
    shared loud-substitution contract: a RuntimeWarning AND the
    ``attention_fallbacks`` mpit pvar, never NotImplementedError."""
    from mpi_tpu import mpit
    from mpi_tpu.tpu.pallas_attention import attention_vmem_plan

    Pn, Sb, d = 2, 8, 128
    # a budget even the minimal tile can't satisfy (the plan still
    # raises — the CALLER owns the substitution)
    with pytest.raises(NotImplementedError):
        attention_vmem_plan(Sb, d, 1, 1, np.float32, vmem_limit_bytes=1)
    before = mpit.pvar_read("attention_fallbacks")
    with pytest.warns(RuntimeWarning, match="out of VMEM budget"):
        got, want = _run(Pn, Sb, d, vmem_limit_bytes=1)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert mpit.pvar_read("attention_fallbacks") == before + 1
