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
    """attention_vmem_plan: small blocks → one tile a block; big blocks →
    the largest sublane-aligned divisor tile that fits; impossible
    budgets → a diagnostic with the arithmetic."""
    from mpi_tpu.tpu.pallas_attention import attention_vmem_plan

    assert attention_vmem_plan(64, 128, 1, 1, jnp.float32) == \
        ("tiled", (64, 64))
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

    limit = 100_000  # 16-row forward tiles at Sb=32: two a block
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
    the default budget tiles the fold (a whole-block score matrix alone
    would be 64 MB) and the result still matches the dense oracle.
    P=2, global sequence 8192."""
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
    Mosaic for TPU at a block size no whole-block fold could hold in
    VMEM (Sb=8192 per device: a 256 MB score matrix)."""
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
    # no ppermute ring in the backward: the only collective-permutes are
    # the zigzag exchange's (two per array for q, k, v and the output,
    # and their transposes); the recompute fallback would add 28 more
    assert exp.mlir_module().count("stablehlo.collective_permute") == 16


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
    """Tiled FUSED backward (round 5): with a budget that admits only
    backward tiles smaller than a block, grads from the
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
    limit = 50_000  # tiled forward fits; no backward tile does
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
    no whole-block fold could hold in VMEM (Sb=2048/device: s/p/dp/ds
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
    # causal and tiled, so the zigzag layout: its exchange is the only
    # collective-permute, two per array (q, k, v and the output) and
    # their transposes — a recompute ring would add 2(P-1) more
    assert exp.mlir_module().count("stablehlo.collective_permute") == 16


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


# -- zigzag causal layout ----------------------------------------------------


def _dense_causal(q, k, v):
    """Dense causal GQA attention in float32 on the whole sequence."""
    g = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    n = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("Hq,Hkv", [(2, 2), (4, 2)], ids=["mha", "gqa"])
@pytest.mark.parametrize("Pn", [2, 3, 4])
def test_zigzag_parity(Pn, Hq, Hkv, dtype):
    """Causal attention on the tiled plan takes the zigzag layout (the
    exchange outside the kernels, each arrival folding half a block):
    the output and jax.grad match dense causal attention on the whole
    sequence, with the caller's blocks contiguous in rank order."""
    from mpi_tpu import mpit

    Sb, d, limit = 32, 128, 130_000
    rng = np.random.RandomState(50 + Pn * 10 + Hq)
    # inputs rounded to the dtype, so the oracle sees what the kernel sees
    q, k, v, ct = (np.asarray(jnp.asarray(rng.randn(h, Pn * Sb, d), dtype),
                              np.float32) for h in (Hq, Hkv, Hkv, Hq))
    mesh = default_mesh(Pn)

    def loss(qb, kb, vb, cb):
        out = pallas_ring_attention(qb.astype(dtype), kb.astype(dtype),
                                    vb.astype(dtype), "world", Pn,
                                    causal=True, interpret=True,
                                    vmem_limit_bytes=limit)
        out = out.astype(jnp.float32)
        return jnp.sum(out * cb), out

    spec = P(None, "world")
    before = mpit.pvar_read("attention_zigzag")
    (dq, dk, dv), out = jax.jit(jax.shard_map(
        jax.grad(loss, argnums=(0, 1, 2), has_aux=True), mesh=mesh,
        in_specs=(spec,) * 4, out_specs=((spec,) * 3, spec),
        check_vma=False))(*map(jnp.asarray, (q, k, v, ct)))
    assert mpit.pvar_read("attention_zigzag") == before + 1

    def dense_loss(q_, k_, v_):
        return jnp.sum(_dense_causal(q_, k_, v_) * ct)

    want = [_dense_causal(q, k, v)] + list(
        jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v))
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    for got, ref in zip((out, dq, dk, dv), want):
        got, ref = np.asarray(got), np.asarray(ref)
        assert got.shape == ref.shape
        assert np.abs(got - ref).max() <= tol * np.abs(ref).max()


def _zigzag_case(case):
    """(a shard_map'd call of ring attention, its input avals) for one
    way of calling it; tracing it counts the zigzag layout or not."""
    Pn, Sb, d = (1, 32, 128) if case == "size1" else (4, 32, 128)
    if case == "halves_untileable":
        Sb = 24  # Sb/2 = 12 rows: no 8-row tile divides a chunk
    # 16-row forward and 8-row backward tiles
    kw = dict(causal=True, interpret=True, vmem_limit_bytes=100_000)
    check_vma, grad = False, True
    if case == "full":
        kw["causal"] = False
    elif case == "default_budget":
        kw.pop("vmem_limit_bytes")  # 16-row tiles in both kernels
    elif case == "vma_fallback":
        check_vma = True
    elif case == "forward_oob":
        kw["vmem_limit_bytes"] = 1
    elif case == "backward_oob":
        kw["vmem_limit_bytes"] = 50_000  # tiled forward, no backward tile

    def loss(qb):
        return jnp.sum(pallas_ring_attention(qb, qb, qb, "world", Pn, **kw))

    fn = jax.grad(loss) if grad else loss
    aval = jax.ShapeDtypeStruct((Pn * Sb, d), jnp.float32)
    return jax.shard_map(fn, mesh=default_mesh(Pn), in_specs=P("world"),
                         out_specs=P("world"), check_vma=check_vma), aval


@pytest.mark.parametrize("case,engaged", [
    ("causal_tiled", 1), ("full", 0), ("default_budget", 1), ("size1", 0),
    ("vma_fallback", 0), ("forward_oob", 0), ("backward_oob", 0),
    ("halves_untileable", 0)])
def test_zigzag_engages_only_causal_tiled(case, engaged):
    """``attention_zigzag`` rises once per trace where causal attention
    runs both fused kernels on tiles of half blocks, and on no other
    path."""
    import warnings

    from mpi_tpu import mpit

    fn, aval = _zigzag_case(case)
    before = mpit.pvar_read("attention_zigzag")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # the fallbacks'
        jax.jit(fn).trace(aval)
    assert mpit.pvar_read("attention_zigzag") - before == engaged


def test_causal_tiled_contiguous_where_halves_do_not_tile():
    """Causal and tiled, but no tile divides Sb/2: the kernels keep the
    contiguous layout (diagonal tile-skip, future blocks skipped) and
    still match the differentiated reference ring."""
    from mpi_tpu import mpit
    from mpi_tpu.tpu.pallas_attention import (_fallback_attention,
                                              attention_vmem_plan)

    Pn, Sb, d, limit = 4, 24, 128, 100_000
    assert attention_vmem_plan(Sb, d, 1, 1, jnp.float32, limit)[0] == "tiled"
    assert attention_vmem_plan(Sb, d, 1, 1, jnp.float32, limit,
                               for_backward=True)[0] == "tiled"
    rng = np.random.RandomState(53)
    q, k, v, ct = (rng.randn(Pn * Sb, d).astype(np.float32)
                   for _ in range(4))
    mesh = default_mesh(Pn)

    def loss_kernel(qb, kb, vb, cb):
        out = pallas_ring_attention(qb, kb, vb, "world", Pn, causal=True,
                                    interpret=True, vmem_limit_bytes=limit)
        return jnp.sum(out * cb), out

    def loss_ref(qb, kb, vb, cb):
        out = _fallback_attention(qb, kb, vb, "world", Pn,
                                  1.0 / np.sqrt(d), True)
        return jnp.sum(out * cb), out

    before = mpit.pvar_read("attention_zigzag")
    got = {}
    for name, fn in (("kernel", loss_kernel), ("ref", loss_ref)):
        g, out = jax.jit(jax.shard_map(
            jax.grad(fn, argnums=(0, 1, 2), has_aux=True), mesh=mesh,
            in_specs=(P("world"),) * 4,
            out_specs=((P("world"),) * 3, P("world")),
            check_vma=False))(*map(jnp.asarray, (q, k, v, ct)))
        got[name] = [np.asarray(x) for x in (out,) + tuple(g)]
    assert mpit.pvar_read("attention_zigzag") == before
    np.testing.assert_allclose(got["kernel"][0], _causal_oracle(q, k, v),
                               rtol=2e-4, atol=2e-5)
    for a, b in zip(got["kernel"], got["ref"]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("sb,sub,first,same", [
    (8192, 16, 512, True), (512, 8, 256, False), (384, 16, 192, False),
    (24, 8, 24, True)])
def test_causal_tiles_prefer_halves(sb, sub, first, same):
    """A causal ring's plan tries tiles that divide Sb/2 first, still
    largest first; where none does, the order is the contiguous one."""
    from mpi_tpu.tpu.pallas_attention import _tile_sizes

    ts, plain = _tile_sizes(sb, sub, halves=True), _tile_sizes(sb, sub)
    assert ts[0] == first and sorted(ts) == sorted(plain)
    assert (ts == plain) == same
    half = [t for t in ts if (sb // 2) % t == 0]
    assert ts[:len(half)] == half == sorted(half, reverse=True)


def _pairs_of(t):
    """Tile pairs (i, j) one arrival's ``_Tiles`` fold, from the forward's
    bounds and, separately, from the backward's: the two must agree."""
    span = lambda lo_hi: range(*(int(x) for x in lo_hi))
    fwd = {(i, j) for i in span((t.q_lo, t.q_hi)) for j in span(t.k_of(i))}
    bwd = {(i, j) for j in span((t.k_lo, t.k_hi)) for i in span(t.q_of(j))}
    assert fwd == bwd
    return fwd


def _tile_pairs(diag, past, nq, nk, tq, tk):
    """Tile pairs (i, j) one zigzag arrival folds (forward == backward)."""
    from mpi_tpu.tpu.pallas_attention import _zigzag_tiles

    return _pairs_of(_zigzag_tiles(diag, past, nq, nk, tq, tk))


def test_zigzag_balances_the_cell():
    """The four-chip cell's shapes (Sb 8192 a chip, 512-row tiles): every
    rank folds 520 tile pairs on the zigzag layout, against 136 / 392 /
    648 / 904 on the contiguous one (the diagonal block plus r full past
    blocks; future blocks skipped)."""
    Pn, nt, t = 4, 16, 512
    diag = len(_tile_pairs(True, False, nt, nt, t, t))
    zigzag = [diag + sum(len(_tile_pairs(False, s < r, nt, nt, t, t))
                         for s in range(Pn) if s != r) for r in range(Pn)]
    contiguous = [diag + r * nt * nt for r in range(Pn)]
    assert zigzag == [520] * 4
    assert contiguous == [136, 392, 648, 904]


@pytest.mark.parametrize("Pn,Sb,tq,tk", [(2, 16, 4, 4), (3, 24, 4, 4),
                                         (4, 32, 8, 4), (4, 32, 4, 8),
                                         (4, 64, 16, 16)])
def test_zigzag_tiles_cover_causal_pairs_once(Pn, Sb, tq, tk):
    """By brute force: over every rank and arrival, the tiles the kernels
    visit (masked by global position on the diagonal only) hold each
    causal (query, key) pair of the global sequence exactly once, and
    no other pair."""
    from mpi_tpu.tpu.pallas_attention import _zigzag_mask, _zigzag_pos

    c, nq, nk = Sb // 2, Sb // tq, Sb // tk
    seen = {}
    for r in range(Pn):
        for s in range(Pn):
            for i, j in _tile_pairs(s == r, s < r, nq, nk, tq, tk):
                rows = np.arange(i * tq, (i + 1) * tq)[:, None]
                cols = np.arange(j * tk, (j + 1) * tk)[None, :]
                pq = np.asarray(_zigzag_pos(r, rows, c, Pn))
                pk = np.asarray(_zigzag_pos(s, cols, c, Pn))
                pq, pk = np.broadcast_arrays(pq, pk)
                if s == r:
                    keep = np.asarray(_zigzag_mask(r, c, Pn, i * tq,
                                                   j * tk, tq, tk))
                    assert np.array_equal(keep, pk <= pq)
                else:
                    keep = np.ones_like(pq, bool)
                    assert (pk <= pq).all()  # unmasked: all causal
                for a, b in zip(pq[keep], pk[keep]):
                    seen[(a, b)] = seen.get((a, b), 0) + 1
    S = Pn * Sb
    assert set(seen.values()) == {1}
    assert set(seen) == {(a, b) for a in range(S) for b in range(a + 1)}


# -- sliding window, and P = 1 on the kernels --------------------------------


def _dense_window(q, k, v, window=None, causal=True, sinks=None):
    """Dense causal GQA attention in float32 on the whole sequence, each
    query attending the ``window`` newest keys up to itself (all of them
    where ``window`` is None; every key where not ``causal``); ``sinks``
    ([Hq]): a logit per query head in every row's softmax denominator,
    carrying no value.  q/k may be wider than v; scale 1/sqrt(q width)."""
    g = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    n = q.shape[1]
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    keep = (j <= i) if window is None else (j <= i) & (j > i - window)
    s = jnp.where(keep | (not causal), s, -jnp.inf)
    if sinks is not None:
        sink = jnp.broadcast_to(sinks[:, None, None], s.shape[:2] + (1,))
        s = jnp.concatenate([s, sink], axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :n]
    return jnp.einsum("hqk,hkd->hqd", p, v, precision="highest")


def _window_grads(Pn, Sb, Hq, Hkv, window, limit, seed=0, causal=True,
                  interpret=True, pvar="attention_window"):
    """(kernel out, dq, dk, dv), (dense out, dq, dk, dv) and the ``pvar``
    mpit pvar's rise, for causal (or full) attention over ``Pn`` ranks of
    ``Sb`` rows through ``jax.grad``."""
    from mpi_tpu import mpit

    rng = np.random.RandomState(seed)
    q, k, v, ct = (rng.randn(h, Pn * Sb, 128).astype(np.float32)
                   for h in (Hq, Hkv, Hkv, Hq))

    def loss(qb, kb, vb, cb):
        out = pallas_ring_attention(qb, kb, vb, "world", Pn, causal=causal,
                                    window=window, interpret=interpret,
                                    vmem_limit_bytes=limit)
        return jnp.sum(out * cb), out

    spec = P(None, "world")
    before = mpit.pvar_read(pvar)
    (dq, dk, dv), out = jax.jit(jax.shard_map(
        jax.grad(loss, argnums=(0, 1, 2), has_aux=True),
        mesh=default_mesh(Pn), in_specs=(spec,) * 4,
        out_specs=((spec,) * 3, spec), check_vma=False))(q, k, v, ct)
    rise = mpit.pvar_read(pvar) - before

    def dense_loss(q_, k_, v_):
        return jnp.sum(_dense_window(q_, k_, v_, window, causal) * ct)

    want = [_dense_window(q, k, v, window, causal)] + list(
        jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v))
    return [np.asarray(x) for x in (out, dq, dk, dv)], \
        [np.asarray(x) for x in want], rise


# tiled at Sb 32 with 8-row tiles (the budget admits no larger forward or
# backward tile); W < tile, W not a multiple of the tile, W = Sb, W >= S
_TILE8 = 80_000


@pytest.mark.parametrize("Pn,window,Hq,Hkv", [
    (1, 5, 6, 1), (1, 13, 1, 1), (1, 32, 6, 1), (1, 100, 2, 2),
    (2, 5, 2, 2), (2, 13, 6, 1), (2, 32, 1, 1), (2, 64, 6, 1),
    (4, 5, 6, 1), (4, 13, 2, 2), (4, 32, 6, 1), (4, 200, 1, 1)])
def test_window_parity(Pn, window, Hq, Hkv):
    """Sliding-window causal attention on the tiled kernels (8-row tiles
    of 32-row blocks) at P = 1, 2 and 4: the output and the three
    gradients match dense windowed attention, GQA groups of 6 and 1; a
    window of the whole sequence or more is plain causal attention."""
    from mpi_tpu.tpu.pallas_attention import attention_vmem_plan

    for bwd in (False, True):
        assert attention_vmem_plan(32, 128, Hq, Hkv, jnp.float32, _TILE8,
                                   for_backward=bwd) == ("tiled", (8, 8))
    got, want, rise = _window_grads(Pn, 32, Hq, Hkv, window, _TILE8,
                                    seed=Pn * 100 + window)
    assert rise == 1
    for g, r in zip(got, want):
        assert g.shape == r.shape
        assert np.abs(g - r).max() <= 2e-5 * np.abs(r).max()
    if window >= Pn * 32:
        causal, _, _ = _window_grads(Pn, 32, Hq, Hkv, None, _TILE8,
                                     seed=Pn * 100 + window)
        for g, c in zip(got, causal):
            np.testing.assert_allclose(g, c, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("Pn,window", [(1, 7), (2, 13), (4, 9)])
def test_window_parity_one_tile(Pn, window):
    """At the default budget a 16-row block is one tile: the diagonal
    tile masks the window too, and a block wholly before every query's
    window folds nothing."""
    from mpi_tpu.tpu.pallas_attention import attention_vmem_plan

    for bwd in (False, True):
        assert attention_vmem_plan(16, 128, 6, 1, jnp.float32,
                                   for_backward=bwd) == ("tiled", (16, 16))
    got, want, rise = _window_grads(Pn, 16, 6, 1, window, None, seed=window)
    assert rise == 1
    for g, r in zip(got, want):
        assert np.abs(g - r).max() <= 2e-5 * np.abs(r).max()


@pytest.mark.parametrize("limit", [None, _TILE8], ids=["one_tile", "tiled"])
@pytest.mark.parametrize("Hq,Hkv", [(1, 1), (4, 2), (6, 1)])
def test_size_one_causal_runs_the_kernels(Hq, Hkv, limit):
    """P = 1 causal attention runs the fused kernels as a ring of one —
    one tile a block or 8-row tiles, forward and fused backward — and
    matches dense causal attention and its gradients."""
    got, want, _ = _window_grads(1, 32, Hq, Hkv, None, limit, seed=Hq)
    for g, r in zip(got, want):
        assert np.abs(g - r).max() <= 2e-5 * np.abs(r).max()


@pytest.mark.parametrize("Pn", [1, 4])
def test_size_one_emits_no_ring(Pn):
    """A ring of one emits no RDMA, neighbour barrier or credit, and its
    kernels are not collectives (no side effects, no collective id), so
    the executable caches like any local kernel; a ring of four has all
    of them."""
    mesh = AbstractMesh((Pn,), ("s",))

    def loss(q, k, v):
        out = pallas_ring_attention(q, k, v, "s", Pn, causal=True,
                                    window=24, interpret=False,
                                    vmem_limit_bytes=_TILE8)
        return jnp.sum(out ** 2)

    jf = jax.jit(jax.shard_map(
        lambda q, k, v: jax.grad(loss, argnums=(0, 1, 2))(q, k, v),
        mesh=mesh, in_specs=(P(None, "s"),) * 3,
        out_specs=(P(None, "s"),) * 3, check_vma=False))
    q = jax.ShapeDtypeStruct((6, Pn * 32, 128), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, Pn * 32, 128), jnp.float32)
    jaxpr = str(jax.make_jaxpr(jf)(q, kv, kv))
    ring = Pn > 1
    for prim in ("semaphore_signal", "get_barrier_semaphore", "device_id"):
        assert (prim in jaxpr) == ring, prim
    assert ("has_side_effects=True" in jaxpr) == ring
    assert jaxpr.count("mpi_tpu_swa_attention_") == 2


@pytest.mark.parametrize("off,window,sb,tq,tk", [
    (0, None, 32, 8, 8), (0, 5, 32, 8, 8), (0, 13, 32, 8, 4),
    (0, 9, 32, 4, 8), (0, 32, 32, 8, 8), (0, 100, 32, 8, 8),
    (32, 5, 32, 8, 8), (32, 13, 32, 4, 8), (32, 33, 32, 8, 8),
    (64, 33, 32, 8, 8), (64, 34, 32, 8, 8), (96, 40, 32, 8, 4),
    (32, None, 32, 8, 8), (16, 20, 48, 16, 8)])
def test_window_tiles_visit_exactly_unmasked_pairs(off, window, sb, tq, tk):
    """By brute force: the forward's and the backward's tile ranges on the
    contiguous layout visit the same tile pairs, and exactly those that
    hold an element the causal window keeps (query row r at ``off + r``
    against key row c: ``0 <= off + r - c < window``)."""
    from mpi_tpu.tpu.pallas_attention import _band_tiles

    visited = _pairs_of(_band_tiles(off, window, sb, tq, tk))
    r = np.arange(sb)[:, None] + off
    c = np.arange(sb)[None, :]
    keep = (c <= r) & ((c > r - window) if window else True)
    want = {(i, j) for i in range(sb // tq) for j in range(sb // tk)
            if keep[i * tq:(i + 1) * tq, j * tk:(j + 1) * tk].any()}
    assert visited == want


def _window_case(case):
    """(a shard_map'd call of ring attention, its input aval) for one way
    of calling it; tracing it counts the windowed tile ranges or not."""
    Pn, Sb, d = (1, 32, 128) if case.startswith("size1") else (4, 32, 128)
    kw = dict(causal=True, window=13, interpret=True,
              vmem_limit_bytes=_TILE8)
    check_vma = False
    if case == "causal":
        kw["window"] = None
    elif case == "default_budget":
        kw.pop("vmem_limit_bytes")
    elif case == "vma_fallback":
        check_vma = True
    elif case == "forward_oob":
        kw["vmem_limit_bytes"] = 1

    def loss(qb):
        return jnp.sum(pallas_ring_attention(qb, qb, qb, "world", Pn, **kw))

    aval = jax.ShapeDtypeStruct((Pn * Sb, d), jnp.float32)
    return jax.shard_map(jax.grad(loss), mesh=default_mesh(Pn),
                         in_specs=P("world"), out_specs=P("world"),
                         check_vma=check_vma), aval


@pytest.mark.parametrize("case,engaged", [
    ("windowed", 1), ("size1_windowed", 1), ("causal", 0),
    ("default_budget", 1), ("vma_fallback", 0), ("forward_oob", 0)])
def test_window_counted_once_per_trace(case, engaged):
    """``attention_window`` rises once per trace where windowed attention
    takes the fused kernels' windowed tile ranges, and on no other path;
    a windowed call never takes the zigzag layout."""
    import warnings

    from mpi_tpu import mpit

    fn, aval = _window_case(case)
    before = {p: mpit.pvar_read(p)
              for p in ("attention_window", "attention_zigzag")}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # the fallbacks'
        jax.jit(fn).trace(aval)
    assert mpit.pvar_read("attention_window") - \
        before["attention_window"] == engaged
    assert mpit.pvar_read("attention_zigzag") - \
        before["attention_zigzag"] == int(case == "causal")


def test_window_diagnosed():
    """A window needs causal attention and at least one key."""
    mesh = default_mesh(1)

    def run(**kw):
        def f(x):
            q = jnp.zeros((8, 128), jnp.float32)
            return pallas_ring_attention(q, q, q, "world", 1,
                                         interpret=True, **kw)

        jax.jit(jax.shard_map(lambda x: f(x)[:0], mesh=mesh,
                              in_specs=P("world"), out_specs=P("world"),
                              check_vma=False))(jnp.zeros(1, jnp.float32))

    with pytest.raises(ValueError, match="sliding window"):
        run(window=4)
    with pytest.raises(ValueError, match="sliding window"):
        run(causal=True, window=0)


def test_size_one_on_a_multi_axis_mesh_runs_the_kernel():
    """A ring of one over the ``sp`` axis of a 2-D (dp×sp) mesh sends no
    RDMA, so the interpreter runs the kernel itself — no fallback."""
    import warnings

    from jax.sharding import Mesh

    from mpi_tpu import mpit

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4, 1), ("dp", "sp"))
    rng = np.random.RandomState(59)
    q = rng.randn(4, 16, 128).astype(np.float32)  # one block per dp slice

    def f(qb):
        return pallas_ring_attention(qb[0], qb[0], qb[0], "sp", 1,
                                     causal=True, window=5,
                                     interpret=True)[None]

    before = mpit.pvar_read("pallas_ring_fallbacks")
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        got = np.asarray(jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("dp", "sp", None),
            out_specs=P("dp", "sp", None), check_vma=False))(q))
    assert mpit.pvar_read("pallas_ring_fallbacks") == before
    for sl in range(4):
        want = _dense_window(q[sl][None], q[sl][None], q[sl][None], 5)[0]
        np.testing.assert_allclose(got[sl], want, rtol=2e-4, atol=2e-5)


# -- the prefetching tile loop -----------------------------------------------


@pytest.mark.parametrize("Pn,Sb,Hq,Hkv,causal,window", [
    (2, 48, 2, 2, False, None),   # full: 6 tiles a loop
    (2, 48, 2, 2, True, None),    # zigzag: traced bounds, 1 to 6 a loop
    (2, 48, 2, 1, True, 21),      # a window: 1 to 4 a loop
    (1, 48, 6, 1, True, None),    # 6 heads share dK/dV tiles; 1 to 6
    (2, 8, 4, 1, True, None),     # one 8-row tile a block: 1 a loop
], ids=["full", "zigzag_p2", "window", "gqa6", "one_tile"])
def test_tiled_prefetch_parity(Pn, Sb, Hq, Hkv, causal, window):
    """Both tiled folds stage the next tile pair while they fold this one
    (8-row tiles): the output and the three gradients match dense
    attention, and no fallback runs."""
    from mpi_tpu.tpu.pallas_attention import attention_vmem_plan

    for bwd in (False, True):
        assert attention_vmem_plan(Sb, 128, Hq, Hkv, jnp.float32, _TILE8,
                                   for_backward=bwd) == ("tiled", (8, 8))
    got, want, rise = _window_grads(Pn, Sb, Hq, Hkv, window, _TILE8,
                                    seed=Sb + Hq, causal=causal,
                                    pvar="attention_fallbacks")
    assert rise == 0
    for g, r in zip(got, want):
        assert g.shape == r.shape
        assert np.abs(g - r).max() <= 2e-5 * np.abs(r).max()


@pytest.mark.parametrize("window", [None, 13], ids=["causal", "window"])
def test_tiled_folds_under_the_tpu_interpreter(window):
    """Under Pallas's TPU interpreter a DMA runs only when it is waited
    for: a tile folded before its copy is waited reads a buffer its data
    has not reached, and a dQ write-back never waited is lost.  A ring of
    one, a GQA group of 6, 8-row tiles."""
    from jax.experimental.pallas import tpu as pltpu

    try:
        got, want, _ = _window_grads(1, 32, 6, 1, window, _TILE8, seed=61,
                                     interpret=pltpu.InterpretParams())
    finally:
        pltpu.reset_tpu_interpret_mode_state()
    for g, r in zip(got, want):
        assert np.abs(g - r).max() <= 2e-5 * np.abs(r).max()


class _TileLoopModel:
    """Runs ``_mk_tile_loop``'s loop in Python, each DMA landing at any
    moment between its start and its wait, and checks its schedule: one
    copy in flight per semaphore; no two copies in flight on one buffer
    where either writes it; a fold reads its own slot's tiles, with
    nothing in flight into or out of that slot; nothing in flight when a
    loop returns.  Stands in for the semaphore array and for the parts of
    ``pl``, ``lax`` and ``pltpu`` that the loop uses."""

    shape = (2, 3)  # the semaphore array: 2 slots, 2 loads + the store

    def __init__(self):
        self.inflight = {}  # semaphore -> (src, dst)
        self.data = {}      # buffer -> what it holds

    @property
    def at(self):
        return self

    def __getitem__(self, slot_col):
        return ("sem",) + slot_col

    @staticmethod
    def when(cond):
        return lambda f: f() if cond else None

    @staticmethod
    def fori_loop(lo, hi, body, carry):
        for n in range(lo, hi):
            carry = body(n, carry)
        return carry

    @staticmethod
    def rem(n, m):
        return n % m

    def make_async_copy(self, src, dst, sem):
        model = self

        class Copy:
            def start(self):
                assert sem not in model.inflight
                for s, d in model.inflight.values():
                    assert dst not in (s, d) and src != d, (src, dst, s, d)
                model.inflight[sem] = (src, dst)

            def wait(self):
                assert model.inflight.pop(sem) == (src, dst)
                model.data[dst] = model.data.get(src, src)

        return Copy()

    def run(self, lo, hi, store, version):
        """One loop over tiles [lo, hi) that loads q and dq rows, folds
        and (``store``) writes dq back, as the backward's inner loop
        does; ``version`` tells this loop's dq writes apart."""
        from mpi_tpu.tpu import pallas_attention as pa

        def loads(n, slot):
            return ((("hbm", "q", n), ("vmem", "q", slot)),
                    (("hbm", "dq", n), ("vmem", "dq", slot)))

        def body(n, slot, folded):
            assert all(b[0] != "vmem" or b[2] != slot
                       for cp in self.inflight.values() for b in cp)
            assert self.data[("vmem", "q", slot)] == ("hbm", "q", n)
            # the dq tile holds what the previous loop wrote back
            assert self.data[("vmem", "dq", slot)] == \
                self.data.get(("hbm", "dq", n), ("hbm", "dq", n))
            self.data[("vmem", "dq", slot)] = ("dq", n, version)
            return folded + [n]

        def dq_store(n, slot):
            return ("vmem", "dq", slot), ("hbm", "dq", n)

        folded = pa._mk_tile_loop(self)(lo, hi, loads, body, [],
                                        store=dq_store if store else None)
        assert folded == list(range(lo, hi))
        assert not self.inflight
        if store:
            for n in range(lo, hi):
                assert self.data[("hbm", "dq", n)] == ("dq", n, version)


@pytest.mark.parametrize("store", [False, True], ids=["loads", "write_back"])
@pytest.mark.parametrize("lo,hi", [(0, 0), (3, 2), (0, 1), (5, 6), (0, 2),
                                   (1, 4), (2, 9)])
def test_tile_loop_schedule(lo, hi, store, monkeypatch):
    """The prefetching loop's DMA schedule, for empty loops, loops of one
    tile and longer ones, run twice over the same rows (one k tile's
    q-tile loop, then the next's)."""
    from types import SimpleNamespace

    from mpi_tpu.tpu import pallas_attention as pa

    model = _TileLoopModel()
    monkeypatch.setattr(pa, "pl", SimpleNamespace(when=model.when))
    monkeypatch.setattr(pa, "lax", model)
    monkeypatch.setattr(pa, "pltpu", model)
    model.run(lo, hi, store, version=0)
    model.run(lo, hi, store, version=1)


@pytest.mark.parametrize("sb,hq,hkv,halves", [
    (8192, 32, 2, False), (8192, 32, 2, True), (16384, 48, 8, False)],
    ids=["attn.sp1.s8k", "attn.sp4.s32k", "attn.swa4k.sp1.s16k"])
def test_cells_keep_512_tiles(sb, hq, hkv, halves):
    """The second tile buffers still leave both folds of the benchmark's
    attention cells on 512 x 512 tiles under the default budget."""
    from mpi_tpu.tpu.pallas_attention import attention_vmem_plan

    for bwd in (False, True):
        assert attention_vmem_plan(sb, 128, hq, hkv, jnp.bfloat16, None, bwd,
                                   halves) == ("tiled", (512, 512))


# -- split head widths (q/k 192, v 128) and sinks ----------------------------


def _split_grads(Pn, Sb, Hq, Hkv, window, with_sinks, seed):
    """(kernel out, dq, dk, dv[, dsinks]), (dense ones) and the mpit
    ``attention_sink`` rise for causal attention over ``Pn`` ranks of
    ``Sb`` rows with q/k heads 192 wide and v heads 128 wide, through
    ``jax.grad``; sink logits uniform in [2, 6) where ``with_sinks``
    (dsinks summed over the ranks' partial sums)."""
    from mpi_tpu import mpit

    rng = np.random.RandomState(seed)
    S = Pn * Sb
    q, k = (rng.randn(h, S, 192).astype(np.float32) for h in (Hq, Hkv))
    v = rng.randn(Hkv, S, 128).astype(np.float32)
    ct = rng.randn(Hq, S, 128).astype(np.float32)
    sinks = rng.uniform(2, 6, Hq).astype(np.float32) if with_sinks else None

    def loss(qb, kb, vb, cb, *s):
        out = pallas_ring_attention(qb, kb, vb, "world", Pn, causal=True,
                                    window=window,
                                    sinks=s[0] if s else None,
                                    interpret=True)
        return jnp.sum(out * cb), out

    def per_rank(*xs):
        nargs = (0, 1, 2) + ((4,) if with_sinks else ())
        grads, out = jax.grad(loss, argnums=nargs, has_aux=True)(*xs)
        return tuple(grads[:3]) + tuple(g[None] for g in grads[3:]), out

    spec = P(None, "world")
    extra = (sinks,) if with_sinks else ()
    before = mpit.pvar_read("attention_sink")
    grads, out = jax.jit(jax.shard_map(
        per_rank, mesh=default_mesh(Pn),
        in_specs=(spec,) * 4 + (P(),) * len(extra),
        out_specs=((spec,) * 3 + (P("world"),) * len(extra), spec),
        check_vma=False))(q, k, v, ct, *extra)
    rise = mpit.pvar_read("attention_sink") - before
    got = [out] + list(grads[:3]) + [g.sum(0) for g in grads[3:]]

    def dense_loss(q_, k_, v_, *s):
        return jnp.sum(_dense_window(q_, k_, v_, window,
                                     sinks=s[0] if s else None) * ct)

    args = (q, k, v) + extra
    want = [_dense_window(q, k, v, window, sinks=sinks)] + list(
        jax.grad(dense_loss, argnums=tuple(range(len(args))))(*args))
    return [np.asarray(x) for x in got], [np.asarray(x) for x in want], rise


@pytest.mark.parametrize("Pn,Hq,Hkv,window,with_sinks", [
    (1, 8, 1, 128, True), (2, 8, 1, 128, True), (1, 8, 1, 128, False),
    (2, 8, 1, 128, False), (1, 16, 1, None, False), (2, 16, 1, None, False),
    (2, 16, 1, None, True), (2, 8, 2, 40, True)],
    ids=["swa_sinks_p1", "swa_sinks_p2", "swa_p1", "swa_p2", "full_g16_p1",
         "full_g16_p2", "full_g16_sinks_p2", "swa40_g4_sinks_p2"])
def test_split_widths_and_sinks_parity(Pn, Hq, Hkv, window, with_sinks):
    """q/k heads 192 wide (zero-padded to 256 lanes before the kernels)
    and v heads 128 wide, GQA groups of 8 and 16 (MiMo-V2-Flash's), a
    128-key window on 128-row tiles (P = 2: K, V, dK and dV circulate as
    four planes; full attention at P = 2 takes the zigzag layout), sinks
    on and off: the output, dq, dk, dv and dsinks match dense attention
    with the sink in the softmax denominator."""
    got, want, rise = _split_grads(Pn, 256, Hq, Hkv, window, with_sinks,
                                   seed=Pn * 10 + Hq + int(with_sinks))
    assert rise == int(with_sinks)
    assert len(got) == 4 + int(with_sinks)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        assert np.abs(g - r).max() <= 2e-5 * np.abs(r).max()


@pytest.mark.parametrize("sb,window,tile,folds", [
    (8192, 128, 128, 2_080_768), (16384, 128, 128, 4_177_920),
    (8192, 100, 128, 2_080_768), (8192, 200, 256, 4_128_768),
    (16384, 4096, 512, 66_060_288)],
    ids=["mimo", "s16k", "w100", "w200", "trinity"])
def test_window_tile_plan(sb, window, tile, folds):
    """A window narrower than 512 rows caps the tile at the window rounded
    up to whole lanes: a 128-key band folds at most about twice the pairs
    it keeps, where 512-row tiles would fold 8x (12.8 %, 8,126,464 pairs
    at S 8192); a window of 512 or more keeps 512-row tiles.
    ``_band_pairs`` (the ``attention_band_pairs`` counter) counts what the
    plan folds."""
    from mpi_tpu.tpu.pallas_attention import _band_pairs, attention_vmem_plan

    for bwd in (False, True):
        assert attention_vmem_plan(sb, 256, 64, 8, jnp.bfloat16, None, bwd,
                                   dv=128, window=window) == \
            ("tiled", (tile, tile))
    w = min(window, sb)
    kept = w * (w + 1) // 2 + (sb - w) * w
    band = _band_pairs(1, sb, tile, tile, window)
    assert band == folds
    if window == 128:
        assert band <= 2 * kept
        assert _band_pairs(1, sb, 512, 512, window) >= 7.8 * kept
    if sb == 8192 and window == 128:
        assert _band_pairs(1, sb, 512, 512, window) == 8_126_464


def test_sinks_and_widths_diagnosed():
    """Sinks want one logit per query head; v's width, unlike q/k's, must
    be whole lanes."""
    mesh = default_mesh(1)

    def run(q_shape, v_shape, **kw):
        def f(x):
            q = jnp.zeros(q_shape, jnp.float32)
            v = jnp.zeros(v_shape, jnp.float32)
            return pallas_ring_attention(q, q, v, "world", 1, causal=True,
                                         interpret=True, **kw)

        jax.jit(jax.shard_map(lambda x: jnp.ravel(f(x))[:0], mesh=mesh,
                              in_specs=P("world"), out_specs=P("world"),
                              check_vma=False))(jnp.zeros(1, jnp.float32))

    with pytest.raises(ValueError, match="one logit per query head"):
        run((4, 8, 192), (4, 8, 128), sinks=jnp.zeros(3))
    with pytest.raises(NotImplementedError, match="value head dim"):
        run((4, 8, 192), (4, 8, 192))
    with pytest.raises(ValueError, match="equal"):
        run((4, 8, 192), (4, 16, 128))
    run((4, 8, 192), (4, 8, 128), sinks=jnp.zeros(4))  # accepted


@pytest.mark.parametrize("window", [None, 24], ids=["causal", "window"])
def test_sink_and_band_pairs_counted_once_per_trace(window):
    """``attention_sink`` rises once per trace that takes a sink, and
    ``attention_band_pairs`` by the windowed plan's folded pairs per
    windowed trace (none without a window)."""
    from mpi_tpu import mpit
    from mpi_tpu.tpu.pallas_attention import _band_pairs

    def loss(qb, vb, s):
        return jnp.sum(pallas_ring_attention(
            qb, qb, vb, "world", 2, causal=True, window=window, sinks=s,
            interpret=True, vmem_limit_bytes=_TILE8))

    fn = jax.shard_map(jax.grad(loss, argnums=(0, 1, 2)),
                       mesh=default_mesh(2),
                       in_specs=(P(None, "world"),) * 2 + (P(),),
                       out_specs=(P(None, "world"),) * 2 + (P(),),
                       check_vma=False)
    avals = (jax.ShapeDtypeStruct((2, 64, 192), jnp.float32),
             jax.ShapeDtypeStruct((2, 64, 128), jnp.float32),
             jax.ShapeDtypeStruct((2,), jnp.float32))
    before = {p: mpit.pvar_read(p)
              for p in ("attention_sink", "attention_band_pairs")}
    jax.jit(fn).trace(*avals)
    assert mpit.pvar_read("attention_sink") - before["attention_sink"] == 1
    band = 0 if window is None else _band_pairs(2, 32, 8, 8, window)
    assert mpit.pvar_read("attention_band_pairs") - \
        before["attention_band_pairs"] == band


def _pallas_outs(jaxpr, suffix):
    """[result avals] of every ``pallas_call`` in ``jaxpr`` (nested ones
    too) whose kernel name ends with ``suffix``."""
    from jax.extend import core as jcore

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            if str(eqn.params["name"]).endswith(suffix):
                found.append([v.aval for v in eqn.outvars])
            continue
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    found += _pallas_outs(sub, suffix)
    return found


def _lse64(q, k, window, causal, sinks):
    """float64 numpy logsumexp of every query row's scores (scale
    1/sqrt(q width)), the sink logit included: [Hq, S]."""
    g = q.shape[0] // k.shape[0]
    k = np.repeat(k, g, axis=0).astype(np.float64)
    s = np.einsum("hqd,hkd->hqk", q.astype(np.float64), k) / \
        np.sqrt(q.shape[-1])
    n = q.shape[1]
    i, j = np.arange(n)[:, None], np.arange(n)[None, :]
    if causal:
        keep = (j <= i) if window is None else (j <= i) & (j > i - window)
        s = np.where(keep, s, -np.inf)
    if sinks is not None:
        s = np.concatenate([s, np.broadcast_to(
            sinks.astype(np.float64)[:, None, None], s.shape[:2] + (1,))],
            axis=-1)
    top = s.max(axis=-1, keepdims=True)
    return (top + np.log(np.exp(s - top).sum(axis=-1, keepdims=True)))[..., 0]


@pytest.mark.parametrize("Sb,Hq,Hkv,d_qk,window,with_sinks,limit", [
    (32, 2, 2, 128, None, False, _TILE8),
    (32, 6, 1, 128, 5, False, _TILE8),
    (32, 1, 1, 128, 13, True, _TILE8),
    (256, 8, 1, 192, 128, True, None),
    (256, 6, 1, 192, None, True, None),
    (32, 2, 1, 128, "full", False, _TILE8)],
    ids=["causal", "w5_gqa6", "w13_sinks", "w128_sinks_split",
         "causal_gqa6_sinks_split", "full"])
def test_size_one_forward_writes_out_and_lse_directly(Sb, Hq, Hkv, d_qk,
                                                      window, with_sinks,
                                                      limit):
    """A ring of one writes out and lse from the forward's q loop: its
    ``pallas_call`` has only its results (out, and lse under
    differentiation), no m/l/o working buffers in HBM; lse matches a
    float64 logsumexp with the sink in it, and out dense attention."""
    rng = np.random.RandomState(Sb + Hq + int(with_sinks))
    q = rng.randn(Hq, Sb, d_qk).astype(np.float32)
    k = rng.randn(Hkv, Sb, d_qk).astype(np.float32)
    v = rng.randn(Hkv, Sb, 128).astype(np.float32)
    sinks = rng.uniform(2, 6, Hq).astype(np.float32) if with_sinks else None
    causal = window != "full"
    window = None if window == "full" else window
    extra = (sinks,) if with_sinks else ()

    def attn(qb, kb, vb, *s):
        return pallas_ring_attention(qb, kb, vb, "world", 1, causal=causal,
                                     window=window, sinks=s[0] if s else None,
                                     interpret=True, vmem_limit_bytes=limit)

    def with_lse(qb, kb, vb, *s):
        out, vjp = jax.vjp(lambda a, b, c: attn(a, b, c, *s), qb, kb, vb)
        lse, = [x for x in jax.tree_util.tree_leaves(vjp)
                if x.shape == (Hq * Sb, 128)]
        return out, lse

    smap = lambda f, n_out: jax.shard_map(
        f, mesh=default_mesh(1), in_specs=(P(),) * (3 + len(extra)),
        out_specs=(P(),) * n_out if n_out > 1 else P(), check_vma=False)
    kind = "ring" if window is None else "swa"
    fwd = f"mpi_tpu_{kind}_attention_fwd"
    out_aval = jax.core.ShapedArray((Hq * Sb, 128), jnp.float32)
    for f, n_out, results in ((attn, 1, [out_aval]),
                              (with_lse, 2, [out_aval, out_aval])):
        jaxpr = jax.make_jaxpr(smap(f, n_out))(q, k, v, *extra).jaxpr
        calls = _pallas_outs(jaxpr, fwd)
        assert len(calls) == 1
        assert [(a.shape, a.dtype) for a in calls[0]] == \
            [(a.shape, a.dtype) for a in results]
    out, lse = jax.jit(smap(with_lse, 2))(q, k, v, *extra)
    want = _lse64(q, k, window, causal, sinks)
    np.testing.assert_allclose(np.asarray(lse),
                               np.broadcast_to(want.reshape(-1, 1),
                                               (Hq * Sb, 128)),
                               rtol=0, atol=2e-5 * max(1, np.abs(want).max()))
    dense = _dense_window(q, k, v, window, causal, sinks=sinks)
    assert np.abs(np.asarray(out) - dense).max() <= \
        2e-5 * np.abs(dense).max()


@pytest.mark.parametrize("case,engaged", [
    ("size1", 1), ("size1_inference", 1), ("size1_forward_oob", 0),
    ("size1_vma_fallback", 0), ("p2", 0), ("p4", 0)])
def test_direct_out_counted_once_per_trace(case, engaged):
    """``attention_direct_out`` rises by one per trace whose forward writes
    out and lse from its q loop (a ring of one on the kernels, trained
    or not), and on no other path: not at P = 2 or 4, not on a
    fallback."""
    import warnings

    from mpi_tpu import mpit

    Pn = int(case[1]) if case.startswith("p") else 1
    kw = dict(causal=True, window=13, interpret=True,
              vmem_limit_bytes=_TILE8)
    if case == "size1_forward_oob":
        kw["vmem_limit_bytes"] = 1

    def attn(qb):
        return pallas_ring_attention(qb, qb, qb, "world", Pn, **kw)

    fn = attn if case == "size1_inference" else \
        jax.grad(lambda qb: jnp.sum(attn(qb)))
    aval = jax.ShapeDtypeStruct((Pn * 32, 128), jnp.float32)
    before = mpit.pvar_read("attention_direct_out")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # the fallbacks'
        jax.jit(jax.shard_map(fn, mesh=default_mesh(Pn),
                              in_specs=P("world"), out_specs=P("world"),
                              check_vma=case == "size1_vma_fallback")
                ).trace(aval)
    assert mpit.pvar_read("attention_direct_out") - before == engaged
