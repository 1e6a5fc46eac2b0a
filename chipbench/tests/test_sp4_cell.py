"""``attn.sp4.s32k`` end to end on 4 virtual CPU devices at a tiny size
(the Pallas ring kernels in interpret mode): it runs correct and reports
its end-to-end metrics, the same seed makes the same inputs, the control
fails its limits, and a run with the exchange between chips left out is
not correct."""

import json
import os
import time

import jax
import pytest
from jax.sharding import PartitionSpec as P

import harness
import tiny

CELL = "attn.sp4.s32k"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.tiny_root(tmp_path_factory.mktemp("bench"))
    path = os.path.join(root, "chipbench", "traffic", "attn_s32k_sp4.json")
    with open(path) as f:
        mix = json.load(f)
    mix["seq_len"] = 256  # 64 tokens a chip
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def _cell(root):
    return harness.resolve_cell(harness.load_spec(root), CELL, root)


def _run(cell, seed=2**31 + 23):
    return harness.run_cell(cell, seed, 0.3, False, jax.devices(),
                            time.perf_counter())


def test_cell_runs_correct(root):
    cell = _cell(root)
    assert cell.chips == 4 and cell.mix["sp"] == 4
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"attn_tokens_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "attn.mfu", "device_idle.attn", "attn.kernel_roofline"}


def test_same_seed_same_inputs(root):
    import jax.numpy as jnp

    cell = _cell(root)
    a, b, c = (harness.build_driver(cell, s, jax.devices())
               for s in (123, 123, 124))
    leaves = lambda d: jax.tree.leaves(d.inputs)
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(leaves(a), leaves(b)))
    assert not all(bool(jnp.array_equal(x, y))
                   for x, y in zip(leaves(a), leaves(c)))


def test_control_fails_the_limits(root):
    import calibrate

    cell = _cell(root)
    program, control, _ = calibrate.readings(cell, 5, 0.2, jax.devices())
    limits = cell.mix["limits"]
    assert all(v <= limits[k] for k, v in program.items()), program
    assert any(v > limits[k] for k, v in control.items()), control


def test_exchange_left_out_is_not_correct(monkeypatch, root):
    """Each chip attends only to its own block of the sequence: the ring
    exchange between chips is left out."""
    from mpi_tpu.tpu.pallas_attention import pallas_ring_attention

    real = harness.build_driver

    def build(cell, seed, devices):
        d = real(cell, seed, devices)

        def step(q, k, v, do):
            def attn(q, k, v):
                return pallas_ring_attention(q, k, v, "sp", 1, causal=True,
                                             interpret=True)
            out, vjp = jax.vjp(attn, q, k, v)
            return (out,) + tuple(vjp(do))

        spec = P(None, "sp", None)
        d.fn = jax.jit(jax.shard_map(step, mesh=d.mesh, in_specs=(spec,) * 4,
                                     out_specs=(spec,) * 4, check_vma=False))
        return d

    monkeypatch.setattr(harness, "build_driver", build)
    res = _run(_cell(root), seed=99)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
