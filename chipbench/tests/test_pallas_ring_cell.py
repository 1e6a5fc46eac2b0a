"""``allreduce.f32.256m.pallas_ring`` end to end on 4 virtual CPU devices
at a tiny size (the Pallas ring in interpret mode): both new cells find
their files by name, the cell runs correct through ``pallas_ring`` and no
other algorithm, the same seed makes the same inputs, the control fails
its limit, and a program that returns its input comes out not correct."""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

import harness
import tiny

CELL = "allreduce.f32.256m.pallas_ring"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.tiny_root(tmp_path_factory.mktemp("bench"))
    path = os.path.join(root, "chipbench", "traffic",
                        "allreduce_256m_pallas_ring.json")
    with open(path) as f:
        mix = json.load(f)
    mix["bytes_per_chip"] = 4 * 128 * 64
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def _cell(root):
    return harness.resolve_cell(harness.load_spec(root), CELL, root)


def _run(cell, seed=2**31 + 31):
    return harness.run_cell(cell, seed, 0.3, False, jax.devices(),
                            time.perf_counter())


@pytest.mark.parametrize("name", [CELL, "attn.swa128.sp1.s8k"])
def test_new_cells_find_their_files(name):
    """Each new cell's configuration, plain reference, traffic mix,
    driver and metric readers are found by name."""
    cell = harness.resolve_cell(harness.load_spec(), name)
    assert os.path.isfile(cell.reference)
    harness.load_module("drivers", cell.mix["driver"], cell.root)
    for m in cell.end_to_end + cell.per_layer:
        assert hasattr(harness.metric_reader(m["name"], cell.root), "read")


def test_cell_runs_correct_through_pallas_ring(root, monkeypatch):
    """Through the kernel itself: the interpreter runs it only without
    vma typing (with it, it runs the loud ppermute fallback, which the
    harness counts against the run), so the CPU rehearsal turns vma
    typing off; the chip compiles the kernel either way."""
    import functools

    from mpi_tpu.tpu import TpuCommunicator

    monkeypatch.setattr(jax, "shard_map", functools.partial(
        jax.shard_map, check_vma=False))
    cell = _cell(root)
    assert cell.chips == 4 and cell.cfg["algorithm"] == "auto"
    seen = []
    real = TpuCommunicator.allreduce

    def allreduce(self, x, *a, algorithm="auto", **kw):
        seen.append(algorithm)
        return real(self, x, *a, algorithm=algorithm, **kw)

    monkeypatch.setattr(TpuCommunicator, "allreduce", allreduce)
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"allreduce_busbw", "allreduce_p95_ms",
                                   "setup_s"}
    assert seen and set(seen) == {"pallas_ring"}


def test_same_seed_same_inputs(root):
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


def test_input_returned_is_not_correct(root, monkeypatch):
    real = harness.build_driver

    def build(cell, seed, devices):
        d = real(cell, seed, devices)
        d.fn = lambda x: x
        return d

    monkeypatch.setattr(harness, "build_driver", build)
    res = _run(_cell(root), seed=99)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
