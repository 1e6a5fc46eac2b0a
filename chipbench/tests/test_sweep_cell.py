"""``allreduce.f32.sweep`` end to end on 4 virtual CPU devices at tiny
sizes: it runs correct and reports its end-to-end metrics, each size has
a program and an input of its own, the same seed makes the same inputs,
the control fails its limit, and a size whose program returns its input
comes out not correct."""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import harness
import tiny

CELL = "allreduce.f32.sweep"
SIZES = [4 * 128 * n for n in (8, 16, 32, 64, 128)]  # 4 KiB .. 64 KiB


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.tiny_root(tmp_path_factory.mktemp("bench"))
    path = os.path.join(root, "chipbench", "traffic", "allreduce_sweep.json")
    with open(path) as f:
        mix = json.load(f)
    mix["sizes_per_chip"] = SIZES
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def _cell(root):
    return harness.resolve_cell(harness.load_spec(root), CELL, root)


def _run(cell, seed=2**31 + 29):
    return harness.run_cell(cell, seed, 0.3, False, jax.devices(),
                            time.perf_counter())


def test_cell_runs_correct(root):
    cell = _cell(root)
    assert cell.chips == 4
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= len(SIZES) and res["failed"] == 0
    assert set(res["metrics"]) == {"allreduce_busbw", "allreduce_p95_ms",
                                   "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "allreduce.ici_share", "device_idle.allreduce"}


def test_sizes_cycle_with_their_own_programs(root):
    """Call i reduces size i mod 5 through that size's own program; the
    mean bus bytes a call are the cycle's: 1.5 x the sizes' sum / 5."""
    d = harness.build_driver(_cell(root), 7, jax.devices())
    assert [x.shape for x in d.inputs] == [(4 * n // 512, 128) for n in SIZES]
    assert len({id(f) for f in d.fns}) == len(SIZES)
    assert d.units["bus_bytes"] == 1.5 * sum(SIZES) / len(SIZES)
    for i in range(2 * len(SIZES)):
        assert d.call(i).shape == d.inputs[i % len(SIZES)].shape
    with open(os.path.join(tiny.BENCH, "traffic", "allreduce_sweep.json")) as f:
        assert sum(json.load(f)["sizes_per_chip"]) == 85_004_288


def test_same_seed_same_inputs(root):
    cell = _cell(root)
    a, b, c = (harness.build_driver(cell, s, jax.devices())
               for s in (123, 123, 124))
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(a.inputs, b.inputs))
    assert not all(bool(jnp.array_equal(x, y))
                   for x, y in zip(a.inputs, c.inputs))


def test_control_fails_the_limits(root):
    import calibrate

    cell = _cell(root)
    program, control, _ = calibrate.readings(cell, 5, 0.2, jax.devices())
    assert program == {"mismatched_elements": 0}
    assert control["mismatched_elements"] > 0


def test_size_returning_its_input_is_not_correct(monkeypatch, root):
    """The 1 MiB size's program returns its input: the exchange left out
    at one size of the five."""
    real = harness.build_driver

    def build(cell, seed, devices):
        d = real(cell, seed, devices)
        d.fns[2] = jax.jit(jax.shard_map(lambda x: x, mesh=d.mesh,
                                         in_specs=P("world"),
                                         out_specs=P("world")))
        return d

    monkeypatch.setattr(harness, "build_driver", build)
    cell = _cell(root)
    cell.mix["kept"] = 1000  # every call of the window is checked
    res = harness.run_cell(cell, 99, 0.2, False, jax.devices(),
                           time.perf_counter())
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
