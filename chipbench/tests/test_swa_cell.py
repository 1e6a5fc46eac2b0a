"""``attn.swa4k.sp1.s16k`` end to end on a virtual CPU device at a tiny
size (the Pallas kernels in interpret mode, a window shorter than a tile,
so the windowed tile ranges skip tiles): it runs correct and reports its
end-to-end metrics, the same seed makes the same inputs, the control
fails its limits, and each fault the check must catch comes out not
correct: the window left out, the window one key short, one layer's
output swapped for another's."""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

import harness
import tiny

CELL = "attn.swa4k.sp1.s16k"
WINDOW = 40


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.tiny_root(tmp_path_factory.mktemp("bench"))
    for kind, name, patch in (
            ("configs", "trinity_large_attn",
             # a GQA group of 6, as published; the default VMEM budget
             # tiles 2048-row blocks in 512-row tiles
             {"num_attention_heads": 6, "num_key_value_heads": 1,
              "sliding_window": WINDOW}),
            ("traffic", "attn_swa_s16k_sp1", {"seq_len": 2048})):
        path = os.path.join(root, "chipbench", kind, name + ".json")
        with open(path) as f:
            data = json.load(f)
        data.update(patch)
        with open(path, "w") as f:
            json.dump(data, f)
    return root


def _cell(root):
    return harness.resolve_cell(harness.load_spec(root), CELL, root)


def _run(cell, seed=2**31 + 25):
    return harness.run_cell(cell, seed, 0.3, False, jax.devices(),
                            time.perf_counter())


def test_cell_runs_correct(root):
    cell = _cell(root)
    assert cell.chips == 1
    assert cell.cfg["layer_types"] == ["sliding_attention"] * 3 + \
        ["full_attention"]
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"attn_tokens_per_s", "setup_s"}
    assert res["checks"]["window_not_engaged"]["value"] == 0
    assert {m["name"] for m in cell.per_layer} == {
        "attn.mfu", "device_idle.attn", "attn.swa_roofline",
        "attn.swa_time_share"}


def test_work_counts_the_period():
    """The driver's pair counts at the cell's real sizes: 3 windowed
    layers of 58,722,304 pairs and one causal of 134,225,920."""
    import work

    drv = harness.load_module("drivers", "swa_period")
    assert drv.window_pairs(16384, 4096) == 58_722_304
    assert work.causal_pairs(16384) == 134_225_920
    assert drv.window_pairs(100, 4096) == work.causal_pairs(100)
    brute = sum(min(i + 1, 7) for i in range(50))
    assert drv.window_pairs(50, 7) == brute


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


def _window_changed(monkeypatch, change):
    """The program's sliding layers run with window ``change(window)``."""
    from mpi_tpu.tpu import pallas_attention as pa

    real = pa.pallas_ring_attention

    def attn(*a, window=None, **kw):
        return real(*a, window=None if window is None else change(window),
                    **kw)

    monkeypatch.setattr(pa, "pallas_ring_attention", attn)


def _swapped(monkeypatch):
    """The first sliding layer's outputs swapped for the second's."""
    real = harness.build_driver

    def build(cell, seed, devices):
        d = real(cell, seed, devices)
        fn = d.fn

        def f(*a):
            outs = fn(*a)
            swap = lambda x: x.at[0].set(x[1])
            return tuple(swap(x) for x in outs[:4]) + outs[4:]
        d.fn = f
        return d

    monkeypatch.setattr(harness, "build_driver", build)


FAULTS = {
    "window_left_out": lambda mp: _window_changed(mp, lambda w: None),
    "window_one_key_short": lambda mp: _window_changed(mp, lambda w: w - 1),
    "layer_swapped": _swapped,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(monkeypatch, root, fault):
    """Each fault breaks a limit of the errors themselves, not only the
    window counter."""
    FAULTS[fault](monkeypatch)
    res = _run(_cell(root), seed=99)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
    broken = [k for k, c in res["checks"].items()
              if k.endswith("_err") and not c["value"] <= c["limit"]]
    assert broken, res["checks"]
