"""``attn.swa128.sp1.s8k`` end to end on a virtual CPU device at a tiny
size (the Pallas kernels in interpret mode, q/k heads 192 wide and v 128,
a 128-key window with sinks on 128-row tiles, GQA groups of 8 and 16): it
runs correct and reports its end-to-end metrics, the pair and FLOP counts
of the period at its real sizes, the same seed makes the same inputs,
each control fails a limit (the reference in float8, with the window one
key short, without the sinks), and each fault the check must catch comes
out not correct: the window one key short, the sinks dropped, one layer's
output swapped for another's."""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

import harness
import tiny

CELL = "attn.swa128.sp1.s8k"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.tiny_root(tmp_path_factory.mktemp("bench"))
    for kind, name, patch in (
            ("configs", "mimo_v2_flash_attn",
             # the published groups (8 sliding, 16 full), widths and window
             {"swa_num_attention_heads": 8, "swa_num_key_value_heads": 1,
              "num_attention_heads": 16, "num_key_value_heads": 1}),
            ("traffic", "attn_swa128_s8k_sp1", {"seq_len": 512})):
        path = os.path.join(root, "chipbench", kind, name + ".json")
        with open(path) as f:
            data = json.load(f)
        data.update(patch)
        with open(path, "w") as f:
            json.dump(data, f)
    return root


def _cell(root):
    return harness.resolve_cell(harness.load_spec(root), CELL, root)


def _run(cell, seed=2**31 + 29):
    return harness.run_cell(cell, seed, 0.3, False, jax.devices(),
                            time.perf_counter())


def test_cell_runs_correct(root):
    cell = _cell(root)
    assert cell.chips == 1
    assert cell.cfg["hybrid_layer_pattern"] == [1] * 5 + [0]
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"attn_tokens_per_s", "setup_s"}
    assert res["checks"]["window_not_engaged"]["value"] == 0
    assert res["checks"]["sink_not_engaged"]["value"] == 0
    assert "dsinks_rel_err" in res["checks"]
    assert {m["name"] for m in cell.per_layer} == {
        "attn.mfu", "device_idle.attn", "attn.swa_roofline",
        "attn.swa_time_share", "attn.swa_fold_share", "attn.full_roofline"}


def test_fold_share_reads_the_tile_plan(root):
    """The window's tile plan at 128-row tiles folds 2 tile pairs a query
    tile but the first: at S 512, 7 pairs of 128 x 128 a head, of which
    the window keeps 8,256 + 384 x 128."""
    from types import SimpleNamespace

    drv = harness.build_driver(_cell(root), 5, jax.devices())
    harness.warm(drv)
    units = drv.units
    assert units["band_pairs"] == 7 * 128 * 128
    assert units["swa_kept_pairs"] == 128 * 129 // 2 + 384 * 128
    share = harness.metric_reader("attn.swa_fold_share").read(
        SimpleNamespace(units=units))
    assert share == pytest.approx(100 * 57_408 / 114_688)


def test_work_counts_the_period():
    """The driver's counts at the cell's real sizes: five windowed layers
    of 1,040,448 kept pairs a head, one causal of 33,558,528; 6 x 320 FLOPs
    a pair and query head; the least bytes of 192-wide q/dq/k/dk and
    128-wide out/dO/v/dv."""
    import work

    drv = harness.load_module("drivers", "hybrid_period")
    assert drv.window_pairs(8192, 128) == 1_040_448
    assert work.causal_pairs(8192) == 33_558_528
    brute = sum(min(i + 1, 128) for i in range(8192))
    assert drv.window_pairs(8192, 128) == brute
    assert drv.layer_flops(1, 1, 192, 128) == 1920
    assert drv.layer_flops(1, 1, 128, 128) == 12 * 128  # the 12 d of work.py
    with open(os.path.join(harness.HERE, "configs",
                           "mimo_v2_flash_attn.json")) as f:
        cfg = json.load(f)
    kinds = drv.layer_kinds(cfg)
    assert kinds == [(128, True, 5, 64, 8, 192, 128),
                     (None, False, 1, 64, 4, 192, 128)]
    full = drv.layer_flops(work.causal_pairs(8192), 64, 192, 128)
    assert full == pytest.approx(4.1238e12, rel=1e-4)
    # q/dq and dO/out 64 heads, k/dk and v/dv 8 heads, bf16
    assert drv.layer_bytes(8192, 64, 8, 192, 128, 2) == \
        2 * 8192 * (64 * (2 * 192 + 2 * 128) + 8 * (2 * 192 + 2 * 128))


def test_same_seed_same_inputs(root):
    cell = _cell(root)
    a, b, c = (harness.build_driver(cell, s, jax.devices())
               for s in (123, 123, 124))
    leaves = lambda d: jax.tree.leaves(d.inputs)
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(leaves(a), leaves(b)))
    assert not all(bool(jnp.array_equal(x, y))
                   for x, y in zip(leaves(a), leaves(c)))


@pytest.mark.parametrize("control", ["float8", "window_short",
                                     "sinks_dropped"])
def test_controls_fail_the_limits(root, control):
    """The program's readings keep every limit; each control breaks one of
    the error limits."""
    cell = _cell(root)
    drv = harness.build_driver(cell, 7, jax.devices())
    harness.warm(drv)
    win = harness.window(drv, 0.2, 7, cell.mix["kept"])
    limits = cell.mix["limits"]
    worst = lambda rs: {k: max(r[k] for r in rs) for k in rs[0]}
    program = worst(drv.check(win.kept))
    assert all(v <= limits[k] for k, v in program.items()), program
    got = worst(drv.check(win.kept, control=control))
    assert any(v > limits[k] for k, v in got.items() if k.endswith("_err")), got


def _attention_changed(monkeypatch, change):
    """The program's sliding layers run with ``change(kwargs)``."""
    from mpi_tpu.tpu import pallas_attention as pa

    real = pa.pallas_ring_attention

    def attn(*a, **kw):
        if kw.get("window") is not None:
            kw = change(dict(kw))
        return real(*a, **kw)

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
            return tuple(swap(x) for x in outs[:5]) + outs[5:]
        d.fn = f
        return d

    monkeypatch.setattr(harness, "build_driver", build)


FAULTS = {
    "window_one_key_short": lambda mp: _attention_changed(
        mp, lambda kw: dict(kw, window=kw["window"] - 1)),
    "sinks_dropped": lambda mp: _attention_changed(
        mp, lambda kw: dict(kw, sinks=jnp.full_like(kw["sinks"], -1e30))),
    "layer_swapped": _swapped,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(monkeypatch, root, fault):
    """Each fault breaks a limit of the errors themselves, not only the
    counters."""
    FAULTS[fault](monkeypatch)
    res = _run(_cell(root), seed=99)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
    broken = [k for k, c in res["checks"].items()
              if k.endswith("_err") and not c["value"] <= c["limit"]]
    assert broken, res["checks"]
