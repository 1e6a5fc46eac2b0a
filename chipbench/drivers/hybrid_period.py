"""Driver: one training step, forward plus backward, of the attention
cores of one whole period of a model whose ``hybrid_layer_pattern`` mixes
sliding-window layers (1) and full layers (0) that differ in head counts,
head widths and sinks: each sliding layer through
``pallas_ring_attention(q, k, v, "sp", 1, causal=True,
window=sliding_window, sinks=...)`` and each full layer with
``window=None``, inside one ``jax.jit(jax.shard_map(...))`` over a
one-chip ``sp`` mesh.  The layers of one kind are stacked and run under
one ``lax.scan``, so each kind's kernel pair is lowered and compiled
once.

Layer kinds, from the configuration: sliding layers take
``swa_num_attention_heads`` / ``swa_num_key_value_heads`` heads with q/k
``swa_head_dim`` and v ``swa_v_head_dim`` wide and, where
``add_swa_attention_sink_bias``, one sink logit per query head; full
layers take ``num_attention_heads`` / ``num_key_value_heads``,
``head_dim`` / ``v_head_dim`` and ``add_full_attention_sink_bias``.

Inputs: ``mix["inputs"]`` sets of q [n, Hq, S, dk], k [n, Hkv, S, dk],
v [n, Hkv, S, dv] (times ``attention_value_scale``) and dO [n, Hq, S,
dv] per layer kind (n layers of that kind) in the configuration's dtype,
and sink logits [n, Hq] float32 uniform in ``sink_logit_range``, made on
the device from the seed; step i uses set i mod inputs.  The check
compares each kept step's out, dq, dk, dv (and dsinks) of every layer
with the configuration's plain float32 reference, and fails where the
program's ``attention_window`` or ``attention_sink`` counter did not rise
at set-up (the sliding layers did not take the windowed tile ranges, or
their sinks).
"""

import numpy as np

import harness
import work

window_pairs = harness.load_module("drivers", "swa_period").window_pairs


def layer_flops(pairs, hq, dk, dv):
    """FLOPs of one layer's forward + backward over ``pairs`` kept
    (query, key) pairs a query head: forward QK^T (2 dk) and PV (2 dv),
    backward dV and dP (2 dv each), dQ and dK (2 dk each)."""
    return 6.0 * (dk + dv) * hq * pairs


def layer_bytes(seq, hq, hkv, dk, dv, itemsize):
    """Least HBM bytes of one layer's forward + backward: read q, k, v,
    dO and write out, dq, dk, dv once each."""
    return 2.0 * itemsize * seq * (hq + hkv) * (dk + dv)


def layer_kinds(cfg):
    """[(window, sinks, layers, hq, hkv, dk, dv)] of the period, sliding
    layers first."""
    pattern = cfg["hybrid_layer_pattern"]
    kinds = [(cfg["sliding_window"], cfg["add_swa_attention_sink_bias"],
              pattern.count(1), cfg["swa_num_attention_heads"],
              cfg["swa_num_key_value_heads"], cfg["swa_head_dim"],
              cfg["swa_v_head_dim"]),
             (None, cfg["add_full_attention_sink_bias"], pattern.count(0),
              cfg["num_attention_heads"], cfg["num_key_value_heads"],
              cfg["head_dim"], cfg["v_head_dim"])]
    return [k for k in kinds if k[2]]


class Driver:
    issue_span = harness.ISSUE_SPAN

    def __init__(self, cfg, mix, seed, devices, ref):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from mpi_tpu import mpit
        from mpi_tpu.tpu.pallas_attention import pallas_ring_attention

        self.cfg, self.mix, self.ref = cfg, mix, ref
        seq = mix["seq_len"]
        self.kinds = layer_kinds(cfg)
        dtype = jnp.dtype(cfg["dtype"])
        value_scale = cfg["attention_value_scale"]
        sink_lo, sink_hi = cfg["sink_logit_range"]
        self.mesh = Mesh(np.array(devices[:1]), ("sp",))
        self.interpret = devices[0].platform == "cpu"
        interpret = self.interpret
        self.pvars = ("attention_window", "attention_sink",
                      "attention_band_pairs")
        self.pvars_before = {p: mpit.pvar_read(p) for p in self.pvars}

        def group(window, sinks, q, k, v, do):
            """One kind's layers, stacked on axis 0, under one scan."""
            def layer(_, x):
                q_, k_, v_, do_, s_ = x
                args = (q_, k_, v_) + (() if s_ is None else (s_,))
                out, vjp = jax.vjp(
                    lambda a, b, c, *s: pallas_ring_attention(
                        a, b, c, "sp", 1, causal=True, window=window,
                        sinks=s[0] if s else None, interpret=interpret),
                    *args)
                return None, (out,) + tuple(vjp(do_))

            return jax.lax.scan(layer, None, (q, k, v, do, sinks))[1]

        def step(*xs):
            outs = ()
            for n, (window, sinks, *_) in enumerate(self.kinds):
                q, k, v, do, s = xs[5 * n:5 * n + 5]
                outs += group(window, s if sinks else None, q, k, v, do)
            return outs

        spec = P(None, None, "sp", None)
        in_specs, out_specs = (), ()
        for _, sinks, *_ in self.kinds:
            in_specs += (spec,) * 4 + (P(),)
            out_specs += (spec,) * 4 + ((P(),) if sinks else ())
        self.fn = jax.jit(jax.shard_map(step, mesh=self.mesh,
                                        in_specs=in_specs,
                                        out_specs=out_specs,
                                        check_vma=False))

        def make(key):
            arrays = ()
            for kkey, (_, _, n, hq, hkv, dk, dv) in zip(
                    jax.random.split(key, len(self.kinds)), self.kinds):
                kq, kk, kv, kd, ks = jax.random.split(kkey, 5)
                arrays += (jax.random.normal(kq, (n, hq, seq, dk), dtype),
                           jax.random.normal(kk, (n, hkv, seq, dk), dtype),
                           (value_scale * jax.random.normal(
                               kv, (n, hkv, seq, dv), jnp.float32)
                            ).astype(dtype),
                           jax.random.normal(kd, (n, hq, seq, dv), dtype),
                           jax.random.uniform(ks, (n, hq), jnp.float32,
                                              sink_lo, sink_hi))
            return arrays

        shardings = tuple(NamedSharding(self.mesh, s) for s in in_specs)
        make = jax.jit(make, out_shardings=shardings)
        self.arg_shapes = jax.eval_shape(make, jax.random.key(0))
        self.inputs = [] if seed is None else [
            make(harness.prng_key(seed, j)) for j in range(mix["inputs"])]
        self.fixed_units = {"tokens": seq, "flops": 0.0, "bytes": 0.0}
        for window, _, n, hq, hkv, dk, dv in self.kinds:
            kind = "swa" if window is not None else "full"
            pairs = (work.causal_pairs(seq) if window is None
                     else window_pairs(seq, window))
            flops = n * layer_flops(pairs, hq, dk, dv)
            nbytes = n * layer_bytes(seq, hq, hkv, dk, dv, dtype.itemsize)
            self.fixed_units.update({f"{kind}_flops": flops,
                                     f"{kind}_bytes": nbytes})
            self.fixed_units["flops"] += flops
            self.fixed_units["bytes"] += nbytes
            if window is not None:
                self.fixed_units["swa_kept_pairs"] = pairs

    def rises(self):
        """How far each counter of ``self.pvars`` rose since set-up began
        (the window traces nothing, so after it: the set-up's rise)."""
        from mpi_tpu import mpit

        return {p: mpit.pvar_read(p) - self.pvars_before[p]
                for p in self.pvars}

    @property
    def units(self):
        """The work a step does, and ``band_pairs``: the query-key pairs
        (one query head) a windowed trace's tile plan folds, from the
        counters' rise at set-up."""
        units = dict(self.fixed_units)
        rise = self.rises()
        if rise["attention_window"] > 0:
            units["band_pairs"] = (rise["attention_band_pairs"]
                                   / rise["attention_window"])
        return units

    def call(self, i):
        return self.fn(*self.inputs[i % len(self.inputs)])

    def free(self):
        self.fn = None

    def check(self, kept, control=False):
        """One reading per kept step: the worst, over the period's layers,
        of the relative L2 and max errors of out, dq, dk and dv (and the
        relative L2 error of dsinks) against the float32 reference; and
        whether the sliding layers took the windowed tile ranges and the
        sinks.  ``control``: True, or a name in the reference's
        ``CONTROLS``, makes that control stand in for the program's
        output (True: the reference computed in float8)."""
        rise = self.rises()
        readings = []
        for i, got in kept:
            xs = self.inputs[i % len(self.inputs)]
            worst = {}
            at = 0
            for n, (window, sinks, count, *_) in enumerate(self.kinds):
                width = 5 if sinks else 4
                for layer in range(count):
                    q, k, v, do, s = (x[layer] for x in xs[5 * n:5 * n + 5])
                    outs = (None if control else
                            tuple(y[layer] for y in got[at:at + width]))
                    errs = self.ref.check_layer(
                        (q, k, v, do), outs, window, s if sinks else None,
                        control="float8" if control is True else control)
                    for key, value in errs.items():
                        worst[key] = max(worst.get(key, 0.0), value)
                at += width
            worst["window_not_engaged"] = int(rise["attention_window"] <= 0)
            worst["sink_not_engaged"] = int(rise["attention_sink"] <= 0)
            readings.append(worst)
        return readings
