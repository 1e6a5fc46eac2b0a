"""Plain reference of ``mimo_v2_flash_attn``: causal GQA attention with
q/k heads ``head_dim`` wide and v heads ``v_head_dim`` wide, each query
over all keys up to itself (full layers) or over the ``sliding_window``
newest of them with a per-head sink logit in the softmax denominator
(sliding layers), forward and backward, in float32 at ``highest`` matmul
precision, with plain ``jax.numpy``; and the comparison that decides
``correct``.  Imports nothing of the program.

With a sink s_h, query i of head h weighs key j by
e^{z_ij} / (e^{s_h} + sum_j' e^{z_ij'}) and the sink carries no value, so
the sink's share p_i = e^{s_h} / (...) takes weight off every key, and
dL/ds_h = -sum_i p_i (dO_i . O_i).

The reference runs in blocks of query rows.  A full layer's block sees
every key, masked; a sliding layer's block sees the keys of its window
only: K and V are padded with ``window`` zero rows in front, and block
rows ``[r, r + B)`` take the padded rows ``[r, r + window + B)``, the
positions ``r - window .. r + B - 1``, masked by position.  The float8
control is the same computation with every matmul operand rounded to
float8 (e4m3), the precision below the configuration's bfloat16.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NAMES = ("out", "dq", "dk", "dv")


def _ein(spec, a, b, low):
    if low:
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("window", "low", "block"))
def layer_fwd_bwd(q, k, v, do, sinks, *, window, low, block):
    """One layer's out [Hq, S, dv], dq [Hq, S, dk], dk [Hkv, S, dk], dv
    [Hkv, S, dv] and dsinks [Hq] (zeros where ``sinks`` is None), float32.

    q: [Hq, S, dk]; k: [Hkv, S, dk]; v: [Hkv, S, dv]; do: [Hq, S, dv]
    (any float dtype); sinks: [Hq] or None.  Query i attends key j iff
    ``j <= i`` and, with a ``window``, ``j > i - window``.  Scale
    1/sqrt(dk)."""
    hq, seq, d = q.shape
    hkv, _, d_v = v.shape
    g = hq // hkv
    scale = 1.0 / np.sqrt(d)
    f32 = jnp.float32
    qg = q.astype(f32).reshape(hkv, g, seq, d)
    dog = do.astype(f32).reshape(hkv, g, seq, d_v)
    sink = None if sinks is None else \
        sinks.astype(f32).reshape(hkv, g, 1, 1)
    # keys a block sees: all of them, or the window before it (padded)
    pad = 0 if window is None else window
    span = seq if window is None else window + block
    k = jnp.pad(k.astype(f32), ((0, 0), (pad, 0), (0, 0)))
    v = jnp.pad(v.astype(f32), ((0, 0), (pad, 0), (0, 0)))

    def body(b, carry):
        out, dq, dk, dv, ds = carry
        r = b * block
        k0 = 0 if window is None else r  # first padded key row seen
        qb = jax.lax.dynamic_slice_in_dim(qg, r, block, axis=2)
        dob = jax.lax.dynamic_slice_in_dim(dog, r, block, axis=2)
        kb = jax.lax.dynamic_slice_in_dim(k, k0, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, k0, span, axis=1)
        qpos = r + jnp.arange(block)[:, None]
        kpos = k0 - pad + jnp.arange(span)[None, :]
        keep = (kpos <= qpos) & (kpos >= 0)
        if window is not None:
            keep = keep & (kpos > qpos - window)
        s = jnp.where(keep, _ein("hgqd,hkd->hgqk", qb, kb, low) * scale,
                      -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink)
        e = jnp.exp(s - m)
        denom = jnp.sum(e, axis=-1, keepdims=True)
        if sink is not None:
            e_sink = jnp.exp(sink - m)
            denom = denom + e_sink
        p = e / denom
        ob = _ein("hgqk,hkd->hgqd", p, vb, low)
        dp = _ein("hgqd,hkd->hgqk", dob, vb, low)
        delta = jnp.sum(dob * ob, axis=-1, keepdims=True)
        if sink is not None:
            ds = ds - jnp.sum(e_sink / denom * delta, axis=(2, 3))
        dsb = p * (dp - delta)
        dqb = _ein("hgqk,hkd->hgqd", dsb, kb, low) * scale
        dkb = _ein("hgqk,hgqd->hkd", dsb, qb, low) * scale
        dvb = _ein("hgqk,hgqd->hkd", p, dob, low)
        add = lambda acc, x: jax.lax.dynamic_update_slice_in_dim(
            acc, jax.lax.dynamic_slice_in_dim(acc, k0, span, axis=1) + x,
            k0, axis=1)
        out = jax.lax.dynamic_update_slice_in_dim(out, ob, r, axis=2)
        dq = jax.lax.dynamic_update_slice_in_dim(dq, dqb, r, axis=2)
        return out, dq, add(dk, dkb), add(dv, dvb), ds

    carry = (jnp.zeros((hkv, g, seq, d_v), f32),
             jnp.zeros((hkv, g, seq, d), f32),
             jnp.zeros((hkv, pad + seq, d), f32),
             jnp.zeros((hkv, pad + seq, d_v), f32),
             jnp.zeros((hkv, g), f32))
    out, dq, dk, dv, ds = jax.lax.fori_loop(0, seq // block, body, carry)
    return (out.reshape(hq, seq, d_v), dq.reshape(hq, seq, d), dk[:, pad:],
            dv[:, pad:], ds.reshape(hq))


def _block(hq, seq, keys):
    """Query rows per block: a power of two dividing ``seq`` whose score
    tensors ([Hq, rows, keys] float32) stay near 256 MiB."""
    b = 8
    while b * 2 <= seq and seq % (b * 2) == 0 and \
            hq * b * 2 * keys * 4 <= 1 << 28:
        b *= 2
    return b


def layer_reference(q, k, v, do, window, sinks=None, low=False):
    """(out, dq, dk, dv, dsinks) of one layer, float32, on the inputs'
    device."""
    hq, seq, _ = q.shape
    keys = seq if window is None else window + _block(hq, seq, window)
    block = _block(hq, seq, keys)
    return layer_fwd_bwd(q, k, v, do, sinks, window=window, low=low,
                         block=block)


@jax.jit
def _errs(a, b):
    """→ (sum of squared differences, sum of squares of b, max |a - b|,
    max |b|)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return (jnp.sum((a - b) ** 2), jnp.sum(b * b), jnp.max(jnp.abs(a - b)),
            jnp.max(jnp.abs(b)))


# what stands in for the program's output in a control: the reference
# computed in float8, with the window one key short, or without the sinks
CONTROLS = {
    "float8": lambda ins, window, sinks: layer_reference(
        *ins, window, sinks, low=True),
    "window_short": lambda ins, window, sinks: layer_reference(
        *ins, None if window is None else window - 1, sinks),
    "sinks_dropped": lambda ins, window, sinks: layer_reference(
        *ins, window, None),
}


def check_layer(inputs, got, window, sinks=None, control=None):
    """Errors of one layer's (out, dq, dk, dv[, dsinks]) against the
    float32 reference: per tensor the relative L2 error, and the largest
    absolute error over the reference's largest magnitude (which sees one
    token altered where the L2 error of 10**8 elements would not); with
    ``sinks``, the relative L2 error of dsinks too.  ``got=None``: the
    ``control`` named in :data:`CONTROLS` stands in."""
    want = layer_reference(*inputs, window, sinks)
    if got is None:
        got = CONTROLS[control](inputs, window, sinks)
    errs = {}
    for name, a, b in zip(NAMES, got, want):
        e, r, gap, top = (float(x) for x in _errs(a, b))
        errs[f"{name}_rel_err"] = float(np.sqrt(e / r))
        errs[f"{name}_max_err"] = gap / top
    if sinks is not None:
        e, r, _, _ = (float(x) for x in _errs(got[4], want[4]))
        errs["dsinks_rel_err"] = float(np.sqrt(e / r))
    return errs
