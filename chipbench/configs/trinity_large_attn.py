"""Plain reference of ``trinity_large_attn``: causal GQA attention, each
query over all keys up to itself (full layers) or over the
``sliding_window`` newest of them (sliding layers), forward and backward,
in float32 at ``highest`` matmul precision, with plain ``jax.numpy``; and
the comparison that decides ``correct``.  Imports nothing of the program.

The reference runs in blocks of query rows.  A full layer's block sees
every key, masked; a sliding layer's block sees the keys of its window
only: K and V are padded with ``window`` zero rows in front, and block
rows ``[r, r + B)`` take the padded rows ``[r, r + window + B)``, the
positions ``r - window .. r + B - 1``, masked by position.  The control is
the same computation with every matmul operand rounded to float8 (e4m3),
the precision below the configuration's bfloat16.
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
def layer_fwd_bwd(q, k, v, do, *, window, low, block):
    """One layer's out, dq [Hq, S, d] and dk, dv [Hkv, S, d], float32.

    q, do: [Hq, S, d]; k, v: [Hkv, S, d] (any float dtype).  Query i
    attends key j iff ``j <= i`` and, with a ``window``, ``j > i -
    window``."""
    hq, seq, d = q.shape
    hkv = k.shape[0]
    g = hq // hkv
    scale = 1.0 / np.sqrt(d)
    f32 = jnp.float32
    qg = q.astype(f32).reshape(hkv, g, seq, d)
    dog = do.astype(f32).reshape(hkv, g, seq, d)
    # keys a block sees: all of them, or the window before it (padded)
    pad = 0 if window is None else window
    span = seq if window is None else window + block
    k = jnp.pad(k.astype(f32), ((0, 0), (pad, 0), (0, 0)))
    v = jnp.pad(v.astype(f32), ((0, 0), (pad, 0), (0, 0)))

    def body(b, carry):
        out, dq, dk, dv = carry
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
        s = _ein("hgqd,hkd->hgqk", qb, kb, low) * scale
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        ob = _ein("hgqk,hkd->hgqd", p, vb, low)
        dp = _ein("hgqd,hkd->hgqk", dob, vb, low)
        delta = jnp.sum(dob * ob, axis=-1, keepdims=True)
        ds = p * (dp - delta)
        dqb = _ein("hgqk,hkd->hgqd", ds, kb, low) * scale
        dkb = _ein("hgqk,hgqd->hkd", ds, qb, low) * scale
        dvb = _ein("hgqk,hgqd->hkd", p, dob, low)
        add = lambda acc, x: jax.lax.dynamic_update_slice_in_dim(
            acc, jax.lax.dynamic_slice_in_dim(acc, k0, span, axis=1) + x,
            k0, axis=1)
        out = jax.lax.dynamic_update_slice_in_dim(out, ob, r, axis=2)
        dq = jax.lax.dynamic_update_slice_in_dim(dq, dqb, r, axis=2)
        return out, dq, add(dk, dkb), add(dv, dvb)

    zeros = jnp.zeros((hkv, g, seq, d), f32)
    kvz = jnp.zeros((hkv, pad + seq, d), f32)
    out, dq, dk, dv = jax.lax.fori_loop(0, seq // block, body,
                                        (zeros, zeros, kvz, kvz))
    return (out.reshape(hq, seq, d), dq.reshape(hq, seq, d), dk[:, pad:],
            dv[:, pad:])


def _block(hq, seq, keys):
    """Query rows per block: a power of two dividing ``seq`` whose score
    tensors ([Hq, rows, keys] float32) stay near 256 MiB."""
    b = 8
    while b * 2 <= seq and seq % (b * 2) == 0 and \
            hq * b * 2 * keys * 4 <= 1 << 28:
        b *= 2
    return b


def layer_reference(q, k, v, do, window, low=False):
    """(out, dq, dk, dv) of one layer, float32, on the inputs' device."""
    hq, seq, _ = q.shape
    keys = seq if window is None else window + _block(hq, seq, window)
    block = _block(hq, seq, keys)
    return layer_fwd_bwd(q, k, v, do, window=window, low=low, block=block)


@jax.jit
def _errs(a, b):
    """→ (sum of squared differences, sum of squares of b, max |a - b|,
    max |b|)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return (jnp.sum((a - b) ** 2), jnp.sum(b * b), jnp.max(jnp.abs(a - b)),
            jnp.max(jnp.abs(b)))


def check_layer(inputs, got, window):
    """Errors of one layer's (out, dq, dk, dv) against the float32
    reference: per tensor the relative L2 error, and the largest absolute
    error over the reference's largest magnitude (which sees one token
    altered where the L2 error of 10**8 elements would not).  ``got=None``:
    the float8 control stands in."""
    q, k, v, do = inputs
    want = layer_reference(q, k, v, do, window)
    if got is None:
        got = layer_reference(q, k, v, do, window, low=True)
    errs = {}
    for name, a, b in zip(NAMES, got, want):
        e, r, gap, top = (float(x) for x in _errs(a, b))
        errs[f"{name}_rel_err"] = float(np.sqrt(e / r))
        errs[f"{name}_max_err"] = gap / top
    return errs
