"""Gated short convolution — the operator of LFM2's convolution layers —
as a Pallas TPU kernel pair with its XLA composition.

    [B | C | x] = X  (three equal parts of the last axis, 3d wide)
    z = B * x
    c[t] = sum_j w[:, j] * z[t - (L-1) + j],  z[t] = 0 for t < 0
    out = C * c

a depthwise causal convolution of L taps (one filter a channel) between
two elementwise gates, within one sequence: rows of a batch do not see
each other.  No matrix product: a pass reads its operands and writes its
results once, so its roofline is HBM bytes.

The kernels walk (batch row, time block): a block holds `rows` whole rows
of X (all 3d channels: contiguous in HBM) and works through them in
channel chunks.  The convolution's reach over a block's edge is read as a
halo of 16 rows (the tile height of bfloat16) from the SAME arrays: the
rows before the block for z (forward and backward), the rows after it for
dc = dOut * C (the backward's dz looks ahead).  So every grid step stands
alone and nothing is carried.  float32 inside, the operands' dtype at the
boundary; the backward recomputes z and c from X (they are cheaper to make
than to store) and writes dX whole and one partial of dW a grid step,
summed outside in float32.

  short_conv(x [b,t,3d], w [d,L])           -> out [b,t,d]
  short_conv_bwd(x, w, g [b,t,d])           -> (dx [b,t,3d], dw [d,L] f32)
"""

from __future__ import annotations

import functools

HALO = 16
_ROWS = (128, 64, 32, 16)
_CHUNKS = (512, 256, 128)
_MAX_TAPS = 8


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def _parts(x):
    d = x.shape[-1] // 3
    return _f32(x[..., :d]), _f32(x[..., d:2 * d]), _f32(x[..., 2 * d:])


def _shift(z, s):
    """z[:, t - s] with zeros before a row's start (s < 0 looks ahead,
    with zeros past its end)."""
    import jax.numpy as jnp

    t = z.shape[1]
    if s == 0:
        return z
    if s > 0:
        return jnp.pad(z, ((0, 0), (s, 0), (0, 0)))[:, :t]
    return jnp.pad(z, ((0, 0), (0, -s), (0, 0)))[:, -s:]


def reference_short_conv(x, w):
    """The XLA composition: the lowering off the TPU and where the plan
    rejects."""
    gate_b, gate_c, xx = _parts(x)
    w = _f32(w)
    taps = w.shape[1]
    z = gate_b * xx
    c = sum(w[:, j] * _shift(z, taps - 1 - j) for j in range(taps))
    return (gate_c * c).astype(x.dtype)


def reference_short_conv_bwd(x, w, g):
    """(dX, dW float32) of the composition, from X alone."""
    import jax.numpy as jnp

    gate_b, gate_c, xx = _parts(x)
    w, g = _f32(w), _f32(g).reshape(gate_c.shape)
    taps = w.shape[1]
    z = gate_b * xx
    shifted = [_shift(z, taps - 1 - j) for j in range(taps)]
    c = sum(w[:, j] * shifted[j] for j in range(taps))
    dc = g * gate_c
    dz = sum(w[:, j] * _shift(dc, -(taps - 1 - j)) for j in range(taps))
    dw = jnp.stack([jnp.sum(dc * zs, axis=(0, 1)) for zs in shifted], axis=1)
    dx = jnp.concatenate([dz * xx, g * c, dz * gate_b], axis=-1)
    return dx.astype(x.dtype), dw


def _plan(x, w, interpret):
    """Static feasibility: (ok, rows, chunk, interpret).  Compiled, a time
    block is whole 16-row tiles that divide t and a channel chunk whole
    128-lane tiles that divide d; the taps reach no further than a halo."""
    from .placement import resolve

    compiled, interpret = resolve(interpret)
    t, d, taps = x.shape[1], w.shape[0], w.shape[1]
    rows = next((r for r in _ROWS if t % r == 0), 0)
    chunk = next((c for c in _CHUNKS if d % c == 0),
                 0 if compiled else d)
    ok = bool(x.ndim == 3 and x.shape[2] == 3 * d and rows and chunk
              and 1 <= taps <= _MAX_TAPS and (compiled or interpret))
    return ok, rows, chunk, interpret


def _part(ref, part, lo, chunk, d=0):
    """Columns lo .. lo + chunk of column part `part` (0, 1, 2 = B, C, x
    of a [rows, 3d] block; 0 of a [rows, d] one), float32."""
    return _f32(ref[:, part * d + lo:part * d + lo + chunk])


def _looking_back(x_ref, bprev_ref, xprev_ref, lo, chunk, d, taps):
    """(B, x, [z[t], z[t-1], .., z[t-taps+1]]) of one channel chunk over a
    block's rows, z = B * x: the rows before the block come from the halo
    (zeros before a sequence's start), one roll of [halo ; z] a tap, no
    select."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    gate_b, xx = _part(x_ref, 0, lo, chunk, d), _part(x_ref, 2, lo, chunk, d)
    z = gate_b * xx
    prev = jnp.where(pl.program_id(1) == 0, 0.0, _part(
        bprev_ref, 0, lo, chunk) * _part(xprev_ref, 0, lo, chunk))
    cat = jnp.concatenate([prev, z], axis=0)
    return gate_b, xx, [z] + [pltpu.roll(cat, s, 0)[HALO:]
                              for s in range(1, taps)]


def _conv(w_ref, lo, chunk, shifted):
    """sum_s w[taps-1-s] * shifted[s] for one channel chunk."""
    taps = len(shifted)
    return sum(w_ref[taps - 1 - s:taps - s, lo:lo + chunk] * shifted[s]
               for s in range(taps))


def _fwd_kernel(x_ref, bprev_ref, xprev_ref, w_ref, o_ref, *, d, chunk,
                taps):
    for lo in range(0, d, chunk):
        _, _, shifted = _looking_back(x_ref, bprev_ref, xprev_ref, lo,
                                      chunk, d, taps)
        o_ref[:, lo:lo + chunk] = (
            _part(x_ref, 1, lo, chunk, d)
            * _conv(w_ref, lo, chunk, shifted)).astype(o_ref.dtype)


def _bwd_kernel(x_ref, g_ref, bprev_ref, xprev_ref, cnext_ref, gnext_ref,
                w_ref, dx_ref, dw_ref, *, d, chunk, taps):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    last = pl.program_id(1) == pl.num_programs(1) - 1
    rows = x_ref.shape[0]
    tap_row = jax.lax.broadcasted_iota(jnp.int32, (_MAX_TAPS, chunk), 0)

    def put(part, lo, value):
        dx_ref[:, part * d + lo:part * d + lo + chunk] = value.astype(
            dx_ref.dtype)

    for lo in range(0, d, chunk):
        gate_b, xx, shifted = _looking_back(x_ref, bprev_ref, xprev_ref, lo,
                                            chunk, d, taps)
        g = _part(g_ref, 0, lo, chunk)
        put(1, lo, g * _conv(w_ref, lo, chunk, shifted))
        dc = g * _part(x_ref, 1, lo, chunk, d)
        ahead = jnp.where(last, 0.0, _part(gnext_ref, 0, lo, chunk)
                          * _part(cnext_ref, 0, lo, chunk))
        cat = jnp.concatenate([dc, ahead], axis=0)
        # dz[t] = sum_s w[taps-1-s] * dc[t + s]
        dz = _conv(w_ref, lo, chunk, [dc] + [
            pltpu.roll(cat, rows + HALO - s, 0)[:rows]
            for s in range(1, taps)])
        put(0, lo, dz * xx)
        put(2, lo, dz * gate_b)
        dw = jnp.zeros((_MAX_TAPS, chunk), jnp.float32)
        for s in range(taps):
            part = jnp.sum(dc * shifted[s], axis=0, keepdims=True)
            dw = jnp.where(tap_row == taps - 1 - s, part, dw)
        dw_ref[:, lo:lo + chunk] = dw


def _halo_specs(d, rows, t):
    """BlockSpecs of the HALO rows before and after block j of a [b, t,
    n * d] array's column part `part` (clamped at the ends; the kernels
    zero what they read there)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    per, n_halo = rows // HALO, t // HALO

    def before(part):
        return pl.BlockSpec(
            (None, HALO, d),
            lambda i, j: (i, jnp.maximum(j * per - 1, 0), part))

    def after(part):
        return pl.BlockSpec(
            (None, HALO, d),
            lambda i, j: (i, jnp.minimum((j + 1) * per, n_halo - 1), part))

    return before, after


def _taps_first(w):
    """w [d, L] -> [_MAX_TAPS, d] float32, channels in the lanes."""
    import jax.numpy as jnp

    return jnp.pad(_f32(w).T, ((0, _MAX_TAPS - w.shape[1]), (0, 0)))


def _note(route):
    from ..monitor import flight

    flight.note_compile_count(route)


def short_conv(x, w, interpret=None):
    """out [b, t, d] of x [b, t, 3d] = [B | C | x] and w [d, L]."""
    import jax
    from jax.experimental import pallas as pl

    ok, rows, chunk, interpret = _plan(x, w, interpret)
    if not ok:
        _note("short_conv_sites_xla")
        return reference_short_conv(x, w)
    _note("short_conv_sites_kernel")
    b, t, _ = x.shape
    d, taps = w.shape
    before, _ = _halo_specs(d, rows, t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, chunk=chunk, taps=taps),
        name="short_conv_fwd",
        grid=(b, t // rows),
        in_specs=[pl.BlockSpec((None, rows, 3 * d), lambda i, j: (i, j, 0)),
                  before(0), before(2),
                  pl.BlockSpec((_MAX_TAPS, d), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((None, rows, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t, d), x.dtype),
        interpret=interpret,
    )(x, x, x, _taps_first(w))


def short_conv_bwd(x, w, g, interpret=None):
    """(dx [b, t, 3d] in x's dtype, dw [d, L] float32) from x, w and the
    cotangent g [b, t, d] of short_conv's result."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    ok, rows, chunk, interpret = _plan(x, w, interpret)
    if not ok:
        return reference_short_conv_bwd(x, w, g)
    b, t, _ = x.shape
    d, taps = w.shape
    g = g.astype(x.dtype).reshape(b, t, d)
    before, after = _halo_specs(d, rows, t)
    whole = pl.BlockSpec((None, rows, 3 * d), lambda i, j: (i, j, 0))
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, chunk=chunk, taps=taps),
        name="short_conv_bwd",
        grid=(b, t // rows),
        in_specs=[whole,
                  pl.BlockSpec((None, rows, d), lambda i, j: (i, j, 0)),
                  before(0), before(2), after(1), after(0),
                  pl.BlockSpec((_MAX_TAPS, d), lambda i, j: (0, 0))],
        out_specs=[whole,
                   pl.BlockSpec((None, None, _MAX_TAPS, d),
                                lambda i, j: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, t // rows, _MAX_TAPS, d),
                                        jnp.float32)],
        interpret=interpret,
    )(x, g, x, x, x, g, _taps_first(w))
    return dx, jnp.sum(dw, axis=(0, 1))[:taps].T
