"""Fused dropout + residual-add epilogue — Pallas TPU kernel, custom VJP.

The r04 A/B ceiling measurement (PERF.md) put the whole dropout
apparatus at +14% transformer throughput; the graph-level hash recompute
(r05) captured most of it but still leaves ~0.3 GB/step of mask-multiply
traffic at the output sites and an XLA fusion boundary per site.  This
kernel closes the residual-connection sites — the `dropout(x) + skip`
pairs in every transformer/BERT block — the way FlashAttention closed
softmax (Dao et al. 2022): recompute instead of store.

    out = where(keep, x * 1/(1-rate), 0) + residual        (one kernel)

  * The keep-mask is drawn INSIDE the kernel from the TPU hardware PRNG
    (pltpu.prng_seed / prng_random_bits), re-seeded per grid tile from
    (stream seed, tile index) — the counter-based-RNG idiom of Salmon et
    al. "Parallel Random Numbers: As Easy as 1, 2, 3".  No mask or
    random-bits tensor ever exists in HBM.
  * The custom VJP regenerates the identical mask in the backward from
    the same scalar seeds: dx = where(keep, g/(1-rate), 0), dres = g.
    The only fwd->bwd residual is the (1,) uint32 seed.
  * Off-TPU (interpret mode) and for shapes Pallas can't tile, the mask
    falls back to the lowbias32 hash of kernels/hash_rng.py over the
    global element index — the in-kernel interpret path and the pure-XLA
    path produce bit-identical masks, and every path regenerates its own
    mask exactly in the backward.

rate == 0 short-circuits to `x + residual` before any seed/kernel
machinery exists, so dropout-off programs compile to the identical HLO
as a plain elementwise add (zero-cost-off; asserted in tests).
"""

from __future__ import annotations

import functools


def _keep_bits(seed_ref, shape, tile_idx, rate, block_r, ncols, hw_prng):
    """Keep-mask for grid tile `tile_idx` — the ONE mask generator both the
    forward and backward kernels call, so fwd/bwd bit-parity is structural
    rather than a property of two code paths staying in sync."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import hash_rng

    if hw_prng:
        from jax.experimental.pallas import tpu as pltpu

        # per-tile re-seed: a backward kernel walking the same grid
        # regenerates bit-identical tiles (order-independent)
        pltpu.prng_seed(seed_ref[0], tile_idx)
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
        return bits >= np.uint32(hash_rng.keep_threshold(rate))
    base = (tile_idx * np.uint32(block_r)) * np.uint32(ncols)
    idx = base + jax.lax.broadcasted_iota(
        jnp.uint32, shape, 0
    ) * np.uint32(ncols) + jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    return hash_rng.keep_mask_tile(seed_ref[0], idx, rate)


def _kernel(seed_ref, x_ref, r_ref, o_ref, *, rate, inv_keep, block_r,
            ncols, hw_prng):
    """One (block_r, ncols) tile: out = keep ? x*inv_keep : 0, + residual."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    x = x_ref[...]
    keep = _keep_bits(seed_ref, x.shape, pl.program_id(0), rate, block_r,
                      ncols, hw_prng)
    out = jnp.where(keep, x * jnp.asarray(inv_keep, x.dtype),
                    jnp.zeros((), x.dtype))
    o_ref[...] = out + r_ref[...].astype(x.dtype)


def _bwd_kernel(seed_ref, g_ref, dx_ref, *, rate, inv_keep, block_r, ncols,
                hw_prng):
    """dx tile: regenerate the forward's keep bits, apply to the cotangent."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    g = g_ref[...]
    keep = _keep_bits(seed_ref, g.shape, pl.program_id(0), rate, block_r,
                      ncols, hw_prng)
    dx_ref[...] = jnp.where(keep, g * jnp.asarray(inv_keep, g.dtype),
                            jnp.zeros((), g.dtype))


def _plan(shape, dtype, interpret):
    """(ok, rows, ncols, block_r, interpret, hw_prng) for a 2-D row tiling.

    The array is viewed as [rows, ncols] with ncols = trailing dim.  TPU
    tiling wants the lane dim % 128 and the sublane block % 8 (16 for
    sub-4-byte dtypes); anything else goes to the pure-XLA fallback —
    same mask, just without the fused single kernel."""
    import numpy as np

    from ..flags import FLAGS
    from .placement import resolve

    ncols = int(shape[-1])
    rows = 1
    for s in shape[:-1]:
        rows *= int(s)
    compiled, interpret = resolve(interpret)
    sub = 16 if np.dtype(dtype).itemsize < 4 else 8
    block_r = 0
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if cand % sub == 0 and rows % cand == 0:
            block_r = cand
            break
    ok = (
        (compiled or interpret)
        and ncols % 128 == 0
        and block_r > 0
        and rows * ncols < 2 ** 32  # uint32 hash index must not wrap
    )
    hw_prng = bool(compiled and FLAGS.tpu_prng_dropout)
    return ok, rows, ncols, block_r, interpret, hw_prng


def _pallas_fwd(x2, r2, seed, rate, inv_keep, block_r, ncols, interpret,
                hw_prng):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = x2.shape[0]
    spec = pl.BlockSpec((block_r, ncols), lambda i: (i, 0))
    kern = functools.partial(_kernel, rate=rate, inv_keep=inv_keep,
                             block_r=block_r, ncols=ncols, hw_prng=hw_prng)
    return pl.pallas_call(
        kern,
        name="dropout_add_fwd",
        grid=(rows // block_r,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, ncols), x2.dtype),
        interpret=interpret,
    )(seed, x2, r2)


def _pallas_bwd(g2, seed, rate, inv_keep, block_r, ncols, interpret,
                hw_prng):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = g2.shape[0]
    spec = pl.BlockSpec((block_r, ncols), lambda i: (i, 0))
    kern = functools.partial(_bwd_kernel, rate=rate, inv_keep=inv_keep,
                             block_r=block_r, ncols=ncols, hw_prng=hw_prng)
    return pl.pallas_call(
        kern,
        name="dropout_add_bwd",
        grid=(rows // block_r,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, ncols), g2.dtype),
        interpret=interpret,
    )(seed, g2)


def _xla_keep(seed, shape, rate):
    """Pure-XLA keep-mask over the flat element index — bit-identical to
    the non-hw-prng kernel path (same (seed, flat index) hash)."""
    from . import hash_rng

    return hash_rng.keep_mask(seed, shape, rate)


def dropout_add(x, residual, rate, seed, scale=None, interpret=None):
    """Fused `dropout(x) + residual` with mask-regenerating backward.

    x, residual: same-shape arrays (residual is cast to x.dtype, matching
    `dropout(x) + residual` under the elementwise-add promotion rules the
    models use).  rate: static float in [0, 1).  seed: (1,) uint32 stream
    seed (hash_rng.seed_from_key) — one per (step, site).  scale: the
    survivor multiplier; defaults to 1/(1-rate) (upscale_in_train).

    rate == 0 returns x + residual directly (identical HLO to the unfused
    dropout-off program; no seed dependency is introduced)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    if not rate:
        return x + residual.astype(x.dtype)
    if not 0.0 < float(rate) < 1.0:
        raise ValueError(f"dropout_add: rate {rate!r} outside [0, 1)")
    if tuple(x.shape) != tuple(residual.shape):
        raise ValueError(
            f"dropout_add: x {tuple(x.shape)} vs residual "
            f"{tuple(residual.shape)} must match")
    rate = float(rate)
    n_elems = 1
    for s in x.shape:
        n_elems *= int(s)
    if n_elems >= 2 ** 32:
        # the hash fallback's flat uint32 index would wrap and repeat the
        # mask pattern — refuse rather than silently correlate bits (same
        # contract as flash_attention's Tq*Tk guard)
        raise ValueError(
            f"dropout_add: {n_elems} elements >= 2^32 wraps the uint32 "
            "mask index and correlates dropout bits; split the tensor "
            "into < 2^32-element dropout sites")
    inv_keep = float(scale) if scale is not None else 1.0 / (1.0 - rate)
    seed = jnp.reshape(seed, (1,)).astype(jnp.uint32)
    ok, rows, ncols, block_r, interp, hw_prng = _plan(
        x.shape, x.dtype, interpret)
    rdt = residual.dtype  # static: closed over by the VJPs (a dtype is
    # not a jax type, so it cannot ride in the residuals tuple)

    def _f0(s):
        return np.zeros(s.shape, dtype=jax.dtypes.float0)

    if not ok:
        # pure-XLA fallback: same hash mask, custom VJP still regenerates
        # it in the backward (no bool-mask residual crosses fwd->bwd)
        @jax.custom_vjp
        def _da(x, residual, seed):
            keep = _xla_keep(seed[0], x.shape, rate)
            return jnp.where(keep, x * jnp.asarray(inv_keep, x.dtype),
                             jnp.zeros((), x.dtype)) + residual.astype(x.dtype)

        def _fwd(x, residual, seed):
            return _da(x, residual, seed), seed

        def _bwd(seed, g):
            keep = _xla_keep(seed[0], g.shape, rate)
            dx = jnp.where(keep, g * jnp.asarray(inv_keep, g.dtype),
                           jnp.zeros((), g.dtype))
            return dx, g.astype(rdt), _f0(seed)

        _da.defvjp(_fwd, _bwd)
        return _da(x, residual, seed)

    shape = x.shape

    @jax.custom_vjp
    def _da(x, residual, seed):
        out = _pallas_fwd(x.reshape(rows, ncols),
                          residual.reshape(rows, ncols), seed, rate,
                          inv_keep, block_r, ncols, interp, hw_prng)
        return out.reshape(shape)

    def _fwd(x, residual, seed):
        return _da(x, residual, seed), seed

    def _bwd(seed, g):
        dx = _pallas_bwd(g.reshape(rows, ncols), seed, rate, inv_keep,
                         block_r, ncols, interp, hw_prng)
        return dx.reshape(shape), g.astype(rdt), _f0(seed)

    _da.defvjp(_fwd, _bwd)
    return _da(x, residual, seed)
