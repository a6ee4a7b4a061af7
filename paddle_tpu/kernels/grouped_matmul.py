"""Grouped matmul over the experts a chip holds — Pallas TPU kernels,
forward, dX and dW, over rows sorted by expert.

Adapted from the public megablox `gmm` / `tgmm` of JAX's Pallas TPU ops
(jax/experimental/pallas/ops/tpu/megablox/gmm.py, Apache 2.0): the same
group metadata (a row tile that straddles two experts is visited once
for each, under a row mask) and the same two kernel bodies, cut down to
what one chip's share of an expert layer needs (no sharded group
offset, no accumulate-into-existing output, tiles that divide k and n)
and given names, a plan gate and XLA fallbacks like every other kernel
family here.

It is dropless: `group_sizes[i]` rows belong to expert i, whatever the
router sent; no capacity, no padding of an expert to a fixed size.  The
rows are a static [m, k] buffer whose first sum(group_sizes) rows are
live; the grid visits only the row tiles that hold a live row (a traced
grid bound), so the dead tail costs no kernel time.  The forward and dX
kernels WRITE only live rows: what a row past the last expert's holds is
undefined, and the caller discards it where it consumes the result (by a
select, never by a product); dW masks its operands' rows itself.

Callers hand these a chunk of the sorted pairs, not the T x top_k buffer,
with the experts' sizes clipped to the chunk.

  grouped_matmul(lhs [m,k], rhs [g,k,n], sizes)            -> [m,n]
  grouped_matmul(lhs [m,n], rhs [g,k,n], sizes, transpose_rhs=True)
                               (the forward's dX)           -> [m,k]
  grouped_matmul_dw(lhs [m,k], dout [m,n], sizes)           -> [g,k,n]
"""

from __future__ import annotations

import functools

ROW_TILE = 256
_TILES = (512, 384, 256, 128)


def _tile(dim, compiled):
    """The largest tile of `_TILES` that divides `dim`; the whole of a
    dimension no tile divides where the interpreter runs (it has no lane
    rule), else 0."""
    for t in _TILES:
        if dim % t == 0:
            return t
    return 0 if compiled else dim


def _plan(m, k, n, interpret):
    """Static feasibility: (ok, (tm, tk, tn), interpret).  Compiled, every
    tile is a multiple of 128 that divides its dimension; `k` and `n` are
    a weight's dimensions and `m` the row buffer."""
    from .placement import resolve

    compiled, interpret = resolve(interpret)
    tm = ROW_TILE if m % ROW_TILE == 0 else _tile(m, compiled)
    tk, tn = _tile(k, compiled), _tile(n, compiled)
    ok = bool(tm and tk and tn and (compiled or interpret))
    return ok, (tm, tk, tn), interpret


def make_group_metadata(group_sizes, m, tm, visit_empty_groups):
    """megablox's `make_group_metadata` without the sharded offset:
    ((group_offsets [g+1], group_ids, m_tile_ids), num_tiles).  Grid index
    i works on rows tile `m_tile_ids[i]` for expert `group_ids[i]`; only
    the first `num_tiles` indices are live.  With `visit_empty_groups`
    (dW) an expert with no row still gets one tile, so that its output is
    written (with zeros)."""
    import jax.numpy as jnp

    num_groups = group_sizes.shape[0]
    group_ends = jnp.cumsum(group_sizes)
    group_offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), group_ends]).astype(jnp.int32)
    rounded_ends = ((group_ends + tm - 1) // tm * tm).astype(jnp.int32)
    group_starts = group_offsets[:-1]
    rounded_starts = group_starts // tm * tm
    group_tiles = jnp.where(group_sizes == 0, 0,
                            rounded_ends - rounded_starts) // tm
    if visit_empty_groups:
        group_tiles = jnp.where(group_sizes == 0, 1, group_tiles)
    tiles_m = m // tm
    length = tiles_m + num_groups - 1
    group_ids = jnp.repeat(jnp.arange(num_groups, dtype=jnp.int32),
                           group_tiles, total_repeat_length=length)
    # a tile is visited once by the expert that owns its first row, and
    # once more by each expert that starts inside it
    starts_inside = jnp.logical_and(group_starts % tm != 0, group_sizes != 0)
    if visit_empty_groups:
        starts_inside = jnp.logical_or(starts_inside, group_sizes == 0)
    # a group that starts at or past the buffer's end has no tile there
    partial_ids = jnp.where(starts_inside,
                            jnp.minimum(group_starts // tm, tiles_m - 1),
                            tiles_m)
    visits = jnp.zeros(tiles_m + 1, jnp.int32).at[partial_ids].add(1)
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32),
                            visits[:tiles_m] + 1,
                            total_repeat_length=length)
    return (group_offsets, group_ids, m_tile_ids), group_tiles.sum()


def _row_mask(group_metadata, grid_id, tm, width):
    """[tm, width] mask of the tile's rows that belong to this grid
    index's expert."""
    import jax
    import jax.numpy as jnp

    group_offsets, group_ids, m_tile_ids = group_metadata
    group = group_ids[grid_id]
    rows = m_tile_ids[grid_id] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, width), 0)
    return jnp.logical_and(rows >= group_offsets[group],
                           rows < group_offsets[group + 1])


def _gmm_kernel(offsets_ref, group_ids_ref, m_tile_ids_ref, lhs_ref, rhs_ref,
                out_ref, acc_ref, *, tm, tn, tiles_k, transpose_rhs):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    grid_id, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], dims,
        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        mask = _row_mask((offsets_ref, group_ids_ref, m_tile_ids_ref),
                         grid_id, tm, tn)
        out_ref[...] = jax.lax.select(
            mask, acc_ref[...], out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


def _tgmm_kernel(offsets_ref, group_ids_ref, m_tile_ids_ref, lhs_ref,
                 rhs_ref, out_ref, acc_ref, *, tm, tk, tn):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    grid_id = pl.program_id(2)
    last = pl.num_programs(2) - 1
    group = group_ids_ref[grid_id]
    prev = group_ids_ref[jnp.maximum(grid_id - 1, 0)]
    nxt = group_ids_ref[jnp.minimum(grid_id + 1, last)]

    @pl.when(jnp.logical_or(grid_id == 0, prev != group))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets_ref[group + 1] > offsets_ref[group])
    def _accumulate():
        meta = (offsets_ref, group_ids_ref, m_tile_ids_ref)
        # masked and transposed in float32, as megablox does: the v5e
        # has no bf16 select, and Mosaic transposes 32-bit tiles
        lhs = jnp.where(_row_mask(meta, grid_id, tm, tk),
                        lhs_ref[...].astype(jnp.float32), 0.0)
        rhs = jnp.where(_row_mask(meta, grid_id, tm, tn),
                        rhs_ref[...].astype(jnp.float32), 0.0)
        acc_ref[...] += jax.lax.dot(
            lhs.T.astype(lhs_ref.dtype), rhs.astype(rhs_ref.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_or(grid_id == last, nxt != group))
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _group_of_row(group_sizes, m):
    """[m] expert of each row of the sorted buffer; `g` past the last."""
    import jax.numpy as jnp

    return jnp.searchsorted(jnp.cumsum(group_sizes),
                            jnp.arange(m, dtype=jnp.int32), side="right")


def reference_grouped_matmul(lhs, rhs, group_sizes, transpose_rhs=False):
    """The XLA fallback of `grouped_matmul` (`jax.lax.ragged_dot`)."""
    import jax
    import jax.numpy as jnp

    if transpose_rhs:
        rhs = jnp.swapaxes(rhs, 1, 2)
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)


def reference_grouped_matmul_dw(lhs, dout, group_sizes):
    """The XLA fallback of `grouped_matmul_dw`: one masked product an
    expert."""
    import jax
    import jax.numpy as jnp

    gid = _group_of_row(group_sizes, lhs.shape[0])

    def one(g):
        rows = jnp.where((gid == g)[:, None], lhs, jnp.zeros((), lhs.dtype))
        return jax.lax.dot_general(rows, dout, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    out = jax.lax.map(one, jnp.arange(group_sizes.shape[0], dtype=jnp.int32))
    return out.astype(lhs.dtype)


def grouped_matmul(lhs, rhs, group_sizes, transpose_rhs=False,
                   interpret=None):
    """lhs[rows of expert i] @ rhs[i] for every expert, rows sorted by
    expert; the rows past the last expert's are not written.  With
    `transpose_rhs` the product is with rhs[i].T: the same walk computes
    the forward's dX, under that kernel's name."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    ok, (tm, tk, tn), interpret = _plan(m, k, n, interpret)
    if not ok:
        return reference_grouped_matmul(lhs, rhs, group_sizes, transpose_rhs)
    group_sizes = group_sizes.astype(jnp.int32)
    metadata, num_tiles = make_group_metadata(group_sizes, m, tm, False)
    tiles_k, tiles_n = k // tk, n // tn

    def lhs_index(n_i, grid_id, k_i, offsets, group_ids, m_tile_ids):
        return m_tile_ids[grid_id], k_i

    def rhs_index(n_i, grid_id, k_i, offsets, group_ids, m_tile_ids):
        if transpose_rhs:
            return group_ids[grid_id], n_i, k_i
        return group_ids[grid_id], k_i, n_i

    def out_index(n_i, grid_id, k_i, offsets, group_ids, m_tile_ids):
        return m_tile_ids[grid_id], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn, tiles_k=tiles_k,
                          transpose_rhs=transpose_rhs),
        name="moe_gmm_bwd_dx" if transpose_rhs else "moe_gmm_fwd",
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec(rhs_block, rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(tiles_n, num_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*metadata, lhs, rhs)


def grouped_matmul_dw(lhs, dout, group_sizes, interpret=None):
    """lhs[rows of expert i].T @ dout[rows of expert i] for every expert:
    [g, k, n], zeros for an expert with no row."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = dout.shape[1]
    ok, (tm, tk, tn), interpret = _plan(m, k, n, interpret)
    if not ok:
        return reference_grouped_matmul_dw(lhs, dout, group_sizes)
    group_sizes = group_sizes.astype(jnp.int32)
    metadata, num_tiles = make_group_metadata(group_sizes, m, tm, True)

    def lhs_index(n_i, k_i, grid_id, offsets, group_ids, m_tile_ids):
        return m_tile_ids[grid_id], k_i

    def rhs_index(n_i, k_i, grid_id, offsets, group_ids, m_tile_ids):
        return m_tile_ids[grid_id], n_i

    def out_index(n_i, k_i, grid_id, offsets, group_ids, m_tile_ids):
        return group_ids[grid_id], k_i, n_i

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, tk=tk, tn=tn),
        name="moe_gmm_bwd_dw",
        out_shape=jax.ShapeDtypeStruct((group_sizes.shape[0], k, n),
                                       lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((tm, tn), rhs_index)],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            grid=(n // tn, k // tk, num_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*metadata, lhs, dout)
