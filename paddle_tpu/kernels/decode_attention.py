"""Flash-decode: Pallas single-query attention over a growing KV cache.

The decode half of autoregressive generation (paddle_tpu/generation): at
every generated token each sequence attends ONE query row against its
cache prefix.  The training flash kernels (kernels/attention.py) are the
wrong shape for this — their grid tiles the query axis, which here has
length 1, and they stream the FULL key buffer even though a sequence of
length L only owns L valid cache rows out of max_t.

Design:
  * grid (batch, max_t / block_t): one grid step per [block_t, h, dh]
    cache block of one sequence; q is a [h, dh] tile and the online-
    softmax state ([h, 1] running max/sum, [h, dh] f32 accumulator)
    rides VMEM scratch across the block axis.
  * the cache blocks arrive through the BlockSpec pipeline, not manual
    DMA: Mosaic refuses to slice an HBM ref whose minor dim is not a
    multiple of 128 ("Slice shape along dimension 3 must be aligned to
    tiling (128), but is 64", libtpu 0.0.34), and transformer-base has
    d_head 64; a pipelined block whose last two dims span the array's
    is accepted at any d_head % 64.
  * per-sequence lengths ride scalar prefetch
    (pltpu.PrefetchScalarGridSpec) into the index maps: steps past
    ceil(len/block_t) re-name the last valid block, so a sequence of
    length L reads ceil(L/block_t) blocks, NOT max_t/block_t, and the
    mid-block tail is masked by position.  This is what makes the
    compiled program length-INDEPENDENT: lengths are runtime data,
    never shapes.
  * the paged variant is the same kernel behind a table hop in the
    index map (the block table rides scalar prefetch too).
  * forward-only by contract: generation never differentiates through
    the cache (the op is registered no_grad); there is no backward
    kernel and no residual.

Falls back to a pure-XLA implementation off-TPU or off-contract
(_decode_plan), numerically identical.
"""

from __future__ import annotations


def reference_decode(q, k, v, lengths, scale=1.0):
    """Pure-XLA fallback (and numerics oracle for the kernel tests).

    q [b, h, dh]; k/v [b, max_t, h, dh]; lengths [b] int — number of
    valid cache rows per sequence (positions >= length are masked out of
    the softmax).  Returns [b, h, dh] in q.dtype; softmax statistics and
    the value accumulation are f32 like the Pallas kernel.
    """
    import jax
    import jax.numpy as jnp

    max_t = k.shape[1]
    logits = jnp.einsum(
        "bhd,bthd->bht", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    valid = (
        jnp.arange(max_t, dtype=jnp.int32)[None, :]
        < lengths.astype(jnp.int32)[:, None]
    )  # [b, t]
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)  # [b, h, t]
    out = jnp.einsum("bht,bthd->bhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _decode_kernel(*refs, scale, block_t, n_prefetch):
    """One grid step = one [block_t, h, dh] cache block of one sequence;
    the online-softmax state rides VMEM scratch across the block axis.

    Shared by the ring and the paged entry points: they differ only in
    the index map that places the block (a contiguous row window vs a
    scalar-prefetched table hop), so `refs` leads with `n_prefetch`
    scalar refs of which only the first — the lengths — is read here.

    All math keeps the cache tile's own [t, h, dh] layout (h on
    sublanes, dh on lanes): scores are a lane reduction, the softmax
    and the value accumulation reduce over the leading block axis — no
    transpose, no 1-D vector and no M=1 matmul for Mosaic to relayout.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    lens_ref = refs[0]
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs[n_prefetch:]
    i = pl.program_id(0)
    t = pl.program_id(1)
    length = lens_ref[i]

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, f32)
        l_scr[...] = jnp.zeros(l_scr.shape, f32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, f32)

    # blocks wholly past the sequence's length are skipped (their index
    # map repeats the last valid block, so no DMA was issued either)
    @pl.when(t * block_t < length)
    def _block():
        q = q_ref[...].astype(f32) * scale                  # [h, dh]
        k = k_ref[...].astype(f32)                          # [bt, h, dh]
        v = v_ref[...].astype(f32)
        s = jnp.sum(k * q[None], axis=2, keepdims=True)     # [bt, h, 1]
        k_pos = t * block_t + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        s = jnp.where(k_pos < length, s, -1e30)
        m_prev = m_scr[...]                                 # [h, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        p = jnp.exp(s - m_new[None])                        # [bt, h, 1]
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=0)
        acc_scr[...] = acc_scr[...] * alpha + jnp.sum(p * v, axis=0)
        m_scr[...] = m_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        # length == 0 cannot happen in the generation drivers (prefill
        # always writes >= 1 row) but keep the division safe anyway
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def _last_block(length, block_t):
    """Index of the last cache block holding a valid row (0 when the
    sequence is empty): index maps clamp to it, so grid steps past the
    length re-name the resident block and the pipeline issues no DMA —
    a sequence of length L reads ceil(L / block_t) blocks, not
    max_t / block_t."""
    import jax.numpy as jnp

    return jnp.maximum(length - 1, 0) // block_t


def _decode_call(q, k, v, prefetch, n_blocks, q_map, kv_map, block_t,
                 scale, interpret):
    """The pallas_call both entry points share: `prefetch` are the
    scalar-prefetch operands (lengths first), the maps take
    (sequence, block, *prefetch refs)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, dh = q.shape
    kv_block = (None, block_t, h, dh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, n_blocks),
        in_specs=[
            pl.BlockSpec((None, h, dh), q_map),
            pl.BlockSpec(kv_block, kv_map),
            pl.BlockSpec(kv_block, kv_map),
        ],
        out_specs=pl.BlockSpec((None, h, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),    # running max
            pltpu.VMEM((h, 1), jnp.float32),    # running sum
            pltpu.VMEM((h, dh), jnp.float32),   # value accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_t=block_t,
                          n_prefetch=len(prefetch)),
        name="flash_decode_fwd",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=bool(interpret),
    )(*prefetch, q, k, v)


#: scoped-VMEM limit the decode kernels request from Mosaic (v5e: 128 MiB
#: physical, 16 MiB default scope) and the share of it a plan may claim
_VMEM_LIMIT = 32 * 1024 * 1024
_VMEM_BUDGET = 16 * 1024 * 1024


def _padded_head_tile(n_head, d_head, esize):
    """(rows, lanes) one [h, dh] cache row occupies in VMEM: h pads to
    the dtype's sublane quantum, dh to 128 lanes."""
    sub = 8 if esize >= 4 else 16
    return -(-n_head // sub) * sub, -(-d_head // 128) * 128


def _walk_vmem_bytes(block_t, n_head, d_head, esize):
    """VMEM one decode grid step holds, counted the way Mosaic
    allocates it: padded [block_t, h, dh] cache tiles
    (_padded_head_tile); the pipeline double-buffers the k and the v
    tile; the body keeps about four tile-sized f32 temporaries
    (promoted k/v, the k*q and p*v products)."""
    rows, lanes = _padded_head_tile(n_head, d_head, esize)
    tile = block_t * rows * lanes
    return 2 * 2 * tile * esize + 4 * tile * 4


def _decode_plan(q, k, block_t, interpret):
    """Static feasibility gate; returns (ok, block_t, interpret).

    Contract (mirrors the attention-kernel discipline; audited statically
    by analysis/kernel_lint.py):
      * d_head % 64 == 0 (dh is the lane dim of every tile) and
        n_head % 8 == 0 for f32 / % 16 for narrower dtypes (h is the
        sublane dim of the [t, h, dh] cache tile);
      * max_t % block_t == 0 (the length-masked tail block is the ONLY
        partial block) and block_t % 8 == 0;
      * the double-buffered k/v tiles + f32 temporaries
        (_walk_vmem_bytes) fit _VMEM_BUDGET of the requested limit.
    Off-contract shapes return ok=False and the caller runs the XLA
    fallback — numerically identical, just without the length-bounded
    block streaming.
    """
    import numpy as np

    from .placement import resolve

    b, h, dh = q.shape
    max_t = k.shape[1]
    compiled, interpret = resolve(interpret)
    esize = np.dtype(q.dtype).itemsize
    block_t = min(block_t, max_t)
    # snap the block down to a divisor of max_t (max_t is a power-of-two
    # buffer in the generation tier, so this terminates at a sane size)
    while block_t > 8 and max_t % block_t:
        block_t //= 2
    sublane = 8 if esize >= 4 else 16
    ok = (
        (compiled or interpret)
        and dh % 64 == 0
        and h % sublane == 0
        and max_t % block_t == 0
        and block_t % 8 == 0
        and _walk_vmem_bytes(block_t, h, dh, esize) <= _VMEM_BUDGET
    )
    return ok, block_t, interpret


def flash_decode(q, k, v, lengths, scale=1.0, block_t=256, interpret=None):
    """Single-query attention against a length-masked cache.

    q [b, h, dh]; k/v [b, max_t, h, dh] (HBM-resident, the generation
    tier's per-layer cache slice); lengths [b] int32.  Returns
    [b, h, dh].  Off-contract shapes (or off-TPU without an explicit
    interpret=True) run reference_decode instead.
    """
    import jax.numpy as jnp

    ok, block_t, interp = _decode_plan(q, k, block_t, interpret)
    if not ok or (interp and interpret is None):
        # off-TPU the XLA fallback beats interpret-mode emulation; tests
        # drive the kernel explicitly with interpret=True
        return reference_decode(q, k, v, lengths, scale)

    return _decode_call(
        q, k, v, (lengths.astype(jnp.int32),), k.shape[1] // block_t,
        lambda i, t, lens: (i, 0, 0),
        lambda i, t, lens: (
            i, jnp.minimum(t, _last_block(lens[i], block_t)), 0, 0),
        block_t, scale, interp)


# -- paged variant (FLAGS_paged_kv_cache) --------------------------------
#
# The cache is a global block POOL [num_blocks, block_t, h, dh] (one
# layer's slice); a sequence's logical row r lives at pool block
# table[seq, r // block_t], row r % block_t.  The kv walk is identical to
# the ring kernel's except the DMA source address comes from the
# scalar-prefetched block table instead of a contiguous row window — the
# vLLM PagedAttention layout on the make_async_copy idiom.


def reference_decode_paged(q, k_pool, v_pool, table, lengths, scale=1.0):
    """Pure-XLA paged fallback: gather the table-addressed blocks into
    the contiguous logical view and run the ring oracle on it.

    q [b, h, dh]; k_pool/v_pool [num_blocks, block_t, h, dh]; table
    [b, max_blocks] int32; lengths [b].  Positions >= length mask to
    -1e30 exactly as the ring path does, so whatever garbage sits in
    unreferenced (or trap) blocks contributes an exact softmax zero —
    the result is bit-identical to the ring cache holding the same
    valid rows.
    """
    nb, bt, h, dh = k_pool.shape
    b, mb = table.shape
    flat = table.reshape(-1)
    view_k = k_pool[flat].reshape(b, mb * bt, h, dh)
    view_v = v_pool[flat].reshape(b, mb * bt, h, dh)
    return reference_decode(q, view_k, view_v, lengths, scale)


def paged_scatter_rows(cache, new, table, pos, active, layer):
    """Functional core of the paged cache write, shared by the
    paged_kv_cache_update lowering and the fused megastep's XLA
    composition (so flag-on fused/unfused programs stay bit-identical).

    cache [L, num_blocks, block_t, h, dh]; new [b, t, h, dh]; table
    [b, max_blocks] int32; pos [b].  Logical rows pos..pos+t-1 of each
    sequence scatter to pool row table[b, r // bt] * bt + r % bt of
    layer `layer`; inactive lanes and rows past the logical window
    route out of bounds and DROP (the paged analogue of the ring's
    keep-mask + clamp).
    """
    import jax.numpy as jnp

    nb, bt = cache.shape[1], cache.shape[2]
    h, dh = cache.shape[3], cache.shape[4]
    b, t = new.shape[0], new.shape[1]
    mb = table.shape[1]
    pos32 = pos.reshape(-1).astype(jnp.int32)
    rows = pos32[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    blk = jnp.take_along_axis(
        table.astype(jnp.int32), jnp.clip(rows // bt, 0, mb - 1), axis=1)
    flat = blk * bt + rows % bt
    total = nb * bt
    oob = rows >= mb * bt
    if active is not None:
        keep = active.reshape(-1).astype(jnp.bool_)
        oob = oob | ~keep[:, None]
    flat = jnp.where(oob, total, flat)
    pool = cache[layer].reshape(total, h, dh)
    pool = pool.at[flat.reshape(-1)].set(
        new.reshape(b * t, h, dh).astype(pool.dtype), mode="drop")
    return cache.at[layer].set(pool.reshape(nb, bt, h, dh))


#: the flattened block table rides scalar prefetch into SMEM alongside
#: the lengths; past this many entries it no longer fits the scalar
#: budget and the plan rejects (the lint matrix's oversized-table leg)
_PAGED_TABLE_CAP = 4096


def _paged_plan(q, k_pool, table, interpret):
    """Static feasibility gate for the paged walk; returns
    (ok, block_t, interpret).

    block_t is FIXED by the pool geometry (no snapping — a misaligned
    pool is a build error, not a tuning knob), so the gate rejects:
      * block_t % 8 != 0 (sublane quantum of the DMA'd [bt, h, dh]
        tile) — plus the ring kernel's dh % 64 / n_head sublane checks;
      * b * max_blocks > _PAGED_TABLE_CAP (the whole table must stay
        SMEM-resident for per-iteration address lookups);
      * tiles + temporaries (_walk_vmem_bytes) past _VMEM_BUDGET.
    """
    import numpy as np

    from .placement import resolve

    b, h, dh = q.shape
    block_t = int(k_pool.shape[1])
    max_blocks = int(table.shape[1])
    compiled, interpret = resolve(interpret)
    esize = np.dtype(q.dtype).itemsize
    sublane = 8 if esize >= 4 else 16
    ok = (
        (compiled or interpret)
        and dh % 64 == 0
        and h % sublane == 0
        and block_t % 8 == 0
        and b * max_blocks <= _PAGED_TABLE_CAP
        and _walk_vmem_bytes(block_t, h, dh, esize) <= _VMEM_BUDGET
    )
    return ok, block_t, interpret


def flash_decode_paged(q, k_pool, v_pool, table, lengths, scale=1.0,
                       interpret=None):
    """Single-query attention over the paged pool.

    q [b, h, dh]; k_pool/v_pool [num_blocks, block_t, h, dh] (one
    layer's HBM-resident slice); table [b, max_blocks] int32; lengths
    [b].  Returns [b, h, dh].  Off-contract (or off-TPU without an
    explicit interpret=True) runs reference_decode_paged.
    """
    import jax.numpy as jnp

    ok, block_t, interp = _paged_plan(q, k_pool, table, interpret)
    if not ok or (interp and interpret is None):
        return reference_decode_paged(q, k_pool, v_pool, table, lengths,
                                      scale)

    b, h, dh = q.shape
    max_blocks = int(table.shape[1])
    # the ring kernel with a table hop: logical block t of sequence i
    # streams from pool block tab[i * max_blocks + t]
    return _decode_call(
        q, k_pool, v_pool,
        (lengths.astype(jnp.int32), table.reshape(-1).astype(jnp.int32)),
        max_blocks,
        lambda i, t, lens, tab: (i, 0, 0),
        lambda i, t, lens, tab: (
            tab[i * max_blocks
                + jnp.minimum(t, _last_block(lens[i], block_t))], 0, 0, 0),
        block_t, scale, interp)
