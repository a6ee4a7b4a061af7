"""Flash attention — Pallas TPU kernels with online softmax, forward AND
backward.

Replaces the reference's unfused matmul+softmax+matmul attention chain
(tests/unittests/transformer_model.py:44 builds it op-by-op; the reference
has no fused attention kernel at all — this is the TPU capability upgrade
called out in SURVEY.md §7.6).

Design (per pallas_guide.md):
  * forward: grid (batch*heads, q_blocks); K/V stream through VMEM in
    kv-blocks with running max/sum (online softmax), fp32 accumulation; the
    per-row logsumexp is saved as a residual.
  * backward: FlashAttention-2 style split — one kernel computes dK/dV on a
    (batch*heads, kv_blocks) grid, one computes dQ on (batch*heads,
    q_blocks); both recompute the probability blocks from Q/K and the saved
    logsumexp, so no O(T^2) softmax matrix is ever materialized in either
    pass.  delta = rowsum(dO * O) is a cheap XLA prologue.
  * causal masking is bottom-right aligned; fully-masked blocks are skipped
    via dynamic fori_loop bounds (halves causal FLOPs).
  * additive bias is indexed per-block with broadcast-aware index maps
    ([B,1,1,Tk] padding masks and [B,1,Tq,Tk] causal+padding masks are read
    as-is — never broadcast-materialized to [B,H,Tq,Tk] in HBM).

Falls back to a pure-XLA implementation off-TPU or for unaligned shapes.
The bias gradient (trainable-bias case, e.g. relative-position biases) is
computed by an XLA recompute expression outside the kernels; when the bias
is a stop-gradient mask (the usual case) XLA dead-code-eliminates it.
"""

from __future__ import annotations

import functools


def _bd_visible(q_pos, k_pos, block, offset):
    """The block-diffusion mask over [noisy ; clean] (BD3-LM, arXiv:
    2503.09573, section 3, the one-pass form): rows and keys 0 .. offset-1
    are the noisy copy of a sequence, offset .. 2*offset-1 the clean one,
    both cut into blocks of `block` positions.  A noisy row sees its own
    noisy block (both ways) and the clean blocks before it; a clean row
    sees the clean blocks up to its own; no clean row sees a noisy key.
    Elementwise over int32 position arrays; the kernels, the XLA fallback
    and the bias recompute all call this one function."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def blk(pos):  # numpy scalars inline as literals inside a kernel
        pos = jnp.where(pos < offset, pos, pos - offset)
        if block & (block - 1) == 0:
            return jax.lax.shift_right_logical(
                pos, np.int32(block.bit_length() - 1))
        return jax.lax.div(pos, np.int32(block))

    q_noisy, k_noisy = q_pos < offset, k_pos < offset
    qb, kb = blk(q_pos), blk(k_pos)
    # and / or / not only: Mosaic has no select between boolean vectors
    same = kb == qb
    return (k_noisy & q_noisy & same) | (
        ~k_noisy & ((kb < qb) | (~q_noisy & same)))


def _bd_plane(tq, tk, mask):
    """[tq, tk] bool of `mask` = (block_length, clean_offset)."""
    import jax.numpy as jnp

    return _bd_visible(jnp.arange(tq, dtype=jnp.int32)[:, None],
                       jnp.arange(tk, dtype=jnp.int32)[None, :], *mask)


def _repeat_kv(q, k, v):
    """K and V at the query's head count (axis 1): query head i reads
    key/value head i // group.  The kernels never do this in HBM."""
    import jax.numpy as jnp

    group = q.shape[1] // k.shape[1]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


def reference_attention(q, k, v, bias=None, scale=1.0, causal=False,
                        dropout_rate=0.0, dropout_seed=None, mask=None):
    """Pure-XLA fallback (and numerics reference for tests).

    K and V may have fewer heads than q (grouped-query attention: head i
    reads key/value head i // group; they are repeated here, so their
    gradients come back summed over the group).  `mask`, (block_length,
    clean_offset), is the block-diffusion mask of `_bd_visible`.

    Rows with no causally-visible key (only possible when Tq > Tk under
    bottom-right-aligned causal masking) produce zero output and zero
    gradients — the standard flash-attention convention, and what the
    Pallas path implements.

    With dropout_rate > 0 the attention WEIGHTS are dropped (the
    reference's dropout-on-softmax semantics, transformer_model.py:44)
    using the counter-based hash of kernels/hash_rng.py over the global
    [b, h, tq, tk] element index — bit-identical to the mask the Pallas
    kernels generate in-kernel from the same seed."""
    import jax
    import jax.numpy as jnp

    k, v = _repeat_kv(q, k, v)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        logits = jnp.where(jnp.tril(jnp.ones((tq, tk), bool), tk - tq),
                           logits, -1e30)
    if mask is not None:
        logits = jnp.where(_bd_plane(*logits.shape[-2:], mask), logits,
                           -1e30)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_rate:
        from . import hash_rng

        keep = hash_rng.keep_mask_attn(dropout_seed, weights.shape,
                                       dropout_rate)
        inv = jnp.asarray(1.0 / (1.0 - dropout_rate), weights.dtype)
        weights = jnp.where(keep, weights * inv, jnp.zeros((), weights.dtype))
    out = jnp.einsum("bhqk,bhkd->bhqd", weights, v)
    if causal and q.shape[2] > k.shape[2]:
        tq, tk = q.shape[2], k.shape[2]
        visible = jnp.tril(jnp.ones((tq, tk), bool), tk - tq).any(axis=-1)
        out = jnp.where(visible[:, None], out, jnp.zeros_like(out))
    return out


def _reference_bthd(q, k, v, bias, scale, causal, dropout_rate=0.0,
                    dropout_seed=None, mask=None):
    out = reference_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), bias, scale, causal,
        dropout_rate, dropout_seed, mask)
    return out.transpose(0, 2, 1, 3)


def _keep_tile_prng(seed_ref, shape, pid0, q_blk, k_blk, rate):
    """Hardware-PRNG keep-mask for one attention-weights tile (TPU Pallas
    only).  The per-core PRNG is re-seeded per (stream seed, grid row,
    q-block index, k-block index) — the counter-based-RNG idiom of Salmon
    et al. "Parallel Random Numbers: As Easy as 1, 2, 3" — so the fwd
    kernel and both bwd kernels regenerate bit-identical tiles no matter
    which grid order walks them, and the mask never exists outside
    registers.  This replaces the lowbias32 hash regeneration whose
    O(T²·H) integer vector ops, paid in THREE kernels, made in-kernel
    weights-dropout a net loss at seq 256 (PERF.md r05: −2.5 MFU pts);
    prng_random_bits is a native per-lane generator with no per-element
    mix chain.  Requires fwd and bwd to agree on block sizes (they do:
    _plan picks them once per flash_attention call)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from . import hash_rng

    # Mosaic seeds the PRNG from at most TWO 32-bit values ("Setting seed
    # with more than 2 values is not supported", libtpu 0.0.34): the grid
    # row folds into the stream seed by an odd multiplier (a bijection
    # mod 2^32, the attn_head_seed idiom) and the (q-block, k-block) pair
    # packs into one word (exact for < 65536 blocks per axis).
    i32 = jnp.int32
    row_seed = (seed_ref[0].astype(i32)
                + jnp.asarray(pid0).astype(i32)
                * np.uint32(hash_rng.GOLDEN).astype(np.int32))
    tile = (jnp.asarray(q_blk).astype(i32) * np.int32(65536)
            + jnp.asarray(k_blk).astype(i32))
    pltpu.prng_seed(row_seed, tile)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits >= np.uint32(hash_rng.keep_threshold(rate))


def _keep_tile(seed, shape, head_base, tq, tk, q_lo, k_lo, rate):
    """In-kernel dropout keep-mask for an attention-weights tile.

    shape [h, bq, bk] (whole-head bthd kernels; head_base = b*H) or
    [bq, bk] (bhtd kernels; head_base = the grid's combined b*H + h index).
    The mask bit for logical element (b, h, q, k) is a pure function of
    (seed, b*H + h, q*Tk + k): the head coordinate folds into the seed
    (hash_rng.attn_head_seed — a flat index over [b*h, Tq, Tk] would wrap
    uint32 past 2^32 elements and correlate bits) and the in-plane index
    keys the hash.  Forward and both backward kernels (different grids)
    regenerate identical masks, and the pure-XLA fallback
    (hash_rng.keep_mask_attn) matches bit-for-bit.  tq/tk are unused but
    kept so call sites document the plane extents (exact for tk <= 65535).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import hash_rng

    del tq  # plane index needs only tk; see docstring
    u32 = jnp.uint32
    q_lo = jnp.asarray(q_lo).astype(u32)
    k_lo = jnp.asarray(k_lo).astype(u32)
    head_base = jnp.asarray(head_base).astype(u32)
    if len(shape) == 3:
        gh = head_base + jax.lax.broadcasted_iota(u32, shape, 0)
        q_idx = q_lo + jax.lax.broadcasted_iota(u32, shape, 1)
        k_idx = k_lo + jax.lax.broadcasted_iota(u32, shape, 2)
    else:
        gh = head_base
        q_idx = q_lo + jax.lax.broadcasted_iota(u32, shape, 0)
        k_idx = k_lo + jax.lax.broadcasted_iota(u32, shape, 1)
    # np.uint32 constants inline as jaxpr literals (jax Arrays would be
    # constvars, which a pallas_call refuses to lower)
    hseed = hash_rng.attn_head_seed(seed, gh)
    return hash_rng.keep_mask_tile(hseed, q_idx * np.uint32(tk) + k_idx,
                                   rate, fast=True)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


# lse/delta are per-q-row f32 vectors.  Mosaic's min-tile rule ((8, 128)
# for f32) forbids (1, block_q) blocks of a (bh, tq) array once bh > 1, so
# they live in HBM as (bh, 8, tq): q on the lane dim, replicated across 8
# sublanes (the same trick splash_attention uses, with lanes/sublanes
# swapped because our kernels want q as a column).
LSE_SUBLANES = 8


def _read_bias(bias_ref, q_lo, block_q, k_lo, block_k, bias_q1):
    """Slice a [block_q, block_k] (or [1, block_k]) bias tile from the
    kernel-local bias block (leading broadcast dims squeezed by the
    BlockSpec).  `q_lo`/`k_lo` are offsets into the local block (already 0
    when the BlockSpec pinned that dim)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if bias_q1:
        b = bias_ref[:, pl.ds(k_lo, block_k)]  # [1, block_k]
    else:
        b = bias_ref[pl.ds(q_lo, block_q), pl.ds(k_lo, block_k)]
    return b.astype(jnp.float32)


def _py_where(cond, a, b):
    return a if cond else b


def _bd_key_tiles(q_lo, block_q, block_k, block, offset, where=_py_where):
    """The key tiles that rows q_lo .. q_lo + block_q - 1 may see under the
    block-diffusion mask, as two runs (first tile, count): among the noisy
    keys (the rows' own blocks; none for clean rows) and among the clean
    keys (the blocks before a noisy row's, up to a clean row's own).  No
    other tile holds a visible pair, so the walks visit no other.  A tile
    lies in one half (the plan asks offset % block_q == 0 == offset %
    block_k).  Python ints in and out (the plan's count), or traced
    scalars with `where=jnp.where` (inside a kernel)."""
    noisy = q_lo < offset
    row = where(noisy, q_lo, q_lo - offset)
    b_lo, b_hi = row // block, (row + block_q - 1) // block
    a0 = b_lo * block // block_k
    na = where(noisy, ((b_hi + 1) * block - 1) // block_k + 1 - a0, 0)
    nc = (where(noisy, b_hi, b_hi + 1) * block + block_k - 1) // block_k
    return a0, na, offset // block_k, nc


def _bd_query_tiles(k_lo, block_q, block_k, block, offset, where=_py_where):
    """The transpose of `_bd_key_tiles`: the query tiles that may see keys
    k_lo .. k_lo + block_k - 1, as two runs (first tile, count): noisy rows
    (of a noisy key's own blocks; of the blocks after a clean key's) and
    clean rows (from a clean key's block on; none for noisy keys)."""
    noisy = k_lo < offset
    col = where(noisy, k_lo, k_lo - offset)
    b_lo, b_hi = col // block, (col + block_k - 1) // block
    n0 = where(noisy, b_lo, b_lo + 1) * block // block_q
    nn = where(noisy, ((b_hi + 1) * block - 1) // block_q + 1,
               offset // block_q) - n0
    c0 = (offset + b_lo * block) // block_q
    return n0, nn, c0, where(noisy, 0, 2 * offset // block_q - c0)


def bd_tiles_visited(block_q, block_k, block, offset):
    """(visited, total) (query tile, key tile) pairs a head and sequence
    of the masked forward walk over 2 * offset positions."""
    total_q = 2 * offset // block_q
    visited = 0
    for i in range(total_q):
        _, na, _, nc = _bd_key_tiles(i * block_q, block_q, block_k, block,
                                     offset)
        visited += na + nc
    return visited, total_q * (2 * offset // block_k)


def _bd_walk(mask, lo, block_q, block_k, tiles_of):
    """(count, tile(t)) of a masked walk from the tile that starts at
    `lo`: the t-th tile it visits, first run first."""
    import jax.numpy as jnp

    a0, na, c0, nc = tiles_of(lo, block_q, block_k, *mask, where=jnp.where)
    return na + nc, lambda t: jnp.where(t < na, a0 + t, c0 + t - na)


def _bd_tile(q_lo, k_lo, block_q, block_k, mask):
    """[block_q, block_k] bool: the mask of the tile at (q_lo, k_lo)."""
    import jax
    import jax.numpy as jnp

    shape = (block_q, block_k)
    return _bd_visible(
        q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 0),
        k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1), *mask)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                scale, block_q, block_k, causal, seq_q, seq_k,
                causal_offset, bias_q1, drop_rate, inv_keep, hw_prng=False,
                mask=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    pid0 = pl.program_id(0)

    q = q_ref[...].astype(jnp.float32) * scale  # [block_q, d]
    m = jnp.full((block_q,), -jnp.inf, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)  # [.., d_v]

    n_kv = seq_k // block_k
    if causal:
        # highest k position visible to this q block, bottom-right aligned
        hi = qi * block_q + block_q - 1 + causal_offset
        n_kv = jnp.minimum(n_kv, (hi // block_k) + 1)
    if mask is not None:
        n_kv, tile = _bd_walk(mask, qi * block_q, block_q, block_k,
                              _bd_key_tiles)

    def body(j, carry):
        m, l, acc = carry
        if mask is not None:
            j = tile(j)
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T  # [block_q, block_k]
        if bias_ref is not None:
            s = s + _read_bias(bias_ref, 0, block_q, j * block_k, block_k,
                               bias_q1)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos + causal_offset >= k_pos, s, -1e30)
        if mask is not None:
            s = jnp.where(_bd_tile(qi * block_q, j * block_k, block_q,
                                   block_k, mask), s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=1)
        if drop_rate:
            # weights-dropout: l (the softmax normalizer) accumulates the
            # UNdropped p; only the value-accumulator sees the mask
            if hw_prng:
                keep = _keep_tile_prng(seed_ref, (block_q, block_k),
                                       pid0, qi, j, drop_rate)
            else:
                keep = _keep_tile(seed_ref[0], (block_q, block_k),
                                  pid0, seq_q, seq_k,
                                  qi * block_q, j * block_k, drop_rate)
            p = jnp.where(keep, p, 0.0)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m, l, acc))
    # Rows with no visible key (Tq > Tk causal: the dynamic bound can be 0,
    # or every visited entry was causally masked to -1e30): output 0, and
    # lse=+inf so the backward recompute p = exp(s - lse) is exactly 0.
    masked = (l == 0.0) | (m <= -1e29)
    l_safe = jnp.where(masked, 1.0, l)
    if drop_rate:
        acc = acc * inv_keep
    o_ref[...] = jnp.where(
        masked[:, None], 0.0, acc / l_safe[:, None]
    ).astype(o_ref.dtype)
    lse = jnp.where(masked, jnp.inf, m + jnp.log(l_safe))
    lse_ref[...] = jnp.broadcast_to(lse[None, :], (LSE_SUBLANES, block_q))


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, *, scale, block_q, block_k, causal,
                   seq_q, seq_k, causal_offset, bias_q1, drop_rate, inv_keep,
                   hw_prng=False, mask=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    pid0 = pl.program_id(0)

    q = q_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[0, :]      # [block_q] f32 (sublane-replicated tile)
    delta = delta_ref[0, :]  # [block_q] f32
    d = q.shape[-1]
    acc = jnp.zeros((block_q, d), jnp.float32)

    n_kv = seq_k // block_k
    if causal:
        hi = qi * block_q + block_q - 1 + causal_offset
        n_kv = jnp.minimum(n_kv, (hi // block_k) + 1)
    if mask is not None:
        n_kv, tile = _bd_walk(mask, qi * block_q, block_q, block_k,
                              _bd_key_tiles)

    def body(j, acc):
        if mask is not None:
            j = tile(j)
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = (q @ k.T) * scale
        if bias_ref is not None:
            s = s + _read_bias(bias_ref, 0, block_q, j * block_k, block_k,
                               bias_q1)
        p = jnp.exp(s - lse[:, None])
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            p = jnp.where(q_pos + causal_offset >= k_pos, p, 0.0)
        if mask is not None:
            p = jnp.where(_bd_tile(qi * block_q, j * block_k, block_q,
                                   block_k, mask), p, 0.0)
        dp = do @ v.T  # [block_q, block_k]
        if drop_rate:
            if hw_prng:
                keep = _keep_tile_prng(seed_ref, (block_q, block_k),
                                       pid0, qi, j, drop_rate)
            else:
                keep = _keep_tile(seed_ref[0], (block_q, block_k),
                                  pid0, seq_q, seq_k,
                                  qi * block_q, j * block_k, drop_rate)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        ds = p * (dp - delta[:, None]) * scale
        return acc + ds @ k

    acc = jax.lax.fori_loop(0, n_kv, body, acc)
    dq_ref[...] = acc.astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, *, scale, block_q, block_k,
                    causal, seq_q, seq_k, causal_offset, bias_q1, drop_rate,
                    inv_keep, hw_prng=False, mask=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    pid0 = pl.program_id(0)

    k = k_ref[...].astype(jnp.float32)  # [block_k, d]
    v = v_ref[...].astype(jnp.float32)  # [block_k, d_v]
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)

    n_q = seq_q // block_q
    lo = 0
    if causal:
        # first q position that can see this kv block
        lo_pos = ki * block_k - causal_offset
        lo = jnp.maximum(lo_pos // block_q, 0)
    if mask is not None:
        n_q, tile = _bd_walk(mask, ki * block_k, block_q, block_k,
                             _bd_query_tiles)

    def body(i, carry):
        dk, dv = carry
        if mask is not None:
            i = tile(i)
        q = q_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q)]
        delta = delta_ref[0, pl.ds(i * block_q, block_q)]
        s = (q @ k.T) * scale  # [block_q, block_k]
        if bias_ref is not None:
            s = s + _read_bias(bias_ref, i * block_q, block_q, 0, block_k,
                               bias_q1)
        p = jnp.exp(s - lse[:, None])
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            p = jnp.where(q_pos + causal_offset >= k_pos, p, 0.0)
        if mask is not None:
            p = jnp.where(_bd_tile(i * block_q, ki * block_k, block_q,
                                   block_k, mask), p, 0.0)
        dp = do @ v.T
        if drop_rate:
            if hw_prng:
                keep = _keep_tile_prng(seed_ref, (block_q, block_k),
                                       pid0, i, ki, drop_rate)
            else:
                keep = _keep_tile(seed_ref[0], (block_q, block_k),
                                  pid0, seq_q, seq_k,
                                  i * block_q, ki * block_k, drop_rate)
            dv = dv + jnp.where(keep, p * inv_keep, 0.0).T @ do
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        else:
            dv = dv + p.T @ do
        ds = p * (dp - delta[:, None]) * scale
        dk = dk + ds.T @ q
        return dk, dv

    dk, dv = jax.lax.fori_loop(lo, n_q, body, (dk, dv))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Host-side plumbing
# ---------------------------------------------------------------------------


def _dims(x, fmt):
    """(b, h, t, d) of a q/k/v array in the given format."""
    if fmt == "bthd":
        b, t, h, d = x.shape
    else:
        b, h, t, d = x.shape
    return b, h, t, d


def _plan(q, k, block_q, block_k, interpret, fmt="bhtd", v=None, mask=None):
    """Static feasibility check; returns (ok, block_q, block_k, interpret).

    `v` (default: shaped like k) may carry a value head size of its own
    (latent attention: d_qk 192, d_v 128).  The bhtd kernels take it: q
    and k tiles are [.., d_qk], v, the context and its cotangent
    [.., d_v].  k and v may also carry a head count of their own that
    divides q's (grouped-query attention: 32 query heads over 4): the bhtd
    kernels read key/value head i // group through their block index maps.
    `mask` = (block_length, clean_offset) asks for the block-diffusion
    mask over [noisy ; clean]: bhtd, tq == tk == 2 * clean_offset, blocks
    that divide the half and a block length that divides it too, so that
    a tile lies in one half.  The whole-head bthd kernels keep one head
    size, one head count and no such mask."""
    from .placement import resolve

    b, h, tq, d = _dims(q, fmt)
    hk, tk = _dims(k, fmt)[1:3]
    dv = d if v is None else _dims(v, fmt)[3]
    if fmt == "bthd" and (dv != d or hk != h or mask is not None):
        return False, 0, 0, resolve(interpret)[1]
    if h % hk:
        return False, 0, 0, resolve(interpret)[1]
    compiled, interpret = resolve(interpret)
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    if fmt == "bthd":
        # whole-head blocks: each kv tile is [block, h, d] — cap the block
        # so the bwd kernel's working set fits vmem (block=512 with
        # h*d=512 bf16 fails to compile; 256 is the measured safe bound:
        # 256 KB per kv tile).  The bound is in BYTES, so the cap scales
        # with the dtype: the original hardcoded 2-byte element size let
        # f32 tiles reach 512 KB (caught by the kernel plan linter,
        # analysis/kernel_lint.py).  When even the smallest Mosaic-
        # alignable block (128 lanes) busts the bound, compiled TPU mode
        # must REJECT to the XLA fallback — flooring to 128 would re-admit
        # the exact oversized-tile compile failure the cap exists for
        # (interpret mode has no tile bound; keep the floor there so CPU
        # tests still exercise the kernels).
        import numpy as np

        esize = np.dtype(q.dtype).itemsize
        cap = (256 * 1024) // max(h * d * esize, 1)
        if cap < 128:
            if compiled:
                return False, 0, 0, interpret
            cap = 128
        block_q = min(block_q, cap)
        block_k = min(block_k, cap)
    if compiled:
        # Mosaic: lane-dim (last-dim) dynamic-slice offsets must be
        # 128-aligned; sublane offsets 8-aligned.  The backward kernels
        # slice the lse/delta lane dim by block_q, so it needs 128 too.
        if block_k % 128:
            block_k = 128 if tk % 128 == 0 else 0
        if block_q % 128:
            block_q = 128 if tq % 128 == 0 else 0
    ok = (
        block_q
        and block_k
        and tq % block_q == 0
        and tk % block_k == 0
        and d % 64 == 0  # 64 runs at half-lane MXU occupancy but still wins
        and dv % 64 == 0
        and (compiled or interpret)
    )
    if ok and mask is not None:
        block, offset = mask
        ok = (tq == tk == 2 * offset and offset % block == 0
              and offset % block_q == 0 and offset % block_k == 0)
    return ok, block_q, block_k, interpret


def _bias_spec_and_arg(bias, b, h, tq, tk, block_q, block_k, for_dkv):
    """BlockSpec + argument for the (unbroadcast) bias.

    bias is [Bb, Hb, Tqb, Tk] with Bb in {1, b}, Hb in {1, h}, Tqb in
    {1, tq}.  The grid's first axis is i = batch*h + head; index maps pin
    broadcast dims to 0.  The two leading dims are squeezed, so kernels see
    a [q, k] tile.  Returns (spec, arg, bias_q1)."""
    from jax.experimental import pallas as pl

    bb, hb, tqb, tkb = bias.shape
    bias_q1 = tqb == 1

    def ib(i):
        return i // h if bb > 1 else 0

    def ih(i):
        return i % h if hb > 1 else 0

    if for_dkv:
        # kv-block grid: full q extent, one kv block
        qdim = 1 if bias_q1 else tqb
        spec = pl.BlockSpec(
            (None, None, qdim, block_k),
            lambda i, j: (ib(i), ih(i), 0, j),
        )
    else:
        # q-block grid: one q block, full k extent
        if bias_q1:
            spec = pl.BlockSpec(
                (None, None, 1, tkb), lambda i, j: (ib(i), ih(i), 0, 0)
            )
        else:
            spec = pl.BlockSpec(
                (None, None, block_q, tkb), lambda i, j: (ib(i), ih(i), j, 0)
            )
    return spec, bias, bias_q1


def _qkv_specs(fmt, h, seq_mode_q, seq_mode_k, block_q, block_k, tq, tk, d,
               group=1):
    """BlockSpecs for q-like and k-like operands.

    fmt "bhtd": arrays are pre-reshaped to [b*h, t, d]; grid axis 0 is bh.
    With `group` > 1 the k-like operand is [b*h/group, t, d] and grid row
    i reads its row i // group (b*h + head -> b*h_kv + head // group):
    grouped-query attention without K or V at the query's head count.
    fmt "bthd": arrays stay [b, t, h, d] — the layout the qkv projection
    produces for free (reshape of [b, t, h*d] is a bitcast), so NO
    transpose/relayout copy ever materializes at the custom-call boundary
    (the round-3 profile showed ~5.5 GB/step of such copies).  Grid axis 0
    is b; blocks cover ALL heads (Mosaic's (8,128) tiling forbids slicing
    the second-minor h dim), and the whole-head kernels batch the matmuls
    over h in-register.

    seq_mode_*: "block" (one seq block, indexed by grid axis 1) or "full"
    (whole sequence pinned)."""
    from jax.experimental import pallas as pl

    def spec(seq_mode, block, t, group=1):
        if fmt == "bthd":
            if seq_mode == "block":
                return pl.BlockSpec(
                    (None, block, h, d), lambda i, j: (i, j, 0, 0)
                )
            return pl.BlockSpec(
                (None, t, h, d), lambda i, j: (i, 0, 0, 0)
            )
        row = (lambda i: i // group) if group > 1 else (lambda i: i)
        if seq_mode == "block":
            return pl.BlockSpec((None, block, d),
                                lambda i, j: (row(i), j, 0))
        return pl.BlockSpec((None, t, d), lambda i, j: (row(i), 0, 0))

    return (
        spec(seq_mode_q, block_q, tq),
        spec(seq_mode_k, block_k, tk, group),
    )


# ---------------------------------------------------------------------------
# Whole-head ("bthd") kernels: operands [b, t, h, d] with blocks covering
# all heads; matmuls batch over h (Mosaic batched dot_general, batch dim 0)
# after an in-register [t, h, d] -> [h, t, d] relayout — the relayout that
# the bhtd path pays as an HBM transpose happens here for free in VMEM.
# lse/delta ride as [b, h, tq] f32 (h fills the sublane tile exactly).
# ---------------------------------------------------------------------------


def _bdot(a, b_, contract_a, contract_b):
    """Batched-over-dim-0 dot: a [h, m, x], b_ [h, n, y] -> [h, m, n]."""
    import jax

    return jax.lax.dot_general(
        a, b_, ((contract_a, contract_b), ((0,), (0,)))
    )


def _bias_tile_f32(bias_ref, n_head, bias_h, bias_q1, block_q, q_lo,
                   block_k, k_lo):
    """Read the bias tile as f32 [h|1, q, k].  q-collapsed tiles are
    expanded to [q, k] via an outer product with a ones column — Mosaic
    miscompiles a sublane-extent-1 broadcast next to the batched matmuls
    (`Check failed: limits[i] <= dim(i)`), while a dot lowers cleanly."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if bias_h:
        if bias_q1:
            t = bias_ref[:, :, pl.ds(k_lo, block_k)].astype(jnp.float32)
            ones = jnp.ones((n_head, block_q, 1), jnp.float32)
            return _bdot(ones, t, (2,), (1,))  # [h, q, k]
        t = bias_ref[:, pl.ds(q_lo, block_q), pl.ds(k_lo, block_k)]
        return t.astype(jnp.float32)
    t = _read_bias(bias_ref, q_lo, block_q, k_lo, block_k, bias_q1)
    if bias_q1:
        ones = jnp.ones((block_q, 1), jnp.float32)
        t = jax.lax.dot_general(ones, t, (((1,), (0,)), ((), ())))
    return t[None]  # [1, q, k] broadcasts over heads (vreg replication)


def _fwd_kernel_bthd(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                     lse_ref, *, scale, n_head, block_q, block_k, causal,
                     seq_q, seq_k, causal_offset, bias_q1, bias_h,
                     drop_rate, inv_keep, hw_prng=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    h = n_head
    pid0h = pl.program_id(0) * h

    q = q_ref[...].astype(jnp.float32).transpose(1, 0, 2) * scale  # [h,q,d]
    d = q.shape[-1]
    m = jnp.full((h, block_q), -jnp.inf, jnp.float32)
    l = jnp.zeros((h, block_q), jnp.float32)
    acc = jnp.zeros((h, block_q, d), jnp.float32)

    n_kv = seq_k // block_k
    if causal:
        hi = qi * block_q + block_q - 1 + causal_offset
        n_kv = jnp.minimum(n_kv, (hi // block_k) + 1)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :, :].astype(
            jnp.float32).transpose(1, 0, 2)  # [h, k, d]
        v = v_ref[pl.ds(j * block_k, block_k), :, :].astype(
            jnp.float32).transpose(1, 0, 2)
        s = _bdot(q, k, (2,), (2,))  # [h, q, k]
        if bias_ref is not None:
            s = s + _bias_tile_f32(bias_ref, h, bias_h, bias_q1,
                                   block_q, 0, block_k, j * block_k)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (h, block_q, block_k), 1
            )
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (h, block_q, block_k), 2
            )
            s = jnp.where(q_pos + causal_offset >= k_pos, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=2))
        p = jnp.exp(s - m_new[:, :, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=2)
        if drop_rate:
            # weights-dropout: the normalizer l sees UNdropped p
            if hw_prng:
                keep = _keep_tile_prng(seed_ref, (h, block_q, block_k),
                                       pid0h, qi, j, drop_rate)
            else:
                keep = _keep_tile(seed_ref[0], (h, block_q, block_k),
                                  pid0h, seq_q, seq_k,
                                  qi * block_q, j * block_k, drop_rate)
            p = jnp.where(keep, p, 0.0)
        acc_new = acc * alpha[:, :, None] + _bdot(p, v, (2,), (1,))
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m, l, acc))
    masked = (l == 0.0) | (m <= -1e29)
    l_safe = jnp.where(masked, 1.0, l)
    if drop_rate:
        acc = acc * inv_keep
    o = jnp.where(masked[:, :, None], 0.0, acc / l_safe[:, :, None])
    o_ref[...] = o.transpose(1, 0, 2).astype(o_ref.dtype)
    lse_ref[...] = jnp.where(masked, jnp.inf, m + jnp.log(l_safe))


def _bwd_dq_kernel_bthd(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                        lse_ref, delta_ref, dq_ref, *, scale, n_head,
                        block_q, block_k, causal, seq_q, seq_k,
                        causal_offset, bias_q1, bias_h, drop_rate, inv_keep,
                        hw_prng=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    h = n_head
    pid0h = pl.program_id(0) * h

    q = q_ref[...].astype(jnp.float32).transpose(1, 0, 2)   # [h, q, d]
    do = do_ref[...].astype(jnp.float32).transpose(1, 0, 2)
    lse = lse_ref[...]      # [h, block_q] f32
    delta = delta_ref[...]
    d = q.shape[-1]
    acc = jnp.zeros((h, block_q, d), jnp.float32)

    n_kv = seq_k // block_k
    if causal:
        hi = qi * block_q + block_q - 1 + causal_offset
        n_kv = jnp.minimum(n_kv, (hi // block_k) + 1)

    def body(j, acc):
        k = k_ref[pl.ds(j * block_k, block_k), :, :].astype(
            jnp.float32).transpose(1, 0, 2)
        v = v_ref[pl.ds(j * block_k, block_k), :, :].astype(
            jnp.float32).transpose(1, 0, 2)
        s = _bdot(q, k, (2,), (2,)) * scale
        if bias_ref is not None:
            s = s + _bias_tile_f32(bias_ref, h, bias_h, bias_q1,
                                   block_q, 0, block_k, j * block_k)
        p = jnp.exp(s - lse[:, :, None])
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (h, block_q, block_k), 1
            )
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (h, block_q, block_k), 2
            )
            p = jnp.where(q_pos + causal_offset >= k_pos, p, 0.0)
        dp = _bdot(do, v, (2,), (2,))  # [h, q, k]
        if drop_rate:
            if hw_prng:
                keep = _keep_tile_prng(seed_ref, (h, block_q, block_k),
                                       pid0h, qi, j, drop_rate)
            else:
                keep = _keep_tile(seed_ref[0], (h, block_q, block_k),
                                  pid0h, seq_q, seq_k,
                                  qi * block_q, j * block_k, drop_rate)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        ds = p * (dp - delta[:, :, None]) * scale
        return acc + _bdot(ds, k, (2,), (1,))

    acc = jax.lax.fori_loop(0, n_kv, body, acc)
    dq_ref[...] = acc.transpose(1, 0, 2).astype(dq_ref.dtype)


def _bwd_dkv_kernel_bthd(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                         lse_ref, delta_ref, dk_ref, dv_ref, *, scale,
                         n_head, block_q, block_k, causal, seq_q, seq_k,
                         causal_offset, bias_q1, bias_h, drop_rate,
                         inv_keep, hw_prng=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    h = n_head
    pid0h = pl.program_id(0) * h

    k = k_ref[...].astype(jnp.float32).transpose(1, 0, 2)  # [h, k, d]
    v = v_ref[...].astype(jnp.float32).transpose(1, 0, 2)
    d = k.shape[-1]
    dk = jnp.zeros((h, block_k, d), jnp.float32)
    dv = jnp.zeros((h, block_k, d), jnp.float32)

    n_q = seq_q // block_q
    lo = 0
    if causal:
        lo_pos = ki * block_k - causal_offset
        lo = jnp.maximum(lo_pos // block_q, 0)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * block_q, block_q), :, :].astype(
            jnp.float32).transpose(1, 0, 2)  # [h, q, d]
        do = do_ref[pl.ds(i * block_q, block_q), :, :].astype(
            jnp.float32).transpose(1, 0, 2)
        lse = lse_ref[:, pl.ds(i * block_q, block_q)]    # [h, q]
        delta = delta_ref[:, pl.ds(i * block_q, block_q)]
        s = _bdot(q, k, (2,), (2,)) * scale  # [h, q, k]
        if bias_ref is not None:
            s = s + _bias_tile_f32(bias_ref, h, bias_h, bias_q1,
                                   block_q, i * block_q, block_k, 0)
        p = jnp.exp(s - lse[:, :, None])
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (h, block_q, block_k), 1
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (h, block_q, block_k), 2
            )
            p = jnp.where(q_pos + causal_offset >= k_pos, p, 0.0)
        dp = _bdot(do, v, (2,), (2,))        # [h, q, k]
        if drop_rate:
            if hw_prng:
                keep = _keep_tile_prng(seed_ref, (h, block_q, block_k),
                                       pid0h, i, ki, drop_rate)
            else:
                keep = _keep_tile(seed_ref[0], (h, block_q, block_k),
                                  pid0h, seq_q, seq_k,
                                  i * block_q, ki * block_k, drop_rate)
            dv = dv + _bdot(jnp.where(keep, p * inv_keep, 0.0), do,
                            (1,), (1,))      # [h, k, d]
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        else:
            dv = dv + _bdot(p, do, (1,), (1,))   # [h, k, d]
        ds = p * (dp - delta[:, :, None]) * scale
        dk = dk + _bdot(ds, q, (1,), (1,))   # [h, k, d]
        return dk, dv

    dk, dv = jax.lax.fori_loop(lo, n_q, body, (dk, dv))
    dk_ref[...] = dk.transpose(1, 0, 2).astype(dk_ref.dtype)
    dv_ref[...] = dv.transpose(1, 0, 2).astype(dv_ref.dtype)


def _bias_spec_bthd(bias, b, h, block_q, block_k, for_dkv):
    """BlockSpec for the bias on the whole-head grid (axis 0 = batch).
    Returns (spec, bias_q1, bias_h): bias_h marks a per-head bias (kernel
    tile [h, q, k]); otherwise leading dims squeeze to a [q, k] tile."""
    from jax.experimental import pallas as pl

    bb, hb, tqb, tkb = bias.shape
    bias_q1 = tqb == 1
    bias_h = hb > 1

    def ib(i):
        return i if bb > 1 else 0

    hdim = hb if bias_h else None
    if for_dkv:
        qdim = 1 if bias_q1 else tqb
        spec = pl.BlockSpec(
            (None, hdim, qdim, block_k), lambda i, j: (ib(i), 0, 0, j)
        )
    elif bias_q1:
        spec = pl.BlockSpec(
            (None, hdim, 1, tkb), lambda i, j: (ib(i), 0, 0, 0)
        )
    else:
        spec = pl.BlockSpec(
            (None, hdim, block_q, tkb), lambda i, j: (ib(i), 0, j, 0)
        )
    return spec, bias_q1, bias_h


def _drop_params(dropout_rate):
    """(drop_rate, inv_keep) static kernel params for a dropout rate."""
    if not dropout_rate:
        return 0.0, 1.0
    return float(dropout_rate), 1.0 / (1.0 - dropout_rate)


def _use_hw_prng(drop_rate, interpret):
    """Whether the kernels should draw dropout bits from the TPU hardware
    PRNG (pltpu.prng_seed / prng_random_bits) instead of the lowbias32
    hash.  Compiled-TPU only: prng_seed has no interpret/CPU lowering
    (jax 0.4.37, and retested on 0.9.0: `interpret=True` raises "MLIR
    translation rule for primitive 'prng_seed' not found for platform
    cpu"), so interpret mode and the XLA fallback keep the hash — each
    implementation still regenerates ITS mask identically in fwd and bwd
    (the parity contract is per-implementation, not cross-backend)."""
    if not drop_rate:
        return False
    from ..flags import FLAGS
    from .placement import resolve

    return resolve(interpret)[0] and FLAGS.tpu_prng_dropout


def _bthd_bwd_params(t, h, d, esize, causal):
    """Mosaic parameters of the whole-head backward kernels: 32 MiB of
    scoped VMEM (v5e: 128 MiB physical, 16 MiB default scope; the decode
    kernels ask the same) where the default has been seen refused, the
    default elsewhere.  A walk's working set stands near the default (q
    and dO, or k and v, whole beside the score planes of all heads), and
    XLA parks operands of its own in the same scope: at 16 MiB the dkv
    walk was refused at 8 heads x 256 rows with causal=True (17.49 MB:
    two position planes more; chip) and at BERT-base 32 x 512 (18.50 MB;
    compile-only client).  So: causal, or more than 512 KiB held whole
    (transformer-base's 256 x 8 x 64 in bf16 is at it).  Not for every
    call: the scope a kernel reserves is VMEM that XLA's fusions round
    it lose, 3.0 ms of transformer-base's 78.4 ms step with all 18 sites
    raised (PERF.md, PR 28)."""
    from jax.experimental.pallas import tpu as pltpu

    if causal or 2 * t * h * d * esize > 512 * 1024:
        return pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)
    return None


def _seed_spec():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _flash_forward(q, k, v, bias, seed, scale, causal, block_q, block_k,
                   interpret, fmt="bhtd", dropout_rate=0.0,
                   allow_hw_prng=True, mask=None):
    """Returns (out, lse) via the Pallas kernel.  Caller has checked
    feasibility with _plan.  `out` is in the input format; lse is
    [b, h, tq] f32.  `seed`: (1,) uint32 — the dropout stream seed
    (ignored when dropout_rate == 0).  A masked walk (`mask`, bhtd) tells
    the compile totals which tiles it visits (`attn_tiles_visited` of
    `attn_tiles_total` a head and sequence, monitor/flight.py)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, h, tq, d = _dims(q, fmt)
    hk, tk = _dims(k, fmt)[1:3]
    bh, group = b * h, h // hk
    drop_rate, inv_keep = _drop_params(dropout_rate)
    hw_prng = allow_hw_prng and _use_hw_prng(drop_rate, interpret)
    q_spec, kv_spec = _qkv_specs(fmt, h, "block", "full", block_q, block_k,
                                 tq, tk, d, group)
    if fmt == "bthd":
        args = [seed, q, k, v]
        in_specs = [_seed_spec(), q_spec, kv_spec, kv_spec]
        bias_q1 = bias_h = False
        if bias is not None:
            spec, bias_q1, bias_h = _bias_spec_bthd(
                bias, b, h, block_q, block_k, for_dkv=False)
            in_specs.append(spec)
            args.append(bias)
        kern = functools.partial(
            _fwd_kernel_bthd, scale=scale, n_head=h, block_q=block_q,
            block_k=block_k, causal=causal, seq_q=tq, seq_k=tk,
            causal_offset=tk - tq, bias_q1=bias_q1, bias_h=bias_h,
            drop_rate=drop_rate, inv_keep=inv_keep, hw_prng=hw_prng,
        )
        if bias is None:
            def kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref):
                return kern(seed_ref, q_ref, k_ref, v_ref, None, o_ref,
                            lse_ref)
        else:
            kernel = kern
        out, lse = pl.pallas_call(
            kernel,
            name="flash_bthd_fwd",
            grid=(b, tq // block_q),
            in_specs=in_specs,
            out_specs=[
                q_spec,
                pl.BlockSpec((None, h, block_q), lambda i, j: (i, 0, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, tq, h, d), q.dtype),
                jax.ShapeDtypeStruct((b, h, tq), jnp.float32),
            ],
            interpret=interpret,
        )(*args)
        return out, lse

    dv = v.shape[-1]
    o_spec, v_spec = _qkv_specs(fmt, h, "block", "full", block_q, block_k,
                                tq, tk, dv, group)
    args = [seed, q.reshape(bh, tq, d), k.reshape(bh // group, tk, d),
            v.reshape(bh // group, tk, dv)]
    in_specs = [_seed_spec(), q_spec, kv_spec, v_spec]
    if mask is not None:
        from ..monitor import flight

        visited, total = bd_tiles_visited(block_q, block_k, *mask)
        flight.note_compile_count("attn_tiles_visited", visited)
        flight.note_compile_count("attn_tiles_total", total)
    bias_q1 = False
    if bias is not None:
        spec, barg, bias_q1 = _bias_spec_and_arg(
            bias, b, h, tq, tk, block_q, block_k, for_dkv=False
        )
        in_specs.append(spec)
        args.append(barg)

    kern = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, seq_q=tq, seq_k=tk, causal_offset=tk - tq,
        bias_q1=bias_q1, drop_rate=drop_rate, inv_keep=inv_keep,
        hw_prng=hw_prng, mask=mask,
    )
    if bias is None:
        def kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref):
            return kern(seed_ref, q_ref, k_ref, v_ref, None, o_ref, lse_ref)
    else:
        kernel = kern

    out, lse = pl.pallas_call(
        kernel,
        name="flash_bhtd_fwd",
        grid=(bh, tq // block_q),
        in_specs=in_specs,
        out_specs=[
            o_spec,
            pl.BlockSpec((None, LSE_SUBLANES, block_q),
                         lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, LSE_SUBLANES, tq), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out.reshape(b, h, tq, dv), lse[:, 0, :].reshape(b, h, tq)


def _flash_backward(q, k, v, bias, seed, o, lse, g, scale, causal, block_q,
                    block_k, interpret, fmt="bhtd", dropout_rate=0.0,
                    allow_hw_prng=True, mask=None):
    """Returns (dq, dk, dv) via the two backward kernels, in the input
    format.  `lse` is [b, h, tq] f32; q/k/v/o/g are in `fmt`.

    Grouped-query attention (bhtd, k and v at h / group heads): the dkv
    walk runs once a QUERY head and writes that head's part of dK and dV;
    the group's parts are summed outside the kernel, in float32.  That
    costs 2 x b x h x tk x (d + d_v) elements written and read again (268
    MB a layer at 2 x 32 heads x 4096 x 128 in bfloat16, a third of a
    millisecond of HBM time); a kernel that looped over the group would
    read q and dO of every head once a key tile instead (1.2 GB)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, h, tq, d = _dims(q, fmt)
    tk = _dims(k, fmt)[2]
    bh = b * h
    causal_offset = tk - tq
    drop_rate, inv_keep = _drop_params(dropout_rate)
    hw_prng = allow_hw_prng and _use_hw_prng(drop_rate, interpret)

    if fmt == "bthd":
        bwd_params = _bthd_bwd_params(max(tq, tk), h, d, q.dtype.itemsize,
                                      causal)
        # delta[i] = rowsum(dO * O) -> [b, tq, h] -> [b, h, tq] (tiny f32)
        delta = jnp.sum(
            g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
        ).transpose(0, 2, 1)
        lse_spec_q = pl.BlockSpec((None, h, block_q), lambda i, j: (i, 0, j))
        lse_spec_full = pl.BlockSpec((None, h, tq), lambda i, j: (i, 0, 0))

        q_spec, kv_spec = _qkv_specs(fmt, h, "block", "full", block_q,
                                     block_k, tq, tk, d)
        in_specs = [_seed_spec(), q_spec, kv_spec, kv_spec, q_spec,
                    lse_spec_q, lse_spec_q]
        args = [seed, q, k, v, g, lse, delta]
        bias_q1 = bias_h = False
        if bias is not None:
            spec, bias_q1, bias_h = _bias_spec_bthd(
                bias, b, h, block_q, block_k, for_dkv=False)
            in_specs.insert(4, spec)
            args.insert(4, bias)
        dq_kern = functools.partial(
            _bwd_dq_kernel_bthd, scale=scale, n_head=h, block_q=block_q,
            block_k=block_k, causal=causal, seq_q=tq, seq_k=tk,
            causal_offset=causal_offset, bias_q1=bias_q1, bias_h=bias_h,
            drop_rate=drop_rate, inv_keep=inv_keep, hw_prng=hw_prng,
        )
        if bias is None:
            def dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, dq_ref):
                return dq_kern(seed_ref, q_ref, k_ref, v_ref, None, do_ref,
                               lse_ref, delta_ref, dq_ref)
        else:
            dq_kernel = dq_kern
        dq = pl.pallas_call(
            dq_kernel,
            name="flash_bthd_bwd_dq",
            grid=(b, tq // block_q),
            in_specs=in_specs,
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((b, tq, h, d), q.dtype),
            compiler_params=bwd_params,
            interpret=interpret,
        )(*args)

        qfull_spec, kblock_spec = _qkv_specs(fmt, h, "full", "block",
                                             block_q, block_k, tq, tk, d)
        in_specs = [_seed_spec(), qfull_spec, kblock_spec, kblock_spec,
                    qfull_spec, lse_spec_full, lse_spec_full]
        args = [seed, q, k, v, g, lse, delta]
        bias_q1 = bias_h = False
        if bias is not None:
            spec, bias_q1, bias_h = _bias_spec_bthd(
                bias, b, h, block_q, block_k, for_dkv=True)
            in_specs.insert(4, spec)
            args.insert(4, bias)
        dkv_kern = functools.partial(
            _bwd_dkv_kernel_bthd, scale=scale, n_head=h, block_q=block_q,
            block_k=block_k, causal=causal, seq_q=tq, seq_k=tk,
            causal_offset=causal_offset, bias_q1=bias_q1, bias_h=bias_h,
            drop_rate=drop_rate, inv_keep=inv_keep, hw_prng=hw_prng,
        )
        if bias is None:
            def dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                           delta_ref, dk_ref, dv_ref):
                return dkv_kern(seed_ref, q_ref, k_ref, v_ref, None, do_ref,
                                lse_ref, delta_ref, dk_ref, dv_ref)
        else:
            dkv_kernel = dkv_kern
        dk, dv = pl.pallas_call(
            dkv_kernel,
            name="flash_bthd_bwd_dkv",
            grid=(b, tk // block_k),
            in_specs=in_specs,
            out_specs=[kblock_spec, kblock_spec],
            out_shape=[
                jax.ShapeDtypeStruct((b, tk, h, d), k.dtype),
                jax.ShapeDtypeStruct((b, tk, h, d), v.dtype),
            ],
            compiler_params=bwd_params,
            interpret=interpret,
        )(*args)
        return dq, dk, dv

    dv_ = v.shape[-1]  # the value head size: v, o, g and dv carry it
    hk = k.shape[1]
    group = h // hk
    args3 = [q.reshape(bh, tq, d), k.reshape(b * hk, tk, d),
             v.reshape(b * hk, tk, dv_), g.reshape(bh, tq, dv_)]
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).reshape(bh, 1, tq)
    # lse/delta ride in sublane-replicated (bh, 8, tq) tiles (see above)
    lse3 = jnp.broadcast_to(
        lse.reshape(bh, 1, tq), (bh, LSE_SUBLANES, tq)
    )
    delta3 = jnp.broadcast_to(delta, (bh, LSE_SUBLANES, tq))

    _lse_spec_q = pl.BlockSpec(
        (None, LSE_SUBLANES, block_q), lambda i, j: (i, 0, j)
    )
    _lse_spec_full = pl.BlockSpec(
        (None, LSE_SUBLANES, tq), lambda i, j: (i, 0, 0)
    )
    # ---- dQ: grid over q blocks -----------------------------------------
    q_spec, kv_spec = _qkv_specs(fmt, h, "block", "full", block_q, block_k,
                                 tq, tk, d, group)
    g_spec, v_spec = _qkv_specs(fmt, h, "block", "full", block_q, block_k,
                                tq, tk, dv_, group)
    in_specs = [_seed_spec(), q_spec, kv_spec, v_spec, g_spec,
                _lse_spec_q, _lse_spec_q]
    args = [seed, args3[0], args3[1], args3[2], args3[3], lse3, delta3]
    bias_q1 = False
    if bias is not None:
        spec, barg, bias_q1 = _bias_spec_and_arg(
            bias, b, h, tq, tk, block_q, block_k, for_dkv=False
        )
        in_specs.insert(4, spec)
        args.insert(4, barg)

    dq_kern = functools.partial(
        _bwd_dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, seq_q=tq, seq_k=tk, causal_offset=causal_offset,
        bias_q1=bias_q1, drop_rate=drop_rate, inv_keep=inv_keep,
        hw_prng=hw_prng, mask=mask,
    )
    if bias is None:
        def dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref):
            return dq_kern(seed_ref, q_ref, k_ref, v_ref, None, do_ref,
                           lse_ref, delta_ref, dq_ref)
    else:
        dq_kernel = dq_kern

    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bhtd_bwd_dq",
        grid=(bh, tq // block_q),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        interpret=interpret,
    )(*args)

    # ---- dK/dV: grid over kv blocks -------------------------------------
    qfull_spec, kblock_spec = _qkv_specs(fmt, h, "full", "block", block_q,
                                         block_k, tq, tk, d, group)
    gfull_spec, vblock_spec = _qkv_specs(fmt, h, "full", "block", block_q,
                                         block_k, tq, tk, dv_, group)
    # dk, dv: one row a QUERY head (the group's parts are summed below)
    _, dk_spec = _qkv_specs(fmt, h, "full", "block", block_q, block_k, tq,
                            tk, d)
    _, dv_spec = _qkv_specs(fmt, h, "full", "block", block_q, block_k, tq,
                            tk, dv_)
    in_specs = [_seed_spec(), qfull_spec, kblock_spec, vblock_spec,
                gfull_spec, _lse_spec_full, _lse_spec_full]
    args = [seed, args3[0], args3[1], args3[2], args3[3], lse3, delta3]
    bias_q1 = False
    if bias is not None:
        spec, barg, bias_q1 = _bias_spec_and_arg(
            bias, b, h, tq, tk, block_q, block_k, for_dkv=True
        )
        in_specs.insert(4, spec)
        args.insert(4, barg)

    dkv_kern = functools.partial(
        _bwd_dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, seq_q=tq, seq_k=tk, causal_offset=causal_offset,
        bias_q1=bias_q1, drop_rate=drop_rate, inv_keep=inv_keep,
        hw_prng=hw_prng, mask=mask,
    )
    if bias is None:
        def dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref):
            return dkv_kern(seed_ref, q_ref, k_ref, v_ref, None, do_ref,
                            lse_ref, delta_ref, dk_ref, dv_ref)
    else:
        dkv_kernel = dkv_kern

    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bhtd_bwd_dkv",
        grid=(bh, tk // block_k),
        in_specs=in_specs,
        out_specs=[dk_spec, dv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, dv_), v.dtype),
        ],
        interpret=interpret,
    )(*args)

    def over_group(part, width):
        if group == 1:
            return part.reshape(b, h, tk, width)
        return jnp.sum(part.reshape(b, hk, group, tk, width), axis=2,
                       dtype=jnp.float32).astype(part.dtype)

    return dq.reshape(b, h, tq, d), over_group(dk, d), over_group(dv, dv_)


def _dbias_xla(q, k, bias, lse, g, v, o, scale, causal, dropout_rate=0.0,
               dropout_seed=None, mask=None):
    """Bias cotangent via plain-XLA recompute (dS reduced over broadcast
    dims).  O(T^2) memory — but attention biases are almost always
    stop-gradient masks, and then XLA dead-code-eliminates this whole
    expression; it only materializes for genuinely trainable biases."""
    import jax
    import jax.numpy as jnp

    k, v = _repeat_kv(q, k, v)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    logits = logits + bias.astype(jnp.float32)
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        logits = jnp.where(jnp.tril(jnp.ones((tq, tk), bool), tk - tq),
                           logits, -1e30)
    if mask is not None:
        logits = jnp.where(_bd_plane(*logits.shape[-2:], mask), logits,
                           -1e30)
    p = jnp.exp(logits - lse[..., None])
    dp = jnp.einsum("bhqd,bhkd->bhqk", g.astype(jnp.float32),
                    v.astype(jnp.float32))
    if dropout_rate:
        from . import hash_rng

        keep = hash_rng.keep_mask_attn(dropout_seed, dp.shape, dropout_rate)
        dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    ds = p * (dp - delta[..., None])
    # reduce over dims the bias broadcast along
    axes = tuple(
        i for i, (bd, fd) in enumerate(zip(bias.shape, ds.shape)) if bd != fd
    )
    if axes:
        ds = jnp.sum(ds, axis=axes, keepdims=True)
    return ds.astype(bias.dtype)


def flash_attention(q, k, v, bias=None, scale=1.0, causal=False,
                    block_q=512, block_k=512, interpret=None, fmt="bhtd",
                    dropout_rate=0.0, dropout_seed=None,
                    trainable_bias=True, mask=None):
    """q,k,v: [B, H, T, D] (fmt="bhtd", default) or [B, T, H, D]
    (fmt="bthd"); bias: broadcastable [B, H, Tq, Tk] or None.  Returns the
    context in the same format as q.

    fmt="bhtd" also takes k and v at a head count that divides q's
    (grouped-query attention) and `mask` = (block_length, clean_offset),
    the block-diffusion mask over [noisy ; clean] rows (`_bd_visible`),
    computed in the kernels, which skip the tiles it empties (`_plan`).

    fmt="bthd" is the TPU-preferred calling convention: it is the free
    reshape of the projection output [B, T, H*D], so no split/merge-head
    transpose exists anywhere in the program and XLA inserts no relayout
    copies at the custom-call boundary (round-3 profile: ~5.5 GB/step of
    such copies at the bhtd boundary).

    dropout_rate > 0 applies dropout to the attention WEIGHTS *inside* the
    kernels (the reference's dropout-on-softmax semantics,
    transformer_model.py:44 + dropout_op.cc) — the [Tq, Tk] mask never
    exists in HBM.  The mask bit for element (b,h,q,k) is the counter-based
    hash of kernels/hash_rng.py over (dropout_seed, global index): forward
    and backward kernels regenerate it independently, and the pure-XLA
    fallback produces the identical mask.  `dropout_seed`: (1,) uint32
    array (see hash_rng.seed_from_key), traced — one per (step, site).

    Fully differentiable with Pallas kernels on BOTH passes: forward saves
    only (out, logsumexp); backward recomputes probability blocks in-kernel
    (FlashAttention-2), so neither pass materializes the [Tq, Tk] matrix.

    trainable_bias (default True — the SAFE setting): the bias cotangent
    is computed by an XLA recompute (_dbias_xla) that regenerates the
    dropout mask with the HASH generator, so with dropout + a bias whose
    gradient is consumed the kernels must use the hash mask too, or
    dbias would be masked differently than the forward actually was.
    With trainable_bias=True and dropout on, the TPU hardware-PRNG fast
    path is therefore disabled for this call.  Pass
    trainable_bias=False ONLY when the bias is a stop-gradient mask
    (padding/causal biases — then XLA dead-code-eliminates the dbias
    expression and its mask mismatch is unobservable); the
    fused_attention op lowering derives this automatically from the
    bias var's stop_gradient flag."""
    return flash_attention_fwd(
        q, k, v, bias, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, fmt=fmt,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        trainable_bias=trainable_bias, mask=mask)[0]


def flash_attention_fwd(q, k, v, bias=None, **options):
    """flash_attention (and its options) with the residual its kernel
    writes anyway: (out, lse), lse [b, h, tq] f32 — None where the plan or
    the bias's shape sent the call to the XLA reference.  Differentiable
    in `out` (the custom VJP of the kernel pair); a caller that keeps
    (out, lse) hands them to flash_attention_bwd instead, and the forward
    kernel runs once (ops/fused_ops.py)."""
    seed, fwd, bwd, norm = _flash_kernels(q, k, v, bias, **options)
    if bwd is None:
        return fwd(q, k, v, bias, seed), None
    return _vjp_of_pair(fwd, bwd, 0)(q, k, v, norm(bias), seed)


def flash_attention_bwd(q, k, v, bias, out, lse, g, **options):
    """(dq, dk, dv, dbias) from flash_attention_fwd's (out, lse) and the
    cotangent g of out, under the same options: the backward kernels
    alone, the body of flash_attention's VJP rule.  dbias is None unless
    a bias is given and trainable_bias holds.  None where the plan
    rejects the operands (the forward then gave no lse either)."""
    seed, _, bwd, norm = _flash_kernels(q, k, v, bias, **options)
    if bwd is None:
        return None
    return _bwd_with_dbias(
        bwd, norm, bias, options.get("trainable_bias", True),
        q, k, v, norm(bias), seed, out, lse, g)


def _bwd_with_dbias(bwd, norm, bias, trainable_bias, *operands):
    """A kernel pair's bwd on `operands`, the bias's cotangent (for a
    trainable bias only) pulled back through `norm` to the caller's own
    bias."""
    import jax

    want_dbias = bias is not None and trainable_bias
    *grads, dbias = bwd(*operands, want_dbias)
    if want_dbias:
        dbias, = jax.vjp(norm, bias)[1](dbias)
    return (*grads, dbias)


def _dropout_seed_arg(dropout_rate, dropout_seed, mask_plane, what):
    """The (1,) uint32 stream seed the kernels take (zeros without
    dropout).  The per-head mask plane is keyed by the uint32 index
    q*Tk + k: past 2^32 elements it would wrap and CORRELATE mask bits
    across rows — refuse rather than silently degrade."""
    import jax.numpy as jnp

    if not dropout_rate:
        return jnp.zeros((1,), jnp.uint32)
    if dropout_seed is None:
        raise ValueError(f"{what}: dropout_rate > 0 needs dropout_seed")
    tq, tk = mask_plane
    if tq * tk > 2 ** 32:
        raise ValueError(
            f"{what}: weights-dropout mask plane Tq*Tk = {tq}*{tk} > 2^32 "
            "would wrap the uint32 hash index and correlate mask bits; "
            "drop out the attention OUTPUT (a [T, D] site) instead of the "
            "weights at this length")
    return jnp.reshape(dropout_seed, (1,)).astype(jnp.uint32)


def _bias_norm(bias, b, h, tq, tk):
    """bias -> the [Bb, Hb, Tqb, Tk] operand the kernels slice (each of
    Bb, Hb, Tqb is 1 or full), as a function, so that a gradient can be
    pulled back through it; None where the shape does not broadcast."""
    import jax.numpy as jnp

    if bias is None:
        return lambda bias: None
    bb, hb, tqb, tkb = shape = (1,) * (4 - bias.ndim) + tuple(bias.shape)
    if (bb not in (1, b) or hb not in (1, h) or tqb not in (1, tq)
            or tkb not in (1, tk)):
        return None

    def norm(bias):
        bias = jnp.reshape(bias, shape)
        # key-broadcast biases can't be block-sliced along Tk; materialize
        # the (cheap, [.., .., 1]-thin) broadcast up front
        return jnp.broadcast_to(bias, (bb, hb, tqb, tk)) if tkb == 1 \
            else bias

    return norm


def _vjp_of_pair(fwd, bwd, kept):
    """The jax.custom_vjp of a kernel pair.  Primal: fwd(*operands), the
    result first and then the residuals the kernel writes anyway;
    differentiable in the result alone.  Rule: bwd(*operands,
    *outputs[kept:], the result's cotangent, True) — True: with the
    bias's cotangent, which XLA drops where nothing reads it.  Operands
    are arrays, None for an absent bias, and the uint32 seed last."""
    import numpy as np

    import jax

    @jax.custom_vjp
    def attn(*operands):
        return fwd(*operands)

    def attn_fwd(*operands):
        outs = fwd(*operands)
        return outs, (operands, outs[kept:])

    def attn_bwd(res, cotangents):
        operands, saved = res
        grads = bwd(*operands, *saved, cotangents[0], True)
        return (*grads,
                np.zeros(operands[-1].shape, dtype=jax.dtypes.float0))

    attn.defvjp(attn_fwd, attn_bwd)
    return attn


def _flash_kernels(q, k, v, bias, scale=1.0, causal=False, block_q=512,
                   block_k=512, interpret=None, fmt="bhtd",
                   dropout_rate=0.0, dropout_seed=None, trainable_bias=True,
                   mask=None):
    """(seed, fwd, bwd, norm) for one flash_attention site.  On the kernel
    route fwd(q, k, v, bias, seed) -> (out, lse) and bwd(q, k, v, bias,
    seed, out, lse, g, want_dbias) -> (dq, dk, dv, dbias or None), both on
    bias = norm(the caller's bias).  Where the plan (a pure function of
    shapes, platform and placement) or the bias's shape rejects the
    operands, bwd is None and fwd is the XLA reference -> out."""
    if fmt not in ("bhtd", "bthd"):
        raise ValueError(f"flash_attention: unknown fmt {fmt!r}")
    if mask is not None and causal:
        raise ValueError("flash_attention: a block-diffusion mask and "
                         "causal=True are two masks; give one")
    b, h, tq, _ = _dims(q, fmt)
    tk = _dims(k, fmt)[2]
    seed = _dropout_seed_arg(dropout_rate, dropout_seed, (tq, tk),
                             "flash_attention")
    ok, bq, bk, interp = _plan(q, k, block_q, block_k, interpret, fmt, v,
                               mask)
    norm = _bias_norm(bias, b, h, tq, tk) if ok else None
    if norm is None:
        ref = _reference_bthd if fmt == "bthd" else reference_attention
        return seed, (lambda q, k, v, bias, seed: ref(
            q, k, v, bias, scale, causal, dropout_rate, seed,
            mask)), None, None
    # dropout + consumed bias gradient: the dbias recompute hashes its
    # mask, so the kernels must hash too (see trainable_bias docstring);
    # without a bias the hardware-PRNG path is fully enabled
    allow_hw = not (dropout_rate and trainable_bias and bias is not None)

    def fwd(q, k, v, bias, seed):
        return _flash_forward(q, k, v, bias, seed, scale, causal, bq, bk,
                              interp, fmt, dropout_rate,
                              allow_hw_prng=allow_hw, mask=mask)

    def bwd(q, k, v, bias, seed, out, lse, g, want_dbias):
        dq, dk, dv = _flash_backward(q, k, v, bias, seed, out, lse, g,
                                     scale, causal, bq, bk, interp, fmt,
                                     dropout_rate, allow_hw_prng=allow_hw,
                                     mask=mask)
        if bias is None or not want_dbias:
            return dq, dk, dv, None
        # _dbias_xla is written for bhtd; the transpose is an XLA view
        # feeding an einsum (fused), and trainable biases are rare
        t = (lambda a: a.transpose(0, 2, 1, 3)) if fmt == "bthd" \
            else (lambda a: a)
        return dq, dk, dv, _dbias_xla(t(q), t(k), bias, lse, t(g), t(v),
                                      t(out), scale, causal, dropout_rate,
                                      seed, mask)

    return seed, fwd, bwd, norm


# ---------------------------------------------------------------------------
# Self-attention from the residual stream ("qkv" attention): the q, k, v and
# output projections as XLA dots round the bthd flash kernels.
#
# Layout contract:
#   x       [b, t, d_model]          — the residual-stream activation
#   w_qkv   [d_model, 3*h*dh]        — the fc-packed weight (split order
#                                      q | k | v along the output dim, the
#                                      exact layers.fc + split layout, so
#                                      checkpoints interop bit-for-bit)
#   w_out   [h*dh, d_model]          — the output projection
#   y       [b, t, d_model]
# Every dot reads or writes [b, t, h, dh], the layout the bthd kernels take,
# through free reshapes of the weights ([dm, 3, h, dh] / [h, dh, dm]): no
# [b, t, 3*h*dh] array is made, sliced or concatenated in either direction
# (that glue was 6-14 % of a site's backward, PERF.md PR 28 (1)).  q, k, v,
# the context and the logsumexp are the forward's residuals: the backward
# runs the bthd backward kernels on them between the projections' backward
# dots and recomputes nothing.
#
# Until PR 30 a Pallas kernel (`fused_qkv_fwd`) computed the projections tile
# by tile inside the attention walk, so that q, k, v never reached HBM.  It
# ran at a third of the bf16 peak (float32 dots on widened tiles, 64 output
# lanes a head, unrolled over the heads), its backward had to recompute
# q, k, v, and the composition below beat it by 6 % of BERT-base's step
# (PERF.md PR 28 (3), PR 30): deleted in PR 30 with its plan and its flag.
# ---------------------------------------------------------------------------


def _qkv_weight_views(w_qkv, w_out, n_head):
    """The packed weights as the per-head operands of the projection
    einsums: w_qkv as [dm, 3, h, dh], w_out as [h, dh, dm_out]."""
    dm = w_qkv.shape[0]
    if w_qkv.shape[1] % (3 * n_head):
        raise ValueError(
            f"flash_qkv_attention: packed dim {w_qkv.shape[1]} not "
            f"divisible by 3*n_head={3 * n_head}")
    dh = w_qkv.shape[1] // (3 * n_head)
    return (w_qkv.reshape(dm, 3, n_head, dh),
            w_out.reshape(n_head, dh, w_out.shape[1]))


def flash_qkv_attention(x, w_qkv, w_out, bias=None, n_head=1, **options):
    """Self-attention from the residual stream: x [b, t, d_model], w_qkv
    [d_model, 3*h*dh] (the layers.fc packed layout), w_out [h*dh,
    d_model] -> [b, t, d_model].  `options` (scale, causal, block_q,
    block_k, interpret, dropout_rate, dropout_seed, trainable_bias) are
    flash_attention's; the attention runs in fmt="bthd".  Differentiable:
    the projections by autodiff, the attention by its kernel pair."""
    return flash_qkv_attention_fwd(x, w_qkv, w_out, bias, n_head,
                                   **options)[0]


def flash_qkv_attention_fwd(x, w_qkv, w_out, bias=None, n_head=1,
                            **options):
    """flash_qkv_attention with what its backward needs: (y, q, k, v, ctx,
    lse), q / k / v / ctx [b, t, h, dh] in x.dtype and lse [b, h, t] f32 —
    lse None where flash_attention_fwd ran its XLA reference.  A caller
    that keeps them hands them to flash_qkv_attention_bwd
    (ops/fused_ops.py)."""
    import jax.numpy as jnp

    w4, wo3 = _qkv_weight_views(w_qkv, w_out, n_head)
    q, k, v = (jnp.einsum("btm,mhd->bthd", x, w4[:, i]).astype(x.dtype)
               for i in range(3))
    ctx, lse = flash_attention_fwd(q, k, v, bias, fmt="bthd", **options)
    y = jnp.einsum("bthd,hdm->btm", ctx, wo3).astype(x.dtype)
    return y, q, k, v, ctx, lse


def flash_qkv_attention_bwd(x, w_qkv, w_out, bias, q, k, v, ctx, lse, g,
                            n_head=1, **options):
    """(dx, dw_qkv, dw_out, dbias) from flash_qkv_attention_fwd's (q, k, v,
    ctx, lse) and the cotangent g of y, under the same options: the bthd
    backward kernels between the projections' backward dots.  dx is a
    float32 sum of three, dw_qkv is stacked at weight size.  dbias is None
    unless a bias is given and trainable_bias holds.  None where the plan
    rejects the operands (the forward then gave no lse either)."""
    import jax.numpy as jnp

    w4, wo3 = _qkv_weight_views(w_qkv, w_out, n_head)
    dctx = jnp.einsum("btm,hdm->bthd", g, wo3).astype(x.dtype)
    grads = flash_attention_bwd(q, k, v, bias, ctx, lse, dctx, fmt="bthd",
                                **options)
    if grads is None:
        return None
    *dqkv, dbias = grads
    dw_out = jnp.einsum("bthd,btm->hdm", ctx, g).reshape(
        w_out.shape).astype(w_out.dtype)
    dx = sum(jnp.einsum("bthd,mhd->btm", d, w4[:, i],
                        preferred_element_type=jnp.float32)
             for i, d in enumerate(dqkv)).astype(x.dtype)
    dw_qkv = jnp.stack(
        [jnp.einsum("btm,bthd->mhd", x, d) for d in dqkv],
        axis=1).reshape(w_qkv.shape).astype(w_qkv.dtype)
    return dx, dw_qkv, dw_out, dbias
