"""Fused decode megastep: ONE Pallas launch per decoder layer per token.

The per-token decode program of the generation tier is ~60 small ops for
a 6-layer model (per layer: qkv projection, cache-row write, flash
decode, two more projections, cross attention, feed-forward, three layer
norms) and the PR-16 cost model shows it launch-bound at batch 1 — 97.9%
of the predicted step is dispatch.  This module collapses one WHOLE
decoder layer into a single kernel, so the per-token program becomes
n_layer megastep launches (+ embedding and sampling) instead of ~10 ops
per layer, and q/k/v and the attention context never round-trip HBM:

  * qkv projection of the incoming [b, 1, d_model] token is computed
    in-kernel (per-head column slices of the fused attn_qkv_w weight —
    the PR-8 fused-projection recipe applied at decode time);
  * the fresh k/v row is DMA'd from VMEM scratch straight into the
    HBM-resident ring cache at the runtime counter, through the ALIASED
    output buffer (`input_output_aliases`, the embedding-tier in-place
    recipe) gated on the lane's active mask;
  * the single-query online-softmax walk then streams the length-bounded
    cache prefix exactly like kernels/decode_attention.py (scalar-
    prefetched per-sequence lengths, start-all-then-wait-all block DMA,
    [t,h,d]->[h,t,d] in-register relayout, f32 running max/sum) — the
    just-written row is part of the walk because the write lands before
    the first block fetch;
  * output projection, residual + layer-norm epilogue, the cached
    cross-attention walk, and (VMEM budget permitting, _megastep_plan
    mode "fused-ffn") the position-wise feed-forward + final layer norm
    all happen in the same launch; when the FFN weights do not fit the
    budget next to the attention working set, the FFN+norm runs as a
    SECOND launch per layer (_ffn_kernel) — still 2 launches instead of
    ~10 ops.

Off-contract shapes (plan gate: d_model/d_inner lane alignment, head
sublane alignment, d_head % 64, block divisibility, VMEM budget) and
off-TPU runs fall back to `reference_decode_step` — a pure-XLA
composition that replicates the unfused op chain (ops/math_ops.py
lower_mul reshape-matmul, ops/generation_ops.py kv_cache_update +
decode_attention, ops/nn_ops.py layer_norm_core) op for op, so the
fused_decode_step op is numerically identical to the composition it
replaces on every backend.

Forward-only by contract (generation never differentiates through the
cache); the op registration in ops/generation_ops.py is no_grad and
preserves the cache vars' read-then-write donation contract verbatim.
"""

from __future__ import annotations

import collections
import functools

# the scoped-VMEM limit every megastep launch requests from Mosaic and the
# share of it a plan's counted working set (_megastep_vmem_bytes) may
# claim: the decode kernels' pair
from .decode_attention import (_VMEM_BUDGET, _VMEM_LIMIT,
                               _padded_head_tile)

MegastepPlan = collections.namedtuple(
    "MegastepPlan", ["ok", "fuse_ffn", "block_t", "cross_block_t",
                     "interpret"])


def _snap_block(block_t, max_t):
    """Snap the walk block down to a divisor of max_t (the ring buffers
    are 128-row quanta, so this terminates at a sane power of two)."""
    block_t = min(block_t, max_t)
    while block_t > 8 and max_t % block_t:
        block_t //= 2
    return block_t


def _itemsize(dtype):
    import numpy as np

    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, str(dtype))).itemsize


def _megastep_vmem_bytes(d_model, n_head, d_head, d_inner, bt, cbt,
                         esize):
    """(attention bytes, ffn bytes) of one megastep launch, counted the
    way Mosaic allocates VMEM: the resident weights enter as whole-array
    blocks, which the pipeline holds ONCE (an index map that never moves
    is not double-buffered — a 12 MB whole-array block compiles under
    the 16 MiB default scope, libtpu 0.0.34); every [t, h, dh] walk
    scratch pads h to the dtype's sublane quantum and dh to 128 lanes;
    each walk keeps a promoted-f32 and a transposed copy of its k and v
    tile plus two score planes."""
    rows, lanes = _padded_head_tile(n_head, d_head, esize)
    hd = n_head * d_head
    tile = rows * lanes
    attn = (
        6 * hd * d_model * esize                 # wqkv + wout + wcq + wcout
        + 2 * (bt + cbt) * tile * esize          # k/v walk scratch
        + 2 * 2 * max(bt, cbt) * tile * 4        # f32 + transposed copies
        + 2 * rows * max(bt, cbt) * 4            # score planes
        + d_model * lanes * 4                    # one promoted weight slab
    )
    ffn = 2 * d_model * d_inner * esize + d_inner * 4
    return attn, ffn


def _megastep_plan(d_model, n_head, d_head, d_inner, max_t, cross_t,
                   dtype, block_t=256, interpret=None):
    """Static feasibility gate; returns a MegastepPlan.

    Contract (audited statically by analysis/kernel_lint.py):
      * d_model % 128 == 0 and d_inner % 128 == 0 (both ride the lane
        dim of the projection tiles);
      * d_head % 128 == 0 compiled (% 64 interpreted) and n_head % 8
        == 0 for f32 / % 16 narrower (the cache walk's [h, t, d]
        in-register view).  The walk DMAs cache rows by hand, and
        Mosaic refuses to slice an HBM ref whose minor dim is not a
        multiple of 128 ("Slice shape along dimension 3 must be aligned
        to tiling (128), but is 64", libtpu 0.0.34) — so transformer-
        base (d_head 64) runs the XLA composition on the chip;
      * max_t % block_t == 0 and cross_t % cross_block_t == 0 with both
        blocks % 8 == 0 (the length-masked tail is the only partial
        block);
      * the launch's counted working set (_megastep_vmem_bytes) fits
        _VMEM_BUDGET of the requested _VMEM_LIMIT; the FFN weights join
        the same launch only if they ALSO fit (fuse_ffn), otherwise the
        plan keeps a second per-layer launch.
    Off-contract shapes return ok=False and the caller runs the XLA
    composition fallback — numerically identical.
    """
    from .placement import resolve

    compiled, interpret = resolve(interpret)
    esize = _itemsize(dtype)
    bt = _snap_block(block_t, max_t)
    cbt = _snap_block(block_t, cross_t)
    sublane = 8 if esize >= 4 else 16
    aligned = (
        (compiled or interpret)
        and d_model % 128 == 0
        and d_inner % 128 == 0
        and d_head % (64 if interpret else 128) == 0
        and n_head % sublane == 0
        and max_t % bt == 0 and bt % 8 == 0
        and cross_t % cbt == 0 and cbt % 8 == 0
    )
    attn_bytes, ffn_bytes = _megastep_vmem_bytes(
        d_model, n_head, d_head, d_inner, bt, cbt, esize)
    ok = aligned and attn_bytes <= _VMEM_BUDGET and ffn_bytes <= _VMEM_BUDGET
    fuse_ffn = ok and attn_bytes + ffn_bytes <= _VMEM_BUDGET
    return MegastepPlan(ok, fuse_ffn, bt, cbt, interpret)


# ---------------------------------------------------------------------------
# pure-XLA fallback: the unfused composition, op for op
# ---------------------------------------------------------------------------


def reference_decode_step(x, wqkv, wout, ln1_scale, ln1_bias, wcq, wcout,
                          ln2_scale, ln2_bias, ffn_in_w, ffn_in_b,
                          ffn_out_w, ffn_out_b, ln3_scale, ln3_bias,
                          cache_k, cache_v, cross_k, cross_v, pos,
                          lengths, cross_lengths, active=None, *, layer,
                          n_head, scale, eps=1e-5):
    """The composed decoder step as ONE jax function — the exact op
    chain cached_decoder_step emits with FLAGS_fused_decode_step off
    (lower_mul reshape-matmul, jnp.split thirds, the kv_cache_update
    write with its active keep-mask, FLAGS.flash_decode-routed decode
    attention, layer_norm_core epilogues) so flag-on/off programs stay
    numerically identical on every backend.  Returns
    (out [b, 1, d_model], cache_k', cache_v')."""
    import jax
    import jax.numpy as jnp

    from ..flags import FLAGS
    from . import decode_attention as kda

    b = x.shape[0]
    h = n_head
    dh = cache_k.shape[-1]
    hd = h * dh

    def mul(a, w):
        # ops/math_ops.py lower_mul with x_num_col_dims=2
        a2 = a.reshape((b * 1, -1))
        return (a2 @ w).reshape((b, 1, w.shape[-1]))

    def layer_norm(y, s, bias):
        # ops/nn_ops.py layer_norm_core, begin_norm_axis=2
        stat = jnp.float32 if y.dtype == jnp.bfloat16 else y.dtype
        ys = y.astype(stat)
        mean = jnp.mean(ys, axis=2, keepdims=True)
        var = jnp.mean(jnp.square(ys - mean), axis=2, keepdims=True)
        out = (ys - mean) * jax.lax.rsqrt(var + eps)
        out = out * s.reshape((1, 1, -1)).astype(stat)
        out = out + bias.reshape((1, 1, -1)).astype(stat)
        return out.astype(y.dtype)

    def write(cache, new):
        # ops/generation_ops.py lower_kv_cache_update, verbatim
        pos32 = pos.reshape(-1).astype(jnp.int32)

        def upd(c, n, p):
            return jax.lax.dynamic_update_slice(
                c, n.astype(c.dtype), (p, 0, 0))

        updated = jax.vmap(upd)(cache[layer], new.reshape(b, 1, h, dh),
                                pos32)
        if active is not None:
            keep = active.reshape(-1).astype(jnp.bool_)
            updated = jnp.where(keep[:, None, None, None], updated,
                                cache[layer])
        return cache.at[layer].set(updated)

    def attend(q, kc, vc, lens):
        # ops/generation_ops.py lower_decode_attention routing
        q3 = q.reshape(b, h, dh)
        lens32 = lens.reshape(-1).astype(jnp.int32)
        if FLAGS.flash_decode:
            o = kda.flash_decode(q3, kc, vc, lens32, scale=scale)
        else:
            o = kda.reference_decode(q3, kc, vc, lens32, scale=scale)
        return o.reshape(b, 1, h, dh)

    qkv = mul(x, wqkv)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    cache_k = write(cache_k, k)
    cache_v = write(cache_v, v)
    ctx = attend(q, cache_k[layer], cache_v[layer], lengths)
    attn_out = mul(ctx.reshape(b, 1, hd), wout)
    x = layer_norm(x + attn_out, ln1_scale, ln1_bias)
    cq = mul(x, wcq)
    cctx = attend(cq, cross_k[layer], cross_v[layer], cross_lengths)
    cross_out = mul(cctx.reshape(b, 1, hd), wcout)
    x = layer_norm(x + cross_out, ln2_scale, ln2_bias)
    hid = jax.nn.relu(mul(x, ffn_in_w) + ffn_in_b.reshape((1, 1, -1)))
    ffd = mul(hid, ffn_out_w) + ffn_out_b.reshape((1, 1, -1))
    x = layer_norm(x + ffd, ln3_scale, ln3_bias)
    return x, cache_k, cache_v


# ---------------------------------------------------------------------------
# the megastep kernel
# ---------------------------------------------------------------------------


def _megastep_kernel(pos_ref, lens_ref, clens_ref, act_ref, *refs, layer,
                     scale, eps, block_t, cross_block_t, n_head, d_head,
                     d_model, fuse_ffn):
    """One grid step = one sequence: project qkv, DMA the fresh k/v row
    into the aliased HBM cache at the runtime counter, walk the
    length-bounded cache prefix (online softmax), project + normalize,
    repeat the walk against the cross cache, and (fuse_ffn) finish the
    layer's feed-forward — all without leaving the core."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    dh = d_head
    hd = n_head * d_head
    n_w = 15 if fuse_ffn else 9  # x + weight refs
    x_ref = refs[0]
    (wqkv, wout, ln1s, ln1b, wcq, wcout, ln2s, ln2b) = refs[1:9]
    ffn_refs = refs[9:n_w]
    # refs[n_w:n_w + 2] are the ALIASED cache inputs — reads and the
    # row write go through the output refs (same buffers)
    xk_ref, xv_ref = refs[n_w + 2:n_w + 4]
    o_ref, cko_ref, cvo_ref = refs[n_w + 4:n_w + 7]
    (q_scr, krow, vrow, kblk, vblk, ckblk, cvblk,
     sem_w, sem_k, sem_v) = refs[n_w + 7:]

    i = pl.program_id(0)
    p = pos_ref[i]
    length = lens_ref[i]
    clen = clens_ref[i]
    act = act_ref[i]

    x0 = x_ref[0].astype(f32)  # [1, d_model]

    # fused qkv projection, per-head column slices of the packed weight
    # (columns [0, hd) are q — the jnp.split third the composition
    # takes).  q lands pre-scaled in f32 scratch; the k/v row lands in
    # cache-dtype scratch, the DMA source for the in-place row write.
    for hi in range(n_head):
        q_scr[hi, :] = jnp.dot(
            x0, wqkv[:, hi * dh:(hi + 1) * dh].astype(f32),
            preferred_element_type=f32)[0] * scale
        krow[0, hi, :] = jnp.dot(
            x0, wqkv[:, hd + hi * dh:hd + (hi + 1) * dh].astype(f32),
            preferred_element_type=f32)[0].astype(krow.dtype)
        vrow[0, hi, :] = jnp.dot(
            x0, wqkv[:, 2 * hd + hi * dh:2 * hd + (hi + 1) * dh]
            .astype(f32),
            preferred_element_type=f32)[0].astype(vrow.dtype)

    # in-place cache row write at the runtime counter, through the
    # aliased output buffer; inactive lanes keep their rows (the
    # kv_cache_update active mask).  The walk below reads the same
    # buffer, so its window includes this row (lengths == pos + 1 for
    # active lanes).
    @pl.when(act != 0)
    def _write_row():
        wk = pltpu.make_async_copy(
            krow, cko_ref.at[layer, i, pl.ds(p, 1)], sem_w)
        wv = pltpu.make_async_copy(
            vrow, cvo_ref.at[layer, i, pl.ds(p, 1)], sem_w)
        wk.start()
        wv.start()
        wk.wait()
        wv.wait()

    def walk(src_k, src_v, kscr, vscr, n_valid, blk):
        """decode_attention's online-softmax cache walk against this
        sequence's [max_t, h, dh] slice; q rides q_scr (pre-scaled)."""
        q = q_scr[...]
        m0 = jnp.full((n_head,), -jnp.inf, f32)
        l0 = jnp.zeros((n_head,), f32)
        acc0 = jnp.zeros((n_head, d_head), f32)
        n_blk = jax.lax.div(n_valid + (blk - 1), blk)

        def body(t, carry):
            m, l, acc = carry
            ck = pltpu.make_async_copy(
                src_k.at[layer, i, pl.ds(t * blk, blk)], kscr, sem_k)
            cv = pltpu.make_async_copy(
                src_v.at[layer, i, pl.ds(t * blk, blk)], vscr, sem_v)
            ck.start()
            cv.start()
            ck.wait()
            cv.wait()
            kb = jnp.transpose(kscr[...].astype(f32), (1, 0, 2))
            vb = jnp.transpose(vscr[...].astype(f32), (1, 0, 2))
            s = jax.lax.dot_general(
                q[:, None, :], kb,
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=f32,
            )[:, 0, :]
            k_pos = t * blk + jax.lax.broadcasted_iota(
                jnp.int32, (n_head, blk), 1)
            s = jnp.where(k_pos < n_valid, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=1))
            pexp = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + pexp.sum(axis=1)
            pv = jax.lax.dot_general(
                pexp[:, None, :], vb,
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=f32,
            )[:, 0, :]
            acc_new = acc * alpha[:, None] + pv
            return m_new, l_new, acc_new

        m, l, acc = jax.lax.fori_loop(0, n_blk, body, (m0, l0, acc0))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        return acc / l_safe[:, None]  # [h, dh] f32

    def proj_heads(ctx, w_ref):
        # output projection as a per-head sum (sublane-aligned slices
        # of the [hd, dm] weight) — ctx never round-trips HBM
        out = jnp.zeros((1, d_model), f32)
        for hi in range(n_head):
            out = out + jnp.dot(
                ctx[hi:hi + 1, :],
                w_ref[hi * dh:(hi + 1) * dh, :].astype(f32),
                preferred_element_type=f32)
        return out

    def layer_norm(y, s_ref, b_ref):
        mean = jnp.mean(y, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(y - mean), axis=1, keepdims=True)
        return ((y - mean) * jax.lax.rsqrt(var + eps)
                * s_ref[...].astype(f32) + b_ref[...].astype(f32))

    # self-attention over the ring cache (incl. the fresh row)
    ctx = walk(cko_ref, cvo_ref, kblk, vblk, length, block_t)
    x1 = layer_norm(x0 + proj_heads(ctx, wout), ln1s, ln1b)

    # cached cross-attention: fresh query, prefilled K/V
    for hi in range(n_head):
        q_scr[hi, :] = jnp.dot(
            x1, wcq[:, hi * dh:(hi + 1) * dh].astype(f32),
            preferred_element_type=f32)[0] * scale
    cctx = walk(xk_ref, xv_ref, ckblk, cvblk, clen, cross_block_t)
    x2 = layer_norm(x1 + proj_heads(cctx, wcout), ln2s, ln2b)

    if fuse_ffn:
        fiw, fib, fow, fob, ln3s, ln3b = ffn_refs
        hid = jnp.maximum(
            jnp.dot(x2, fiw[...].astype(f32),
                    preferred_element_type=f32)
            + fib[...].astype(f32), 0.0)
        ffd = jnp.dot(hid, fow[...].astype(f32),
                      preferred_element_type=f32) + fob[...].astype(f32)
        x2 = layer_norm(x2 + ffd, ln3s, ln3b)

    o_ref[0] = x2.astype(o_ref.dtype)


def _ffn_kernel(x_ref, fiw, fib, fow, fob, ln3s, ln3b, o_ref, *, eps):
    """Split-mode second launch: the position-wise feed-forward +
    residual + final layer norm over the whole [b, 1, d_model] batch
    (the FFN weights did not fit VMEM next to the attention set)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    x0 = x_ref[:, 0, :].astype(f32)  # [b, d_model]
    hid = jnp.maximum(
        jnp.dot(x0, fiw[...].astype(f32), preferred_element_type=f32)
        + fib[...].astype(f32), 0.0)
    ffd = jnp.dot(hid, fow[...].astype(f32),
                  preferred_element_type=f32) + fob[...].astype(f32)
    y = x0 + ffd
    mean = jnp.mean(y, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(y - mean), axis=1, keepdims=True)
    y = ((y - mean) * jax.lax.rsqrt(var + eps) * ln3s[...].astype(f32)
         + ln3b[...].astype(f32))
    o_ref[:, 0, :] = y.astype(o_ref.dtype)


def fused_decode_step(x, wqkv, wout, ln1_scale, ln1_bias, wcq, wcout,
                      ln2_scale, ln2_bias, ffn_in_w, ffn_in_b, ffn_out_w,
                      ffn_out_b, ln3_scale, ln3_bias, cache_k, cache_v,
                      cross_k, cross_v, pos, lengths, cross_lengths,
                      active=None, *, layer, n_head, scale, eps=1e-5,
                      block_t=256, interpret=None):
    """One fused decoder layer over a single embedded token.

    x [b, 1, d_model]; wqkv [d_model, 3*h*dh] (packed q|k|v columns —
    attn_qkv_w); wout/wcout [h*dh, d_model]; wcq [d_model, h*dh]; layer
    norm scale/bias [d_model]; ffn_in_w [d_model, d_inner] (+ bias),
    ffn_out_w [d_inner, d_model] (+ bias); cache_k/cache_v
    [L, b, max_t, h, dh] ring buffers (returned updated — the caller
    aliases them back into scope state); cross_k/cross_v the prefilled
    cross caches (read-only); pos/lengths/cross_lengths [b] int32
    counters; active [b] 0/1 write gate or None.

    Returns (out [b, 1, d_model], cache_k', cache_v').  Off-contract
    shapes (or off-TPU without an explicit interpret=True) run
    reference_decode_step — the numerically-identical composition.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, _, d_model = x.shape
    h = n_head
    dh = cache_k.shape[-1]
    max_t = cache_k.shape[2]
    cross_t = cross_k.shape[2]
    d_inner = ffn_in_w.shape[-1]
    plan = _megastep_plan(d_model, h, dh, d_inner, max_t, cross_t,
                          x.dtype, block_t, interpret)
    if not plan.ok or (plan.interpret and interpret is None):
        # off-TPU the XLA composition beats interpret-mode emulation;
        # tests drive the kernel explicitly with interpret=True
        return reference_decode_step(
            x, wqkv, wout, ln1_scale, ln1_bias, wcq, wcout, ln2_scale,
            ln2_bias, ffn_in_w, ffn_in_b, ffn_out_w, ffn_out_b,
            ln3_scale, ln3_bias, cache_k, cache_v, cross_k, cross_v,
            pos, lengths, cross_lengths, active, layer=layer,
            n_head=n_head, scale=scale, eps=eps)

    def scal(a):
        return jnp.asarray(a).reshape(-1).astype(jnp.int32)

    def row2d(a):
        return jnp.asarray(a).reshape(1, -1)

    act32 = (jnp.ones((b,), jnp.int32) if active is None
             else scal(active))
    weights = [wqkv, wout, row2d(ln1_scale), row2d(ln1_bias), wcq,
               wcout, row2d(ln2_scale), row2d(ln2_bias)]
    if plan.fuse_ffn:
        weights += [ffn_in_w, row2d(ffn_in_b), ffn_out_w,
                    row2d(ffn_out_b), row2d(ln3_scale), row2d(ln3_bias)]

    kernel = functools.partial(
        _megastep_kernel, layer=layer, scale=scale, eps=eps,
        block_t=plan.block_t, cross_block_t=plan.cross_block_t,
        n_head=h, d_head=dh, d_model=d_model, fuse_ffn=plan.fuse_ffn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # pos, lengths, cross_lengths, active
        grid=(b,),
        in_specs=(
            [pl.BlockSpec((1, 1, d_model), lambda i, *_: (i, 0, 0))]
            + [pl.BlockSpec(w.shape, lambda i, *_: (0, 0))
               for w in weights]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 4  # caches
        ),
        out_specs=[
            pl.BlockSpec((1, 1, d_model), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, dh), jnp.float32),        # q (pre-scaled)
            pltpu.VMEM((1, h, dh), cache_k.dtype),   # fresh k row
            pltpu.VMEM((1, h, dh), cache_v.dtype),   # fresh v row
            pltpu.VMEM((plan.block_t, h, dh), cache_k.dtype),
            pltpu.VMEM((plan.block_t, h, dh), cache_v.dtype),
            pltpu.VMEM((plan.cross_block_t, h, dh), cross_k.dtype),
            pltpu.VMEM((plan.cross_block_t, h, dh), cross_v.dtype),
            pltpu.SemaphoreType.DMA,  # row write
            pltpu.SemaphoreType.DMA,  # k walk
            pltpu.SemaphoreType.DMA,  # v walk
        ],
    )
    # input indexing for the aliases counts the 4 prefetch scalars, x,
    # and the weight blocks; each cache buffer IS its output (in-place
    # HBM row write, the scatter-add recipe)
    cache_k_idx = 4 + 1 + len(weights)
    out, cache_k, cache_v = pl.pallas_call(
        kernel,
        name="decode_step_fwd",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, d_model), x.dtype),
            jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
            jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype),
        ],
        input_output_aliases={cache_k_idx: 1, cache_k_idx + 1: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=bool(plan.interpret),
    )(scal(pos), scal(lengths), scal(cross_lengths), act32, x,
      *weights, cache_k, cache_v, cross_k, cross_v)

    if not plan.fuse_ffn:
        ffn_kernel = functools.partial(_ffn_kernel, eps=eps)
        out = pl.pallas_call(
            ffn_kernel,
            name="decode_step_ffn_fwd",
            out_shape=jax.ShapeDtypeStruct((b, 1, d_model), x.dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=bool(plan.interpret),
        )(out, ffn_in_w, row2d(ffn_in_b), ffn_out_w, row2d(ffn_out_b),
          row2d(ln3_scale), row2d(ln3_bias))
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# paged-cache variants (FLAGS_paged_kv_cache) — the ring path above is
# untouched so flag-off graphs stay byte-stable
# ---------------------------------------------------------------------------


def _paged_megastep_plan(d_model, n_head, d_head, d_inner, block_t,
                         cross_block_t, batch, max_blocks,
                         cross_max_blocks, dtype, interpret=None):
    """Static feasibility gate for the paged megastep; returns a
    MegastepPlan.  Unlike _megastep_plan the walk blocks are FIXED by
    the pool geometry (misaligned block_t is a build error → reject, no
    snapping), and both flattened block tables must fit the scalar-
    prefetch budget (_PAGED_TABLE_CAP entries) since every walk
    iteration reads its DMA address from SMEM."""
    from .decode_attention import _PAGED_TABLE_CAP
    from .placement import resolve

    compiled, interpret = resolve(interpret)
    esize = _itemsize(dtype)
    bt = int(block_t)
    cbt = int(cross_block_t)
    sublane = 8 if esize >= 4 else 16
    aligned = (
        (compiled or interpret)
        and d_model % 128 == 0
        and d_inner % 128 == 0
        and d_head % (64 if interpret else 128) == 0
        and n_head % sublane == 0
        and bt % 8 == 0 and bt > 0
        and cbt % 8 == 0 and cbt > 0
        and batch * max_blocks <= _PAGED_TABLE_CAP
        and batch * cross_max_blocks <= _PAGED_TABLE_CAP
    )
    attn_bytes, ffn_bytes = _megastep_vmem_bytes(
        d_model, n_head, d_head, d_inner, bt, cbt, esize)
    ok = aligned and attn_bytes <= _VMEM_BUDGET and ffn_bytes <= _VMEM_BUDGET
    fuse_ffn = ok and attn_bytes + ffn_bytes <= _VMEM_BUDGET
    return MegastepPlan(ok, fuse_ffn, bt, cbt, interpret)


def reference_decode_step_paged(x, wqkv, wout, ln1_scale, ln1_bias, wcq,
                                wcout, ln2_scale, ln2_bias, ffn_in_w,
                                ffn_in_b, ffn_out_w, ffn_out_b, ln3_scale,
                                ln3_bias, cache_k, cache_v, cross_k,
                                cross_v, pos, lengths, cross_lengths,
                                self_table, cross_table, active=None, *,
                                layer, n_head, scale, eps=1e-5):
    """The composed decoder step over PAGED caches — the exact op chain
    cached_decoder_step emits with FLAGS_paged_kv_cache on and
    FLAGS_fused_decode_step off (paged_kv_cache_update's shared scatter
    core, paged_decode_attention's table-gathered walk), so fused/
    unfused paged programs stay numerically identical on every backend.
    cache_k/cache_v are [L, num_blocks, block_t, h, dh] pools; the
    tables are [b, max_blocks] int32.  Returns (out, cache_k',
    cache_v')."""
    import jax
    import jax.numpy as jnp

    from ..flags import FLAGS
    from . import decode_attention as kda

    b = x.shape[0]
    h = n_head
    dh = cache_k.shape[-1]
    hd = h * dh

    def mul(a, w):
        a2 = a.reshape((b * 1, -1))
        return (a2 @ w).reshape((b, 1, w.shape[-1]))

    def layer_norm(y, s, bias):
        stat = jnp.float32 if y.dtype == jnp.bfloat16 else y.dtype
        ys = y.astype(stat)
        mean = jnp.mean(ys, axis=2, keepdims=True)
        var = jnp.mean(jnp.square(ys - mean), axis=2, keepdims=True)
        out = (ys - mean) * jax.lax.rsqrt(var + eps)
        out = out * s.reshape((1, 1, -1)).astype(stat)
        out = out + bias.reshape((1, 1, -1)).astype(stat)
        return out.astype(y.dtype)

    def write(cache, new):
        return kda.paged_scatter_rows(cache, new.reshape(b, 1, h, dh),
                                      self_table, pos, active, layer)

    def attend(q, kc, vc, tab, lens):
        q3 = q.reshape(b, h, dh)
        lens32 = lens.reshape(-1).astype(jnp.int32)
        if FLAGS.flash_decode:
            o = kda.flash_decode_paged(q3, kc[layer], vc[layer], tab,
                                       lens32, scale=scale)
        else:
            o = kda.reference_decode_paged(q3, kc[layer], vc[layer], tab,
                                           lens32, scale=scale)
        return o.reshape(b, 1, h, dh)

    qkv = mul(x, wqkv)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    cache_k = write(cache_k, k)
    cache_v = write(cache_v, v)
    ctx = attend(q, cache_k, cache_v, self_table, lengths)
    attn_out = mul(ctx.reshape(b, 1, hd), wout)
    x = layer_norm(x + attn_out, ln1_scale, ln1_bias)
    cq = mul(x, wcq)
    cctx = attend(cq, cross_k, cross_v, cross_table, cross_lengths)
    cross_out = mul(cctx.reshape(b, 1, hd), wcout)
    x = layer_norm(x + cross_out, ln2_scale, ln2_bias)
    hid = jax.nn.relu(mul(x, ffn_in_w) + ffn_in_b.reshape((1, 1, -1)))
    ffd = mul(hid, ffn_out_w) + ffn_out_b.reshape((1, 1, -1))
    x = layer_norm(x + ffd, ln3_scale, ln3_bias)
    return x, cache_k, cache_v


def _paged_megastep_kernel(pos_ref, lens_ref, clens_ref, act_ref,
                           stab_ref, ctab_ref, *refs, layer, scale, eps,
                           block_t, cross_block_t, n_head, d_head,
                           d_model, fuse_ffn, max_blocks,
                           cross_max_blocks):
    """The megastep with table-hopped cache traffic: the fresh k/v row
    lands at pool block stab[i, pos // bt] row pos % bt, and both walks
    DMA [block_t, h, dh] pool blocks at scalar-prefetched table
    addresses instead of contiguous ring windows."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    dh = d_head
    hd = n_head * d_head
    n_w = 15 if fuse_ffn else 9
    x_ref = refs[0]
    (wqkv, wout, ln1s, ln1b, wcq, wcout, ln2s, ln2b) = refs[1:9]
    ffn_refs = refs[9:n_w]
    xk_ref, xv_ref = refs[n_w + 2:n_w + 4]
    o_ref, cko_ref, cvo_ref = refs[n_w + 4:n_w + 7]
    (q_scr, krow, vrow, kblk, vblk, ckblk, cvblk,
     sem_w, sem_k, sem_v) = refs[n_w + 7:]

    i = pl.program_id(0)
    p = pos_ref[i]
    length = lens_ref[i]
    clen = clens_ref[i]
    act = act_ref[i]

    x0 = x_ref[0].astype(f32)  # [1, d_model]

    for hi in range(n_head):
        q_scr[hi, :] = jnp.dot(
            x0, wqkv[:, hi * dh:(hi + 1) * dh].astype(f32),
            preferred_element_type=f32)[0] * scale
        krow[0, hi, :] = jnp.dot(
            x0, wqkv[:, hd + hi * dh:hd + (hi + 1) * dh].astype(f32),
            preferred_element_type=f32)[0].astype(krow.dtype)
        vrow[0, hi, :] = jnp.dot(
            x0, wqkv[:, 2 * hd + hi * dh:2 * hd + (hi + 1) * dh]
            .astype(f32),
            preferred_element_type=f32)[0].astype(vrow.dtype)

    # in-place row write through the table: the covering block's pool
    # address comes from SMEM, the row offset is pos % block_t
    @pl.when(act != 0)
    def _write_row():
        wblk = stab_ref[i * max_blocks + p // block_t]
        woff = p % block_t
        wk = pltpu.make_async_copy(
            krow, cko_ref.at[layer, wblk, pl.ds(woff, 1)], sem_w)
        wv = pltpu.make_async_copy(
            vrow, cvo_ref.at[layer, wblk, pl.ds(woff, 1)], sem_w)
        wk.start()
        wv.start()
        wk.wait()
        wv.wait()

    def walk(src_k, src_v, tab_ref, mb, kscr, vscr, n_valid, blk):
        """The online-softmax walk, block t streaming from pool block
        tab[i * mb + t] of layer `layer`."""
        q = q_scr[...]
        m0 = jnp.full((n_head,), -jnp.inf, f32)
        l0 = jnp.zeros((n_head,), f32)
        acc0 = jnp.zeros((n_head, d_head), f32)
        n_blk = jax.lax.div(n_valid + (blk - 1), blk)

        def body(t, carry):
            m, l, acc = carry
            pb = tab_ref[i * mb + t]
            ck = pltpu.make_async_copy(
                src_k.at[layer, pb], kscr, sem_k)
            cv = pltpu.make_async_copy(
                src_v.at[layer, pb], vscr, sem_v)
            ck.start()
            cv.start()
            ck.wait()
            cv.wait()
            kb = jnp.transpose(kscr[...].astype(f32), (1, 0, 2))
            vb = jnp.transpose(vscr[...].astype(f32), (1, 0, 2))
            s = jax.lax.dot_general(
                q[:, None, :], kb,
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=f32,
            )[:, 0, :]
            k_pos = t * blk + jax.lax.broadcasted_iota(
                jnp.int32, (n_head, blk), 1)
            s = jnp.where(k_pos < n_valid, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=1))
            pexp = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + pexp.sum(axis=1)
            pv = jax.lax.dot_general(
                pexp[:, None, :], vb,
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=f32,
            )[:, 0, :]
            acc_new = acc * alpha[:, None] + pv
            return m_new, l_new, acc_new

        m, l, acc = jax.lax.fori_loop(0, n_blk, body, (m0, l0, acc0))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        return acc / l_safe[:, None]

    def proj_heads(ctx, w_ref):
        out = jnp.zeros((1, d_model), f32)
        for hi in range(n_head):
            out = out + jnp.dot(
                ctx[hi:hi + 1, :],
                w_ref[hi * dh:(hi + 1) * dh, :].astype(f32),
                preferred_element_type=f32)
        return out

    def layer_norm(y, s_ref, b_ref):
        mean = jnp.mean(y, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(y - mean), axis=1, keepdims=True)
        return ((y - mean) * jax.lax.rsqrt(var + eps)
                * s_ref[...].astype(f32) + b_ref[...].astype(f32))

    ctx = walk(cko_ref, cvo_ref, stab_ref, max_blocks, kblk, vblk,
               length, block_t)
    x1 = layer_norm(x0 + proj_heads(ctx, wout), ln1s, ln1b)

    for hi in range(n_head):
        q_scr[hi, :] = jnp.dot(
            x1, wcq[:, hi * dh:(hi + 1) * dh].astype(f32),
            preferred_element_type=f32)[0] * scale
    cctx = walk(xk_ref, xv_ref, ctab_ref, cross_max_blocks, ckblk,
                cvblk, clen, cross_block_t)
    x2 = layer_norm(x1 + proj_heads(cctx, wcout), ln2s, ln2b)

    if fuse_ffn:
        fiw, fib, fow, fob, ln3s, ln3b = ffn_refs
        hid = jnp.maximum(
            jnp.dot(x2, fiw[...].astype(f32),
                    preferred_element_type=f32)
            + fib[...].astype(f32), 0.0)
        ffd = jnp.dot(hid, fow[...].astype(f32),
                      preferred_element_type=f32) + fob[...].astype(f32)
        x2 = layer_norm(x2 + ffd, ln3s, ln3b)

    o_ref[0] = x2.astype(o_ref.dtype)


def fused_decode_step_paged(x, wqkv, wout, ln1_scale, ln1_bias, wcq,
                            wcout, ln2_scale, ln2_bias, ffn_in_w,
                            ffn_in_b, ffn_out_w, ffn_out_b, ln3_scale,
                            ln3_bias, cache_k, cache_v, cross_k, cross_v,
                            pos, lengths, cross_lengths, self_table,
                            cross_table, active=None, *, layer, n_head,
                            scale, eps=1e-5, interpret=None):
    """One fused decoder layer over paged caches.

    Same weight operands as fused_decode_step; cache_k/cache_v and
    cross_k/cross_v are [L, num_blocks, block_t, h, dh] pools and
    self_table/cross_table [b, max_blocks] int32 block tables (graph-
    read-only — the host owns allocation).  Returns (out, cache_k',
    cache_v').  Off-contract shapes (or off-TPU without an explicit
    interpret=True) run reference_decode_step_paged."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, _, d_model = x.shape
    h = n_head
    dh = cache_k.shape[-1]
    d_inner = ffn_in_w.shape[-1]
    plan = _paged_megastep_plan(
        d_model, h, dh, d_inner, cache_k.shape[2], cross_k.shape[2], b,
        self_table.shape[1], cross_table.shape[1], x.dtype, interpret)
    if not plan.ok or (plan.interpret and interpret is None):
        return reference_decode_step_paged(
            x, wqkv, wout, ln1_scale, ln1_bias, wcq, wcout, ln2_scale,
            ln2_bias, ffn_in_w, ffn_in_b, ffn_out_w, ffn_out_b,
            ln3_scale, ln3_bias, cache_k, cache_v, cross_k, cross_v,
            pos, lengths, cross_lengths, self_table, cross_table,
            active, layer=layer, n_head=n_head, scale=scale, eps=eps)

    def scal(a):
        return jnp.asarray(a).reshape(-1).astype(jnp.int32)

    def row2d(a):
        return jnp.asarray(a).reshape(1, -1)

    act32 = (jnp.ones((b,), jnp.int32) if active is None
             else scal(active))
    weights = [wqkv, wout, row2d(ln1_scale), row2d(ln1_bias), wcq,
               wcout, row2d(ln2_scale), row2d(ln2_bias)]
    if plan.fuse_ffn:
        weights += [ffn_in_w, row2d(ffn_in_b), ffn_out_w,
                    row2d(ffn_out_b), row2d(ln3_scale), row2d(ln3_bias)]

    kernel = functools.partial(
        _paged_megastep_kernel, layer=layer, scale=scale, eps=eps,
        block_t=plan.block_t, cross_block_t=plan.cross_block_t,
        n_head=h, d_head=dh, d_model=d_model, fuse_ffn=plan.fuse_ffn,
        max_blocks=int(self_table.shape[1]),
        cross_max_blocks=int(cross_table.shape[1]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # pos, lengths, cross_lengths, active, self table, cross table
        num_scalar_prefetch=6,
        grid=(b,),
        in_specs=(
            [pl.BlockSpec((1, 1, d_model), lambda i, *_: (i, 0, 0))]
            + [pl.BlockSpec(w.shape, lambda i, *_: (0, 0))
               for w in weights]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 4  # pools
        ),
        out_specs=[
            pl.BlockSpec((1, 1, d_model), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, dh), jnp.float32),
            pltpu.VMEM((1, h, dh), cache_k.dtype),
            pltpu.VMEM((1, h, dh), cache_v.dtype),
            pltpu.VMEM((plan.block_t, h, dh), cache_k.dtype),
            pltpu.VMEM((plan.block_t, h, dh), cache_v.dtype),
            pltpu.VMEM((plan.cross_block_t, h, dh), cross_k.dtype),
            pltpu.VMEM((plan.cross_block_t, h, dh), cross_v.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    cache_k_idx = 6 + 1 + len(weights)
    out, cache_k, cache_v = pl.pallas_call(
        kernel,
        name="decode_step_paged_fwd",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, d_model), x.dtype),
            jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
            jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype),
        ],
        input_output_aliases={cache_k_idx: 1, cache_k_idx + 1: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=bool(plan.interpret),
    )(scal(pos), scal(lengths), scal(cross_lengths), act32,
      scal(self_table), scal(cross_table), x, *weights, cache_k,
      cache_v, cross_k, cross_v)

    if not plan.fuse_ffn:
        ffn_kernel = functools.partial(_ffn_kernel, eps=eps)
        out = pl.pallas_call(
            ffn_kernel,
            name="decode_step_paged_ffn_fwd",
            out_shape=jax.ShapeDtypeStruct((b, 1, d_model), x.dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=bool(plan.interpret),
        )(out, ffn_in_w, row2d(ffn_in_b), ffn_out_w, row2d(ffn_out_b),
          row2d(ln3_scale), row2d(ln3_bias))
    return out, cache_k, cache_v
