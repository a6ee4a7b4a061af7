"""Ring attention: exact attention over sequences sharded across devices.

New TPU capability beyond the reference (SURVEY.md §5.7: the reference's max
context is bounded by single-device memory; nothing shards the sequence
axis).  The sequence axis is sharded over a mesh axis; each device holds a
Q shard and streams K/V shards around the ring with `jax.lax.ppermute` over
ICI, combining per-shard partial results with the online-softmax merge that
is flash attention's native algebra.

What makes this the real long-context path (VERDICT r4 item 3):

  * **The Pallas flash kernel runs inside every ring step** (same
    `_flash_forward`/`_flash_backward` kernels as kernels/attention.py) —
    no [t_q, t_k] score matrix ever exists, in forward OR backward, so
    per-device memory is O(t_local·d), independent of total sequence
    length.  Off-TPU / unaligned shapes fall back to a chunked XLA path
    with the same algebra.
  * **Custom VJP re-rings K/V in the backward** instead of saving every
    rotated shard as a residual: the forward stores only (q, k, v, kbias,
    out, lse) — all O(t_local) — and the backward circulates K/V (and the
    traveling dK/dV accumulators) around the ring again, exactly like the
    forward.  Plain autodiff through the unrolled loop would have stored
    n shards = the full sequence per device, defeating context parallelism.
  * **Causal rings skip fully-masked steps**: a chunk strictly in the
    future of this device's queries contributes nothing; a `lax.cond`
    skips its compute (the ring ppermute still advances, so lockstep
    collectives stay aligned).  The diagonal chunk runs the kernel's
    in-block causal mask.
  * **Key-side masks ride the ring**: an optional additive key bias
    [b|1, 1, 1, t_local] travels with its K/V chunk (a few KB), which is
    how `ring_attention_sharded` supports sequence lengths that do not
    divide the mesh axis (pad keys get -inf) and, generally, padding
    masks for ragged batches.
"""

from __future__ import annotations

import functools


def _pinf_to_ninf(lse):
    """Kernel convention for rows with no visible key is lse=+inf (so the
    backward recompute exp(s - lse) is 0).  For MERGING chunk partials the
    empty chunk must contribute exp(-inf)=0 instead."""
    import jax.numpy as jnp

    return jnp.where(jnp.isposinf(lse), -jnp.inf, lse)


def _chunk_fwd_xla(q, k, v, kbias, scale, causal):
    """Pure-XLA chunk partial: returns (o, lse') with lse' = -inf on rows
    with no visible key.  Fallback for shapes the Pallas plan rejects."""
    import jax.numpy as jnp

    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if kbias is not None:
        s = s + kbias.astype(jnp.float32)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
        s = jnp.where(mask, s, -jnp.inf)
    m = s.max(axis=-1)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    den = p.sum(axis=-1)
    num = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    den_safe = jnp.where(den == 0.0, 1.0, den)
    o = (num / den_safe[..., None]).astype(q.dtype)
    lse = jnp.where(den == 0.0, -jnp.inf, m_safe + jnp.log(den_safe))
    return o, lse


def _chunk_bwd_xla(q, k, v, kbias, out, lse, g, scale, causal):
    """Pure-XLA chunk backward against the GLOBAL lse (+inf on globally
    empty rows): p are globally-normalized probabilities, so the standard
    flash ds = p*(dp - delta) algebra applies per chunk."""
    import jax.numpy as jnp

    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if kbias is not None:
        s = s + kbias.astype(jnp.float32)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jnp.exp(s - lse[..., None])          # 0 where masked or lse=+inf
    gf = g.astype(jnp.float32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v.astype(jnp.float32))
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _to_bhtd(x, fmt):
    return x.transpose(0, 2, 1, 3) if fmt == "bthd" else x


def _chunk_plan(q, k, block_q, block_k, fmt):
    """The attention plan gate for one ring chunk.  The ring runs inside
    a fully-manual shard_map, where each shard's kernel is placeable even
    when the program around it is GSPMD-partitioned."""
    from .attention import _plan
    from .placement import gspmd_trace

    with gspmd_trace(False):
        return _plan(q, k, block_q, block_k, None, fmt)


def _chunk_fwd(q, k, v, kbias, scale, causal, block_q, block_k,
               fmt="bhtd"):
    """One ring step's partial attention: Pallas flash kernel when the
    plan allows, XLA chunk otherwise.  Returns (o, lse') with the -inf
    empty-row convention.  fmt "bthd" runs the lane-dense kernels of the
    single-device path (attention.py `_bthd_forward`) — the per-device
    shards stay [b, t_local, h, d] and no split-head
    transpose exists anywhere on the ring (the relayout-copy class the
    bthd kernels were built to kill); only the XLA fallback transposes."""
    from .attention import _flash_forward

    ok, bq, bk, interp = _chunk_plan(q, k, block_q, block_k, fmt)
    if not ok:
        if fmt == "bthd":
            o, lse = _chunk_fwd_xla(_to_bhtd(q, fmt), _to_bhtd(k, fmt),
                                    _to_bhtd(v, fmt), kbias, scale, causal)
            return o.transpose(0, 2, 1, 3), lse
        return _chunk_fwd_xla(q, k, v, kbias, scale, causal)
    import jax.numpy as jnp

    seed = jnp.zeros((1,), jnp.uint32)
    out, lse = _flash_forward(q, k, v, kbias, seed, scale, causal, bq, bk,
                              interp, fmt, 0.0)
    return out, _pinf_to_ninf(lse)


def _chunk_bwd(q, k, v, kbias, out, lse, g, scale, causal, block_q,
               block_k, fmt="bhtd"):
    """One ring step's backward (against global out/lse): Pallas backward
    kernels when possible, XLA otherwise.  `lse` uses the kernel's +inf
    convention for globally-empty rows."""
    from .attention import _flash_backward

    ok, bq, bk, interp = _chunk_plan(q, k, block_q, block_k, fmt)
    if not ok:
        if fmt == "bthd":
            dq, dk, dv = _chunk_bwd_xla(
                _to_bhtd(q, fmt), _to_bhtd(k, fmt), _to_bhtd(v, fmt),
                kbias, _to_bhtd(out, fmt), lse, _to_bhtd(g, fmt), scale,
                causal)
            return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
                    dv.transpose(0, 2, 1, 3))
        return _chunk_bwd_xla(q, k, v, kbias, out, lse, g, scale, causal)
    import jax.numpy as jnp

    seed = jnp.zeros((1,), jnp.uint32)
    return _flash_backward(q, k, v, kbias, seed, out, lse, g, scale,
                           causal, bq, bk, interp, fmt, 0.0)


def _stat_bcast(stat, fmt):
    """[b, h, t] per-row statistic -> broadcastable against the chunk
    output layout ([b, h, t, 1] bhtd / [b, t, h, 1] bthd)."""
    if fmt == "bthd":
        stat = stat.transpose(0, 2, 1)
    return stat[..., None]


def _varying(x, axis_name):
    """Constants made inside a shard_map are unvaried over the mesh axis;
    lax.cond demands both branches match the compute branch's
    device-varying type."""
    import jax

    return jax.lax.pcast(x, axis_name, to="varying")


def _zeros_like_chunk(q, axis_name, fmt="bhtd"):
    import jax
    import jax.numpy as jnp

    from .attention import _dims

    b, h, t, _ = _dims(q, fmt)
    return (_varying(jnp.zeros(q.shape, q.dtype), axis_name),
            _varying(jnp.full((b, h, t), -jnp.inf, jnp.float32), axis_name))


def _ring_fwd(q, k, v, kbias, axis_name, scale, causal, block_q, block_k,
              fmt="bhtd"):
    """Forward ring.  Returns (out, lse) with lse=+inf on rows that saw no
    key anywhere (kernel convention, ready for _chunk_bwd).  Shards are in
    `fmt` layout; per-row statistics always ride [b, h, t]."""
    import jax
    import jax.numpy as jnp

    from .attention import _dims

    n = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]

    b, h, t, d = _dims(q, fmt)
    m = jnp.full((b, h, t), -jnp.inf, jnp.float32)
    den = jnp.zeros((b, h, t), jnp.float32)
    acc = jnp.zeros(q.shape, jnp.float32)

    k_cur, v_cur, kb_cur = k, v, kbias

    for i in range(n):
        kv_idx = (my_idx - i) % n

        def full_fn(args):
            qq, kk, vv, bb = args
            return _chunk_fwd(qq, kk, vv, bb, scale, False, block_q,
                              block_k, fmt)

        def diag_fn(args):
            qq, kk, vv, bb = args
            return _chunk_fwd(qq, kk, vv, bb, scale, True, block_q,
                              block_k, fmt)

        def skip_fn(args):
            return _zeros_like_chunk(args[0], axis_name, fmt)

        args = (q, k_cur, v_cur, kb_cur)
        if not causal:
            o_i, lse_i = full_fn(args)
        else:
            # fully-masked future chunks skip their compute entirely — the
            # causal-FLOPs saving that makes a causal ring ~half cost
            o_i, lse_i = jax.lax.cond(
                kv_idx > my_idx, skip_fn,
                lambda a: jax.lax.cond(kv_idx == my_idx, diag_fn, full_fn,
                                       a),
                args)

        m_new = jnp.maximum(m, lse_i)
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m - m_safe))
        beta = jnp.exp(jnp.where(jnp.isneginf(lse_i), -jnp.inf,
                                 lse_i - m_safe))
        acc = (acc * _stat_bcast(alpha, fmt)
               + o_i.astype(jnp.float32) * _stat_bcast(beta, fmt))
        den = den * alpha + beta
        m = m_new

        if i < n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, fwd_perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, fwd_perm)
            if kb_cur is not None:
                kb_cur = jax.lax.ppermute(kb_cur, axis_name, fwd_perm)

    den_safe = jnp.where(den == 0.0, 1.0, den)
    out = jnp.where(_stat_bcast(den, fmt) == 0.0, 0.0,
                    acc / _stat_bcast(den_safe, fmt)).astype(q.dtype)
    lse = jnp.where(den == 0.0, jnp.inf,
                    jnp.where(jnp.isneginf(m), 0.0, m) + jnp.log(den_safe))
    return out, lse


def _ring_bwd(q, k, v, kbias, out, lse, g, axis_name, scale, causal,
              block_q, block_k, fmt="bhtd"):
    """Backward ring: K/V (and their traveling dK/dV accumulators)
    circulate again; residual memory stays O(t_local)."""
    import jax
    import jax.numpy as jnp

    n = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]

    dq = jnp.zeros(q.shape, jnp.float32)
    dk_t = jnp.zeros(k.shape, jnp.float32)
    dv_t = jnp.zeros(v.shape, jnp.float32)
    k_cur, v_cur, kb_cur = k, v, kbias

    for i in range(n):
        kv_idx = (my_idx - i) % n

        def full_fn(args):
            qq, kk, vv, bb = args
            return _chunk_bwd(qq, kk, vv, bb, out, lse, g, scale, False,
                              block_q, block_k, fmt)

        def diag_fn(args):
            qq, kk, vv, bb = args
            return _chunk_bwd(qq, kk, vv, bb, out, lse, g, scale, True,
                              block_q, block_k, fmt)

        def skip_fn(args):
            qq, kk, vv, _ = args
            pv = functools.partial(_varying, axis_name=axis_name)
            return (pv(jnp.zeros(qq.shape, qq.dtype)),
                    pv(jnp.zeros(kk.shape, kk.dtype)),
                    pv(jnp.zeros(vv.shape, vv.dtype)))

        args = (q, k_cur, v_cur, kb_cur)
        if not causal:
            dq_i, dk_i, dv_i = full_fn(args)
        else:
            dq_i, dk_i, dv_i = jax.lax.cond(
                kv_idx > my_idx, skip_fn,
                lambda a: jax.lax.cond(kv_idx == my_idx, diag_fn, full_fn,
                                       a),
                args)

        dq = dq + dq_i.astype(jnp.float32)
        dk_t = dk_t + dk_i.astype(jnp.float32)
        dv_t = dv_t + dv_i.astype(jnp.float32)

        if i < n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, fwd_perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, fwd_perm)
            if kb_cur is not None:
                kb_cur = jax.lax.ppermute(kb_cur, axis_name, fwd_perm)
            dk_t = jax.lax.ppermute(dk_t, axis_name, fwd_perm)
            dv_t = jax.lax.ppermute(dv_t, axis_name, fwd_perm)

    # after n-1 rotations each traveling accumulator sits one hop before
    # its chunk's home device — one more hop brings it home
    dk_t = jax.lax.ppermute(dk_t, axis_name, fwd_perm)
    dv_t = jax.lax.ppermute(dv_t, axis_name, fwd_perm)
    return dq.astype(q.dtype), dk_t.astype(k.dtype), dv_t.astype(v.dtype)


def ring_attention(q, k, v, axis_name, scale=1.0, causal=False, kbias=None,
                   block_q=512, block_k=512, fmt="bhtd"):
    """Runs INSIDE shard_map: q,k,v are the per-device sequence shards
    [b, h, t_local, d] (fmt "bhtd") or [b, t_local, h, d] (fmt "bthd" —
    the transpose-free convention: the ring path reuses the single-device
    lane-dense bthd kernels, so context parallelism does not
    re-introduce the split/merge-head transposes the bthd kernels
    deleted); optional kbias [b|1, 1, 1, t_local] is an additive key bias
    (padding mask) that travels the ring with its K/V chunk.  Exact
    softmax attention over the full (sharded) sequence."""
    import jax

    have_bias = kbias is not None

    @functools.partial(jax.custom_vjp, nondiff_argnums=())
    def _ring(q, k, v, kbias):
        out, _ = _ring_fwd(q, k, v, kbias if have_bias else None,
                           axis_name, scale, causal, block_q, block_k,
                           fmt)
        return out

    def _fwd(q, k, v, kbias):
        out, lse = _ring_fwd(q, k, v, kbias if have_bias else None,
                             axis_name, scale, causal, block_q, block_k,
                             fmt)
        return out, (q, k, v, kbias, out, lse)

    def _bwd(res, g):
        q, k, v, kbias, out, lse = res
        dq, dk, dv = _ring_bwd(q, k, v, kbias if have_bias else None, out,
                               lse, g, axis_name, scale, causal, block_q,
                               block_k, fmt)
        import jax.numpy as jnp

        return dq, dk, dv, jnp.zeros_like(kbias)

    _ring.defvjp(_fwd, _bwd)

    if kbias is None:
        import jax.numpy as jnp

        t_local = q.shape[1] if fmt == "bthd" else q.shape[2]
        kbias = jnp.zeros((1, 1, 1, t_local), jnp.float32)
    return _ring(q, k, v, kbias)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", scale=1.0,
                           causal=False, fmt="bhtd"):
    """Whole-array entry: q,k,v are global [b, h, T, d] (fmt "bhtd") or
    [b, T, h, d] (fmt "bthd") arrays; the sequence dim shards over
    `axis_name` of `mesh`; returns global output with the same sharding.
    T that does not divide the axis is padded and the pad keys masked via
    the ring-traveling key bias."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .attention import _dims

    n = mesh.shape[axis_name]
    b, h, t, d = _dims(q, fmt)
    tdim = 1 if fmt == "bthd" else 2
    pad = (-t) % n
    kbias = None
    if pad:
        widths = [(0, 0)] * 4
        widths[tdim] = (0, pad)
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
        pos = jnp.arange(t + pad)
        kbias = jnp.where(pos < t, 0.0, -1e30).astype(jnp.float32).reshape(
            1, 1, 1, t + pad)

    # batch stays data-parallel INSIDE the ring when the mesh has a data
    # axis: declaring it in the shard_map specs keeps the incoming
    # (data, sp)-sharded activations in place — leaving it out forces
    # the partitioner to all-gather the batch dim at the boundary
    # ("involuntary full rematerialization" in the dp x tp x sp dryrun)
    baxis = "data" if "data" in getattr(mesh, "axis_names", ()) else None
    spec = (P(baxis, axis_name, None, None) if fmt == "bthd"
            else P(baxis, None, axis_name, None))
    if kbias is None:
        fn = jax.shard_map(
            functools.partial(ring_attention, axis_name=axis_name,
                              scale=scale, causal=causal, fmt=fmt),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v)
    kb_spec = P(None, None, None, axis_name)   # kbias seq dim is LAST
    fn = jax.shard_map(
        lambda q, k, v, kb: ring_attention(q, k, v, axis_name, scale,
                                           causal, kbias=kb, fmt=fmt),
        mesh=mesh, in_specs=(spec, spec, spec, kb_spec), out_specs=spec,
        check_vma=False,
    )
    out = fn(q, k, v, kbias)
    return out[:, :t] if fmt == "bthd" else out[:, :, :t]
