"""Kimi Delta Attention (KDA): a gated delta rule with a decay per channel
of the key, as a chunked Pallas TPU kernel pair with its XLA composition.

For one head, S in R^{dk x dv}, S_0 = 0, and x-hat the L2-normalised x:

    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    o_t = dk^-1/2 S_t^T q_t

(Kimi Linear, arXiv:2510.26692, section 3; q and k normalised inside the
op, `use_qk_l2norm_in_kernel`).  g <= 0 is the log-decay, float32.

Chunked form (the WY / UT representation, as FLA's `chunk_kda`).  Within a
chunk of C rows, G = cumsum(g) (per channel) and, for the chunk's first
state S_0:

    Akk[i, j] = sum_c k_ic k_jc exp(G_ic - G_jc)     j < i
    Aqk[i, j] = sum_c q_ic k_jc exp(G_ic - G_jc)     j <= i
    T = (I + Diag(b) Akk)^-1
    U = T Diag(b) (V - (exp(G) * K) S_0)
    O = (exp(G) * Q) S_0 + Aqk U
    S_C = Diag(exp(G_C)) S_0 + (exp(G_C - G) * K)^T U

Every factor stays at or under one: `exp(G_i - G_j)` is never split into
`exp(G_i) exp(-G_j)`, which overflows float32 once a channel's log-decay
in a chunk passes about 88.  Pairs in different sub-blocks of SUB rows
meet through a reference G (FLA's secondary chunking), both factors exp
of a non-positive number: the row before the later sub-block where the
result's rows are the later sub-block's (`gram`, `_factors`,
`decay_mm`), the earlier sub-block's last row where they are the
earlier one's (`decay_mm_t`); either way one [SUB, C] x [C, d] product
a sub-block (two such stacked in `_factors`).
Pairs inside one sub-block are computed pair by pair (a column a step
in the kernels, all at once over [SUB, SUB, dk] in the composition).
The forward makes Akk, Aqk and the inverse of I + Diag(b) Akk on the
diagonal sub-blocks in one walk of the sub-blocks (`_factors`): a decay
tile exp(G_i - G_j) k_j serves both Grams, their rows across sub-blocks
meet the shared decayed k in one stacked product, and each column of Akk
takes one step of forward substitution for its sub-block's inverse as it
comes.  The inverse below those blocks is a product of (I + E^(2^m))
factors over the sub-blocks.

The state is kept transposed, H = S^T [dv, dk]: a decay by channel is
then a multiply by a row.  Beside O the forward writes, for each chunk,
its first state and its factors: T and Aqk packed into one [C, C] (T
strictly below the diagonal, its unit diagonal implied, Aqk^T on and
above it; `_pack`), both float32, b h t (dk dv / C + C) x 4 bytes: 268
MB a layer of Kimi-Linear's cell at C = 128.  The backward walks the
chunks in reverse from them, carrying dH.  It makes again only the L2
norms, G and Akk (dbeta's row sums read Akk, off the walk's critical
path); the inverse and Aqk, at the head of that path, it reads.  Walking
the chunks again in the backward instead of keeping the states cost as
much as the forward itself on the chip, for no less of the step's peak
memory (PERF.md, section 6).  One algebra (`chunk_fwd`, `chunk_bwd`,
functions of 2-D float32 arrays) serves the kernels' bodies and the XLA
composition, which scans over the chunks.

  kda(q, k, v [b, t, h*dk|dv], g [b, t, h*dk] f32, beta [b, t, h])
      -> (o [b, t, h*dv], states [b, h, n, dv, dk] f32,
          factors [b, h, n, C, C] f32)
  kda_bwd(q, k, v, g, beta, states, factors, do)
      -> (dq, dk, dv in the operands' dtype, dg f32, dbeta f32)
"""

from __future__ import annotations

import functools

SUB = 16
CHUNKS = (64, 128)
# the op's chunk: of the two the kernels take, the one a TPU v5e ran
# faster at Kimi-Linear's shape (PERF.md, section 6)
CHUNK = 128
L2_EPS = 1e-6


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def _dot(a, b, dims):
    """A product of two 2-D float32 arrays at full float32 precision:
    dims "nn" a @ b, "nt" a @ b^T, "tn" a^T @ b."""
    import jax
    import jax.numpy as jnp

    contract = {"nn": ((1,), (0,)), "nt": ((1,), (1,)), "tn": ((0,), (0,))}
    return jax.lax.dot_general(
        a, b, (contract[dims], ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _iota(shape, axis):
    import jax
    import jax.numpy as jnp

    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _eye(n):
    return _iota((n, n), 0) == _iota((n, n), 1)


def _below(n):
    """[n, n]: True strictly below the diagonal."""
    return _iota((n, n), 1) < _iota((n, n), 0)


def _col(row):
    """[1, n] -> [n, 1] by the diagonal of its broadcast (no relayout)."""
    import jax.numpy as jnp

    n = row.shape[1]
    return jnp.sum(jnp.where(_eye(n), jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


def _row(col):
    """[n, 1] -> [1, n]."""
    import jax.numpy as jnp

    n = col.shape[0]
    return jnp.sum(jnp.where(_eye(n), jnp.broadcast_to(col, (n, n)), 0.0),
                   axis=0, keepdims=True)


def _cumsum(x, reverse=False):
    """Inclusive cumulative sum down the rows, as a product with a
    triangle of ones (float32 at full precision)."""
    c = x.shape[0]
    r, s = _iota((c, c), 0), _iota((c, c), 1)
    tri = ((s >= r) if reverse else (s <= r)).astype(x.dtype)
    return _dot(tri, x, "nn")


def _l2norm(x):
    """(x / sqrt(|x|^2 + eps) by row, 1 / sqrt(|x|^2 + eps) [C, 1])."""
    import jax
    import jax.numpy as jnp

    r = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + L2_EPS)
    return x * r, r


def _l2norm_bwd(xn, r, dxn):
    import jax.numpy as jnp

    return r * (dxn - xn * jnp.sum(xn * dxn, axis=1, keepdims=True))


def _refs(gam):
    """For each sub-block I > 0 of the chunk: (its first row, the reference
    G [1, dk] at the row before it)."""
    return [(lo, gam[lo - 1:lo]) for lo in range(SUB, gam.shape[0], SUB)]


def _refs_after(gam):
    """For each sub-block J with rows after it: (its first row, the
    reference G [1, dk] at its last row)."""
    return [(lo, gam[lo + SUB - 1:lo + SUB])
            for lo in range(0, gam.shape[0] - SUB, SUB)]


def _column(p, j):
    """Column j of p [rows, C] as [rows, 1]."""
    import jax.numpy as jnp

    return jnp.sum(jnp.where(_iota(p.shape, 1) == j, p, 0.0), axis=1,
                   keepdims=True)


def _sub_blocks(gam):
    """(first row, rows of G) of each sub-block of the chunk."""
    return [(lo, gam[lo:lo + SUB]) for lo in range(0, gam.shape[0], SUB)]


def _pair_decay(g_block, g_row, keep):
    """exp(G_i - G_j) over a sub-block's rows i for one row j, 1 where the
    pair is not kept (there G_i - G_j may be positive and large)."""
    import jax.numpy as jnp

    return jnp.exp(jnp.where(keep, g_block - g_row, 0.0))


def _pairs(g_blk, strict):
    """(keep [SUB, SUB, 1], exp(G_i - G_j) [SUB, SUB, dk] where kept, 1
    elsewhere) of a sub-block's pairs (i, j): the composition's form."""
    import jax.numpy as jnp

    keep = _iota((SUB, SUB), 0) > _iota((SUB, SUB), 1) if strict else (
        _iota((SUB, SUB), 0) >= _iota((SUB, SUB), 1))
    keep = keep[:, :, None]
    return keep, jnp.exp(jnp.where(
        keep, g_blk[:, None, :] - g_blk[None, :, :], 0.0))


def _diag_gram(x_blk, y_blk, g_blk, lo, c, strict, vector):
    """[SUB, C]: a sub-block's own pairs of `gram`, in columns lo .. lo +
    SUB: pair by pair, a column a step (the kernels' form), or at once
    over [SUB, SUB, dk] (`vector`, the composition's)."""
    import jax.numpy as jnp

    if vector:
        keep, e = _pairs(g_blk, strict)
        blk = jnp.sum(jnp.where(keep, x_blk[:, None, :] * e
                                * y_blk[None, :, :], 0.0), axis=-1)
        return jnp.pad(blk, ((0, 0), (lo, c - lo - SUB)))
    i = _iota((SUB, 1), 0)
    lanes = _iota((SUB, c), 1)
    out = jnp.zeros((SUB, c), jnp.float32)
    for jj in range(SUB):
        keep = (i > jj) if strict else (i >= jj)
        e = _pair_decay(g_blk, g_blk[jj:jj + 1], keep)
        col = jnp.sum(x_blk * e * y_blk[jj:jj + 1], axis=1, keepdims=True)
        out = out + jnp.where((lanes == lo + jj) & keep, col, 0.0)
    return out


def _diag_mm(p_blk, y_blk, g_blk, lo, vector):
    """[SUB, d]: z_i = sum over the sub-block's j <= i of p_ij exp(G_i -
    G_j) y_j, p_blk the sub-block's rows of p [SUB, C]."""
    import jax.numpy as jnp

    if vector:
        keep, e = _pairs(g_blk, False)
        p_own = p_blk[:, lo:lo + SUB, None]
        return jnp.sum(jnp.where(keep, p_own * e * y_blk[None, :, :], 0.0),
                       axis=1)
    i = _iota((SUB, 1), 0)
    out = jnp.zeros(y_blk.shape, jnp.float32)
    for jj in range(SUB):
        keep = i >= jj
        e = _pair_decay(g_blk, g_blk[jj:jj + 1], keep)
        out = out + jnp.where(
            keep, _column(p_blk, lo + jj) * e * y_blk[jj:jj + 1], 0.0)
    return out


def _diag_mm_t(p_blk, x_blk, g_blk, lo, vector):
    """[SUB, d]: z_j = sum over the sub-block's i >= j of p_ij exp(G_i -
    G_j) x_i."""
    import jax.numpy as jnp

    if vector:
        keep, e = _pairs(g_blk, False)
        p_own = p_blk[:, lo:lo + SUB, None]
        return jnp.sum(jnp.where(keep, p_own * e * x_blk[:, None, :], 0.0),
                       axis=0)
    i = _iota((SUB, 1), 0)
    z = jnp.zeros(x_blk.shape, jnp.float32)
    for jj in range(SUB):
        keep = i >= jj
        e = _pair_decay(g_blk, g_blk[jj:jj + 1], keep)
        r = jnp.sum(jnp.where(keep, _column(p_blk, lo + jj) * e * x_blk,
                              0.0), axis=0, keepdims=True)
        z = z + jnp.where(i == jj, r, 0.0)
    return z


def gram(x, y, gam, strict, vector=False):
    """[C, C]: sum_c x_ic y_jc exp(G_ic - G_jc) for j < i (`strict`) or
    j <= i, zero elsewhere."""
    import jax.numpy as jnp

    c = x.shape[0]
    row_blocks = []
    below = dict(_refs(gam))
    for lo, g_blk in _sub_blocks(gam):
        x_blk = x[lo:lo + SUB]
        out = _diag_gram(x_blk, y[lo:lo + SUB], g_blk, lo, c, strict, vector)
        if lo in below:
            ref = below[lo]
            right = jnp.where(_iota(y.shape, 0) < lo,
                              y * jnp.exp(jnp.minimum(ref - gam, 0.0)), 0.0)
            out = out + _dot(x_blk * jnp.exp(g_blk - ref), right, "nt")
        row_blocks.append(out)
    return jnp.concatenate(row_blocks, axis=0)


def decay_mm(p, y, gam, vector=False):
    """[C, d]: z_i = sum_{j <= i} p_ij exp(G_i - G_j) * y_j for p [C, C]
    zero above its diagonal."""
    import jax.numpy as jnp

    below = dict(_refs(gam))
    row_blocks = []
    for lo, g_blk in _sub_blocks(gam):
        p_blk = p[lo:lo + SUB]
        out = _diag_mm(p_blk, y[lo:lo + SUB], g_blk, lo, vector)
        if lo in below:
            ref = below[lo]
            right = jnp.where(_iota(y.shape, 0) < lo,
                              y * jnp.exp(jnp.minimum(ref - gam, 0.0)), 0.0)
            out = out + jnp.exp(g_blk - ref) * _dot(p_blk, right, "nn")
        row_blocks.append(out)
    return jnp.concatenate(row_blocks, axis=0)


def decay_mm_t(p, x, gam, vector=False):
    """[C, d]: z_j = sum_{i >= j} p_ij exp(G_i - G_j) * x_i for p [C, C]
    zero above its diagonal (decay_mm's transpose), by the output's row
    blocks as decay_mm: one [SUB, C] x [C, d] product a sub-block, p
    transposed once."""
    import jax.numpy as jnp

    p_t = p.T
    after = dict(_refs_after(gam))
    row_blocks = []
    for lo, g_blk in _sub_blocks(gam):
        out = _diag_mm_t(p[lo:lo + SUB], x[lo:lo + SUB], g_blk, lo, vector)
        if lo in after:
            ref = after[lo]
            right = jnp.where(_iota(x.shape, 0) >= lo + SUB,
                              x * jnp.exp(jnp.minimum(gam - ref, 0.0)), 0.0)
            out = out + jnp.exp(jnp.minimum(ref - g_blk, 0.0)) * _dot(
                p_t[lo:lo + SUB], right, "nn")
        row_blocks.append(out)
    return jnp.concatenate(row_blocks, axis=0)


def _own_columns(kn_blk, qs_blk, g_blk, vector):
    """A sub-block's own pairs of both Grams, yielded column by column, so
    that each is used as it is made: for each j (Akk's column j [SUB, 1],
    zero on and above row j, Aqk's, zero above it), both from one decay
    tile exp(G_i - G_j) k_j: made a column a step (the kernels' form) or
    all at once over [SUB, SUB, dk] (`vector`, the composition's)."""
    import jax.numpy as jnp

    i = _iota((SUB, 1), 0)
    if vector:
        keep, e = _pairs(g_blk, False)
        w = e * kn_blk[None, :, :]
        strict = (_iota((SUB, SUB), 0) > _iota((SUB, SUB), 1))[:, :, None]
        akk = jnp.sum(jnp.where(strict, kn_blk[:, None, :] * w, 0.0), -1)
        aqk = jnp.sum(jnp.where(keep, qs_blk[:, None, :] * w, 0.0), -1)
        for j in range(SUB):
            yield akk[:, j:j + 1], aqk[:, j:j + 1]
        return
    for j in range(SUB):
        w = _pair_decay(g_blk, g_blk[j:j + 1], i >= j) * kn_blk[j:j + 1]
        yield (
            jnp.where(i > j, jnp.sum(kn_blk * w, axis=1, keepdims=True), 0.0),
            jnp.where(i >= j, jnp.sum(qs_blk * w, axis=1, keepdims=True),
                      0.0))


def _diag_factors(kn_blk, qs_blk, b_blk, g_blk, lo, c, vector):
    """[SUB, C] each, in columns lo .. lo + SUB: a sub-block's own pairs of
    Akk and of Aqk, and its rows of the inverse of its diagonal block of I
    + Diag(b) Akk, by forward substitution as Akk's columns come (row j of
    the inverse is whole once the columns before j are applied)."""
    import jax.numpy as jnp

    lanes = _iota((SUB, c), 1)
    akk = aqk = jnp.zeros((SUB, c), jnp.float32)
    inv = (lanes == lo + _iota((SUB, 1), 0)).astype(jnp.float32)
    for j, (kcol, qcol) in enumerate(
            _own_columns(kn_blk, qs_blk, g_blk, vector)):
        at = lanes == lo + j
        akk = akk + jnp.where(at, kcol, 0.0)
        aqk = aqk + jnp.where(at, qcol, 0.0)
        inv = inv - b_blk * kcol * inv[j:j + 1]
    return akk, aqk, inv


def _factors(kn, qs, beta, gam, vector):
    """(Akk, Aqk, D^-1) [C, C] of a chunk in one walk over its sub-blocks,
    D = I + Diag(b) Akk on its diagonal sub-blocks alone: the two Grams
    share each decay tile, and their pairs across sub-blocks one [2 SUB,
    dk] x [C, dk] product a sub-block (Akk's rows over Aqk's, both against
    the same decayed k)."""
    import jax.numpy as jnp

    c = kn.shape[0]
    below = dict(_refs(gam))
    blocks = []
    for lo, g_blk in _sub_blocks(gam):
        kn_blk, qs_blk = kn[lo:lo + SUB], qs[lo:lo + SUB]
        akk, aqk, inv = _diag_factors(kn_blk, qs_blk, beta[lo:lo + SUB],
                                      g_blk, lo, c, vector)
        if lo in below:
            ref = below[lo]
            right = jnp.where(_iota(kn.shape, 0) < lo,
                              kn * jnp.exp(jnp.minimum(ref - gam, 0.0)), 0.0)
            d = jnp.exp(g_blk - ref)
            both = _dot(jnp.concatenate([kn_blk * d, qs_blk * d], axis=0),
                        right, "nt")
            akk, aqk = akk + both[:SUB], aqk + both[SUB:]
        blocks.append((akk, aqk, inv))
    return [jnp.concatenate(x, axis=0) for x in zip(*blocks)]


def _unit_lower_inverse(n, diag_inv):
    """(I + n)^-1 for n [C, C] strictly lower triangular, from diag_inv,
    the inverse of I + n on its diagonal sub-blocks: (I + E)^-1 diag_inv,
    E = diag_inv times n below those blocks, by the product (I - E)(I +
    E^2)(I + E^4).. (E^(C / SUB) = 0)."""
    import jax.numpy as jnp

    c = n.shape[0]
    blk_r, blk_c = _iota((c, c), 0) // SUB, _iota((c, c), 1) // SUB
    eye = _eye(c).astype(jnp.float32)
    e = _dot(diag_inv, jnp.where(blk_r > blk_c, n, 0.0), "nn")
    inv, power = eye - e, e
    span = 2
    while span < c // SUB:
        power = _dot(power, power, "nn")
        inv = _dot(inv, eye + power, "nn")
        span *= 2
    return _dot(inv, diag_inv, "nn")


def _prepare(q, k, g, scale, vector):
    """What the backward makes again from a chunk's q, k, g: normalised q
    and k with their 1 / norms, q scaled, G and Akk.  `vector`: the
    sub-blocks' own pairs at once (the composition), else pair by pair
    (the kernels)."""
    qn, rq = _l2norm(q)
    kn, rk = _l2norm(k)
    gam = _cumsum(g)
    akk = gram(kn, kn, gam, strict=True, vector=vector)
    return qn, rq, kn, rk, qn * scale, gam, akk


def _pack(aqk, t):
    """[C, C]: T below the diagonal (its unit diagonal implied) and Aqk^T
    on and above it, the forward's one transpose."""
    import jax.numpy as jnp

    return jnp.where(_below(t.shape[0]), t, aqk.T)


def _unpack(packed):
    """(T, Aqk^T) from `_pack`'s [C, C]."""
    import jax.numpy as jnp

    c = packed.shape[0]
    return (jnp.where(_below(c), packed, _eye(c).astype(jnp.float32)),
            jnp.where(_below(c), 0.0, packed))


def _advance(kn, gam, h0, u):
    """H_C = H_0 * exp(G_C) + U^T (exp(G_C - G) * K)."""
    import jax.numpy as jnp

    g_last = gam[-1:]
    return h0 * jnp.exp(g_last) + _dot(u, kn * jnp.exp(g_last - gam), "tn")


def chunk_fwd(q, k, v, g, beta, h0, scale, vector=False):
    """One chunk: (o [C, dv], the next state H_C [dv, dk], T and Aqk
    packed [C, C]) from q, k [C, dk], v [C, dv], g [C, dk], beta [C, 1]
    and H_0 = S_0^T [dv, dk], all float32."""
    import jax.numpy as jnp

    qn, _ = _l2norm(q)
    kn, _ = _l2norm(k)
    gam = _cumsum(g)
    qs = qn * scale
    akk, aqk, diag_inv = _factors(kn, qs, beta, gam, vector)
    t = _unit_lower_inverse(beta * akk, diag_inv)
    eg = jnp.exp(gam)
    u = _dot(t, beta * (v - _dot(kn * eg, h0, "nt")), "nn")
    o = _dot(qs * eg, h0, "nt") + _dot(aqk, u, "nn")
    return o, _advance(kn, gam, h0, u), _pack(aqk, t)


def chunk_bwd(q, k, v, g, beta, h0, packed, do, dh1, scale, vector=False):
    """chunk_fwd's gradients: (dq, dk, dv, dg, dbeta [C, 1], dH_0) from
    its inputs, its packed T and Aqk and the cotangents of o and of H_C."""
    import jax.numpy as jnp

    qn, rq, kn, rk, qs, gam, akk = _prepare(q, k, g, scale, vector)
    t, aqk_t = _unpack(packed)
    c = q.shape[0]
    lower = _iota((c, c), 1) <= _iota((c, c), 0)
    eg = jnp.exp(gam)
    g_last = gam[-1:]
    kt, qt = kn * eg, qs * eg
    kh = kn * jnp.exp(g_last - gam)
    resid = v - _dot(kt, h0, "nt")
    u = _dot(t, beta * resid, "nn")

    du = _dot(aqk_t, do, "nn") + _dot(kh, dh1, "nt")
    dkh = _dot(u, dh1, "nn")
    dqt = _dot(do, h0, "nn")
    daqk = jnp.where(lower, _dot(do, u, "nt"), 0.0)
    dx = _dot(t, du, "tn")
    dm = -_dot(dx, u, "nt")
    dakk = jnp.where(_below(c), beta * dm, 0.0)
    dbeta = (jnp.sum(dm * akk, axis=1, keepdims=True)
             + jnp.sum(dx * resid, axis=1, keepdims=True))
    dv = beta * dx
    dks = -beta * dx
    dkt = _dot(dks, h0, "nn")
    dh0 = (_dot(do, qt, "tn") + _dot(dks, kt, "tn")
           + dh1 * jnp.exp(g_last))
    dg_last = (jnp.sum(dh1 * h0 * jnp.exp(g_last), axis=0, keepdims=True)
               + jnp.sum(dkh * kh, axis=0, keepdims=True))

    dq_a = decay_mm(daqk, kn, gam, vector)
    dk_a = decay_mm_t(daqk, qs, gam, vector)
    dk_b = decay_mm(dakk, kn, gam, vector)
    dk_c = decay_mm_t(dakk, kn, gam, vector)
    dqs = dqt * eg + dq_a
    dkn = dkt * eg + dkh * jnp.exp(g_last - gam) + dk_a + dk_b + dk_c
    dgam = (dqt * qt + dkt * kt - dkh * kh + qs * dq_a - kn * dk_a
            + kn * dk_b - kn * dk_c)
    dgam = dgam + jnp.where(_iota(dgam.shape, 0) == c - 1, dg_last, 0.0)
    dg = _cumsum(dgam, reverse=True)
    dq = _l2norm_bwd(qn, rq, dqs * scale)
    dk = _l2norm_bwd(kn, rk, dkn)
    return dq, dk, dv, dg, dbeta, dh0


# ---------------------------------------------------------------------------
# the XLA composition: a scan over chunks, vmapped over batch rows and heads
# ---------------------------------------------------------------------------


def _pad_rows(x, c):
    import jax.numpy as jnp

    pad = -x.shape[1] % c
    return x if not pad else jnp.pad(
        x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


def _by_chunk(x, heads, c):
    """[b, t, heads*d] -> [b, heads, n, C, d] float32 (t padded to whole
    chunks with zeros: a padded row moves no state it could pass on)."""
    x = _pad_rows(x, c)
    b, t = x.shape[:2]
    return _f32(x).reshape(b, t // c, c, heads, -1).transpose(0, 3, 1, 2, 4)


def _beta_chunks(beta, c):
    b = _pad_rows(beta, c)
    n = b.shape[1] // c
    return _f32(b).reshape(b.shape[0], n, c, -1).transpose(0, 3, 1, 2)[
        ..., None]


def _from_chunks(x, t, dtype):
    """[b, heads, n, C, d] -> [b, t, heads*d]."""
    b, h, n, c, d = x.shape
    return x.transpose(0, 2, 3, 1, 4).reshape(b, n * c, h * d)[:, :t].astype(
        dtype)


def _walk(fn, args, dv, dk, reverse=False):
    """Scan `fn(carry, chunk's arrays) -> (carry, outputs)` over the chunks
    of each (row, head), vmapped over both, from a zero [dv, dk] carry."""
    import jax
    import jax.numpy as jnp

    def one(*xs):
        return jax.lax.scan(fn, jnp.zeros((dv, dk), jnp.float32), xs,
                            reverse=reverse)[1]

    return jax.vmap(jax.vmap(one))(*args)


def _dims(q, v, beta):
    heads = beta.shape[-1]
    return heads, q.shape[-1] // heads, v.shape[-1] // heads


def reference_kda(q, k, v, g, beta, chunk=CHUNK):
    """(o, states, factors) of the composition: the lowering off the TPU
    and where the plan rejects."""
    heads, dk, dv = _dims(q, v, beta)
    args = [_by_chunk(x, heads, chunk) for x in (q, k, v, g)]
    args.append(_beta_chunks(beta, chunk))

    def step(h, xs):
        o, h1, packed = chunk_fwd(*xs, h, dk ** -0.5, vector=True)
        return h1, (o, h, packed)

    o, states, factors = _walk(step, args, dv, dk)
    return _from_chunks(o, q.shape[1], q.dtype), states, factors


def reference_kda_bwd(q, k, v, g, beta, states, factors, do, chunk=CHUNK):
    """(dq, dk, dv, dg f32, dbeta f32) of the composition, from the
    forward's chunk states and factors: a reverse scan that carries dH."""
    import jax.numpy as jnp

    heads, dk, dv = _dims(q, v, beta)
    args = [_by_chunk(x, heads, chunk) for x in (q, k, v, g)]
    args += [_beta_chunks(beta, chunk), states, factors,
             _by_chunk(do.reshape(v.shape), heads, chunk)]

    def step(dh, xs):
        q_, k_, v_, g_, b_, h0, packed, do_ = xs
        dq, dk_, dv_, dg, db, dh0 = chunk_bwd(
            q_, k_, v_, g_, b_, h0, packed, do_, dh, dk ** -0.5,
            vector=True)
        return dh0, (dq, dk_, dv_, dg, db)

    dq, dk_, dv_, dg, db = _walk(step, args, dv, dk, reverse=True)
    t = q.shape[1]
    db = db[..., 0].transpose(0, 2, 3, 1).reshape(
        beta.shape[0], -1, heads)[:, :t]
    return (_from_chunks(dq, t, q.dtype), _from_chunks(dk_, t, k.dtype),
            _from_chunks(dv_, t, v.dtype), _from_chunks(dg, t, jnp.float32),
            db)


# ---------------------------------------------------------------------------
# the Pallas kernels: grid (batch row, head, chunk), the chunk axis walked
# in order (forward) or in reverse (backward) with the state in VMEM
# ---------------------------------------------------------------------------


def _plan(q, v, beta, chunk, interpret):
    """Static feasibility: (ok, interpret).  The kernels run compiled on
    the TPU, or interpreted where a caller asks (`interpret=True`); off
    the TPU the default is the composition.  A head is whole 128-lane
    tiles of q, k and v; the chunk is whole sub-blocks."""
    from .placement import resolve

    compiled, _ = resolve(interpret)
    interpret = interpret is True
    _, dk, dv = _dims(q, v, beta)
    ok = bool(q.ndim == 3 and dk % 128 == 0 and dv % 128 == 0
              and chunk in CHUNKS and (compiled or interpret))
    return ok, interpret


def _zero_at_first_chunk(ref):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        ref[...] = jnp.zeros(ref.shape, jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, f_ref,
                h_ref, *, scale):
    _zero_at_first_chunk(h_ref)
    h0 = h_ref[...]
    s_ref[...] = h0
    o, h_ref[...], f_ref[...] = chunk_fwd(
        _f32(q_ref[...]), _f32(k_ref[...]), _f32(v_ref[...]), g_ref[...],
        _col(b_ref[...]), h0, scale)
    o_ref[...] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, f_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dh_ref, *, scale):
    _zero_at_first_chunk(dh_ref)
    dq, dk, dv, dg, db, dh_ref[...] = chunk_bwd(
        _f32(q_ref[...]), _f32(k_ref[...]), _f32(v_ref[...]), g_ref[...],
        _col(b_ref[...]), s_ref[...], f_ref[...], _f32(do_ref[...]),
        dh_ref[...], scale)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    dg_ref[...] = dg
    db_ref[...] = _row(db)


def _specs(t, dk, dv, chunk, reverse):
    """BlockSpecs of the kernels' operands over grid (row, head, chunk)."""
    from jax.experimental import pallas as pl

    n = t // chunk

    def at(c):
        return n - 1 - c if reverse else c

    def rows(d):
        return pl.BlockSpec((None, chunk, d), lambda i, h, c: (i, at(c), h))

    def per_chunk(shape):
        return pl.BlockSpec((None, None, None) + shape,
                            lambda i, h, c: (i, h, at(c), 0, 0))

    return rows, per_chunk((1, chunk)), per_chunk((dv, dk)), per_chunk(
        (chunk, chunk))


def _beta_rows(beta, chunk):
    """beta [b, t, h] -> [b, h, n, 1, C] float32: a chunk's betas are one
    row of lanes (a block's last two dims are whole there)."""
    b = _f32(_pad_rows(beta, chunk))
    return b.transpose(0, 2, 1).reshape(
        b.shape[0], b.shape[2], -1, 1, chunk)


def _note(route):
    from ..monitor import flight

    flight.note_compile_count(route)


def _padded(q, k, v, g, beta, chunk, *more):
    """The operands padded to whole chunks, g float32, beta as lane rows,
    and (b, padded t, heads, dk, dv)."""
    heads, dk, dv = _dims(q, v, beta)
    q, k, v, g, *more = (_pad_rows(x, chunk)
                         for x in (q, k, v, _f32(g)) + tuple(more))
    return (q, k, v, g, _beta_rows(beta, chunk), *more), (
        q.shape[0], q.shape[1], heads, dk, dv)


def _walk_params(dims, chunk, interpret):
    """What every KDA pallas_call shares: grid (row, head, chunk), a [dv,
    dk] float32 carry in VMEM, the chunk axis walked in sequence."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    b, tp, heads, dk, dv = dims
    return dict(grid=(b, heads, tp // chunk),
                scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
                compiler_params=pltpu.CompilerParams(dimension_semantics=(
                    "parallel", "parallel", "arbitrary")),
                interpret=interpret)


def kda(q, k, v, g, beta, chunk=CHUNK, interpret=None):
    """(o [b, t, h*dv] in q's dtype, states [b, h, n, dv, dk] float32: each
    chunk's first state, transposed, factors [b, h, n, C, C] float32: each
    chunk's T and Aqk packed, `_pack`) of KDA over q, k [b, t, h*dk], v
    [b, t, h*dv], the log-decay g [b, t, h*dk] and beta [b, t, h]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    ok, interpret = _plan(q, v, beta, chunk, interpret)
    if not ok:
        _note("kda_sites_xla")
        return reference_kda(q, k, v, g, beta, chunk)
    _note("kda_sites_kernel")
    t = q.shape[1]
    args, dims = _padded(q, k, v, g, beta, chunk)
    b, tp, heads, dk, dv = dims
    rows, beta_spec, state, factor = _specs(tp, dk, dv, chunk, False)
    per_chunk = (b, heads, tp // chunk)
    o, states, packed = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=dk ** -0.5), name="kda_fwd",
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk), beta_spec],
        out_specs=[rows(dv), state, factor],
        out_shape=[jax.ShapeDtypeStruct((b, tp, heads * dv), q.dtype),
                   jax.ShapeDtypeStruct(per_chunk + (dv, dk), jnp.float32),
                   jax.ShapeDtypeStruct(per_chunk + (chunk, chunk),
                                        jnp.float32)],
        **_walk_params(dims, chunk, interpret))(*args)
    return o[:, :t], states, packed


def kda_bwd(q, k, v, g, beta, states, factors, do, chunk=CHUNK,
            interpret=None):
    """(dq, dk, dv in the operands' dtypes, dg [b, t, h*dk] float32, dbeta
    [b, t, h] float32) from the forward's operands, its chunk states and
    factors and the cotangent of o: the chunks walked in reverse."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    _note("kda_bwd_sites_kept")
    ok, interpret = _plan(q, v, beta, chunk, interpret)
    if not ok:
        return reference_kda_bwd(q, k, v, g, beta, states, factors, do,
                                 chunk)
    t = q.shape[1]
    (q, k, v, g, beta_rows, do), dims = _padded(
        q, k, v, g, beta, chunk, do.astype(v.dtype).reshape(v.shape))
    b, tp, heads, dk, dv = dims
    rows, beta_spec, state, factor = _specs(tp, dk, dv, chunk, True)
    dq, dk_, dv_, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=dk ** -0.5), name="kda_bwd",
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk), beta_spec, state,
                  factor, rows(dv)],
        out_specs=[rows(dk), rows(dk), rows(dv), rows(dk), beta_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, jnp.float32),
                   jax.ShapeDtypeStruct(beta_rows.shape, jnp.float32)],
        **_walk_params(dims, chunk, interpret))(
            q, k, v, g, beta_rows, states, factors, do)
    return (dq[:, :t], dk_[:, :t], dv_[:, :t], dg[:, :t],
            db.reshape(b, heads, tp)[:, :, :t].transpose(0, 2, 1))
