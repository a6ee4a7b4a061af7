"""Fused multi-table embedding kernels — the round-8 attack on the
DeepFM/CTR dispatch wall (PERF.md r05: 52.9k examples/s at 0.05% of the
HBM roofline, `"bound": "dispatch/gather-latency"` — the sparse tier is
hundreds of tiny gather/scatter/optimizer fusions, each paying launch
latency while moving ~KBs; reference analogue: lookup_table_op.h row
gathers + selected_rows_functor.h MergeAdd + the SparseAdamFunctor tier,
all per-table).

Three kernels over a TABLE GROUP — S same-shape `[V, D]` embedding tables
(DeepFM: 26 x [1e6+1, 10] plus 26 x [1e6+1, 1]) — composed by the
`fused_lookup_table` / `fused_sparse_{sgd,adam}` ops (gate:
FLAGS_fused_embedding):

1. `multi_table_gather` — ONE launch gathers every slot's rows.  The
   `[S, B]` int32 ids ride scalar memory via
   `pltpu.PrefetchScalarGridSpec` (available before the body runs); the
   S tables stay HBM-resident (`memory_space=ANY` — no relayout, no
   VMEM staging of 40 MB tables); the kernel issues one async row-DMA
   per (slot, row) into the `[S, block_rows, D]` VMEM output block,
   START-ALL-THEN-WAIT-ALL per slot so row fetches overlap and HBM
   latency amortizes across the in-flight window.  Output is
   `[S, B, D]`: each slot's `[B, D]` is a contiguous slice — consumers
   pay no transpose.

2. `multi_table_scatter_add` — the matching backward/update engine: ONE
   launch applies `table[id] += scale * row` across every table of the
   group.  Rows must be duplicate-free (`merge_slot_rows` first — the
   batched MergeAdd); sentinel ids (== V) mark the merged tail and are
   skipped via `pl.when` (the DMA-level analogue of scatter
   mode="drop").  Tables alias their outputs (`input_output_aliases`):
   touched rows update in place in HBM, O(K·D) traffic.

3. `multi_table_sparse_adam` — fused lazy-Adam apply: one launch DMAs
   each touched row of param/m1/m2 into VMEM scratch, computes the
   moment/param update vectorized on the VPU, and DMAs the three rows
   back — replacing the per-table sort + segment-sum + 2 gathers +
   3 scatters chains (~8 fusions x 52 tables on DeepFM).

Duplicate ids within a batch are the aliasing hazard: a gather/modify/
scatter pipeline would lose one contribution (both reads see the old
row).  Every apply therefore consumes MERGED rows — `merge_slot_rows` is
the vmapped MergeAdd (ONE batched argsort + ONE batched segment-sum for
all S slots, vs S of each per-table), bit-matching the per-table
`SelectedRows.merged()` that lazy Adam already requires for its
one-moment-update-per-row semantics.

Off-TPU: the GATHER runs under Pallas interpret mode (the DMA emulation
keeps the one-launch structure — the HLO dispatch census collapse is
visible on the CPU CI box, tools/hlo_diag.py --sparse), while the APPLY
entry points default to the merged XLA form (`_apply_off_tpu`: the
interpret emulation of the 3-tier RMW measured ~10 s of XLA CPU compile
per program for zero CPU benefit; pass interpret=True to drive the
kernel path off-TPU, as the kernel tests do).  Every entry point also
degrades to a per-table XLA composition (`*_xla`) when the group
doesn't fit the kernel contract (non-float tables, V beyond int32); the
XLA forms are the parity references in tests/test_fused_embedding.py.
"""

from __future__ import annotations

from .placement import resolve


def _cdiv(a, b):
    return -(-a // b)


# VMEM budget for the per-grid-step blocks (out / scratch / rows tiers);
# also bounds the in-flight DMA window (one row DMA per slot per row).
_VMEM_BUDGET_BYTES = 8 << 20


def _auto_block_rows(n_tiers, s_n, d, dtype, total_rows):
    """Rows per grid step such that n_tiers [S, block, D] VMEM blocks fit
    the budget (D pads to the 128-lane tile)."""
    import numpy as np

    lanes = max(d, 128)
    per_row = max(1, n_tiers) * s_n * lanes * np.dtype(dtype).itemsize
    block = _VMEM_BUDGET_BYTES // per_row
    block = max(8, min(512, block))
    # Mosaic blocks the row (sublane) dim in 8s unless the block spans
    # the whole array ("last two dimensions of your block shape are
    # divisible by 8 and 128 ... or be equal to the respective
    # dimensions of the overall array")
    block = total_rows if block >= total_rows else block // 8 * 8
    return int(block)


def _kernel_ok(tables, interpret=None):
    """Group contract for the Pallas path: float tables, int32-addressable
    rows, and — compiled — a lane-aligned row width.  Anything else takes
    the per-table XLA composition.

    Mosaic refuses to DMA-slice an HBM ref whose minor dim is not a
    multiple of 128 ("Slice shape along dimension 1 must be aligned to
    tiling (128), but is 10", libtpu 0.0.34), and a per-row DMA is the
    whole kernel — so DeepFM's [V, 10] / [V, 1] tables run XLA on the
    chip by design."""
    import jax.numpy as jnp

    t0 = tables[0]
    if t0.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        return False
    if t0.shape[0] >= 2**31 - 1:
        return False
    compiled, interp = resolve(interpret)
    if not (compiled or interp) or (compiled and t0.shape[1] % 128):
        return False
    return all(t.shape == t0.shape and t.dtype == t0.dtype for t in tables)


def _apply_off_tpu(interpret):
    """Whether a row-sparse APPLY should take the merged XLA form: the
    aliased in-place DMA kernel is the TPU win, and its interpret
    emulation (3 RMW tiers x S slots per loop body) costs ~10 s of XLA
    CPU compile per program (measured) for zero CPU benefit.  interpret
    default (None) -> XLA off-TPU; tests pass interpret=True to exercise
    the kernel path on the CPU box.  The GATHER keeps its interpret
    default — it is cheap to compile and carries the HLO census
    collapse."""
    return interpret is None and resolve(None)[1]


# ---------------------------------------------------------------------------
# batched MergeAdd (selected_rows_functor.h MergeAdd, vmapped over slots)
# ---------------------------------------------------------------------------


def merge_slot_rows(ids, rows, height):
    """Combine duplicate ids per slot: ids [S, K] int32, rows [S, K, D] ->
    (uids [S, K], mrows [S, K, D]) where each unique id appears once per
    slot with its row-summed value and unused tail slots hold the
    out-of-range sentinel `height` (dropped by scatter, gated off by the
    kernels).  vmap turns the per-table argsort + segment-sum chains into
    ONE batched sort and ONE batched segment-sum for the whole group;
    per-slot results are identical to SelectedRows.merged()."""
    import jax
    import jax.numpy as jnp

    k = ids.shape[1]

    def one(ids_s, rows_s):
        order = jnp.argsort(ids_s)
        sids = ids_s[order]
        srows = rows_s[order]
        is_start = jnp.concatenate(
            [jnp.ones((1,), bool), sids[1:] != sids[:-1]])
        seg = jnp.cumsum(is_start.astype("int32")) - 1
        mrows = jax.ops.segment_sum(srows, seg, num_segments=k)
        uids = jnp.full((k,), height, "int32").at[seg].set(sids)
        return uids, mrows

    return jax.vmap(one)(ids.astype("int32"), rows)


# ---------------------------------------------------------------------------
# multi-table gather
# ---------------------------------------------------------------------------


def multi_table_gather_xla(tables, ids):
    """Per-table reference composition (the flag-off math): S takes +
    stack.  Used off-contract and as the parity oracle."""
    import jax.numpy as jnp

    return jnp.stack(
        [jnp.take(t, ids[s], axis=0) for s, t in enumerate(tables)])


def multi_table_gather(tables, ids, *, block_rows=None, interpret=None):
    """One-launch gather: tables S x [V, D], ids [S, B] int32 ->
    [S, B, D] (slot s's batch is out[s] — a contiguous slice)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tables = list(tables)
    if not _kernel_ok(tables, interpret):
        return multi_table_gather_xla(tables, ids)
    s_n = len(tables)
    v, d = tables[0].shape
    b = ids.shape[1]
    block_rows = block_rows or _auto_block_rows(1, s_n, d, tables[0].dtype, b)
    block_rows = min(block_rows, b)
    ids = ids.astype(jnp.int32)

    def kernel(ids_ref, *refs):
        t_refs = refs[:s_n]
        out_ref = refs[s_n]
        sem = refs[s_n + 1]
        base = pl.program_id(0) * block_rows

        def row_copy(s, r):
            idx = ids_ref[s, base + r]
            return pltpu.make_async_copy(
                t_refs[s].at[pl.ds(idx, 1), :],
                out_ref.at[s, pl.ds(r, 1), :],
                sem,
            )

        # start-all-then-wait-all: every slot's row DMA for the block is
        # in flight before the first wait, so HBM latency amortizes over
        # the whole S x block_rows window instead of being paid per row.
        # ONE row loop with the slots unrolled inside (not a loop pair
        # per slot) also keeps the trace at two while-loops total — the
        # per-slot form compiled ~50 loops and was measured 2x slower to
        # BUILD on the CPU CI box.
        def start(r, _):
            @pl.when(base + r < b)
            def _():
                for s in range(s_n):
                    row_copy(s, r).start()
            return 0

        jax.lax.fori_loop(0, block_rows, start, 0)

        def wait(r, _):
            @pl.when(base + r < b)
            def _():
                for s in range(s_n):
                    row_copy(s, r).wait()
            return 0

        jax.lax.fori_loop(0, block_rows, wait, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(_cdiv(b, block_rows),),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * s_n,
        out_specs=pl.BlockSpec((s_n, block_rows, d),
                               lambda i, ids_ref: (0, i, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    return pl.pallas_call(
        kernel,
        name="embedding_gather_fwd",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, b, d), tables[0].dtype),
        interpret=resolve(interpret)[1],
    )(ids, *tables)


# ---------------------------------------------------------------------------
# multi-table scatter-add / fused sparse optimizer applies
# ---------------------------------------------------------------------------


def multi_table_scatter_add_xla(tables, uids, rows, scale):
    return [
        t.at[uids[s]].add((scale * rows[s]).astype(t.dtype), mode="drop")
        for s, t in enumerate(tables)
    ]


def _apply_pallas(tables_by_kind, uids, rows, scalars, compute,
                  block_rows, interpret):
    """Shared engine of the fused row-sparse applies.

    tables_by_kind: list of K lists of S tables (scatter-add: [params];
    adam: [params, m1s, m2s]) — every table aliases its output and
    updates in place.  uids [S, Kr] int32 MERGED ids (sentinel == V rows
    skipped); rows [S, Kr, D] merged update rows ride a VMEM block.
    scalars: 1-D f32 array of traced scalars, handed to `compute` from
    SMEM.  compute(scratches, rows_block, scalar_ref) -> writes the
    updated rows back into each kind's scratch block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kinds = len(tables_by_kind)
    s_n = len(tables_by_kind[0])
    v, d = tables_by_kind[0][0].shape
    kr = uids.shape[1]
    dtype = tables_by_kind[0][0].dtype
    # kinds scratch tiers + the merged-rows input block share the budget
    block_rows = block_rows or _auto_block_rows(kinds + 1, s_n, d, dtype, kr)
    block_rows = min(block_rows, kr)
    flat_tables = [t for kind in tables_by_kind for t in kind]

    def kernel(ids_ref, scalar_ref, *refs):
        rows_ref = refs[0]
        out_refs = refs[1 + kinds * s_n:1 + 2 * kinds * s_n]
        scratches = refs[1 + 2 * kinds * s_n:1 + 2 * kinds * s_n + kinds]
        sem = refs[-1]
        base = pl.program_id(0) * block_rows

        def row_copy(kind, s, r, to_hbm):
            idx = ids_ref[s, base + r]
            hbm = out_refs[kind * s_n + s].at[pl.ds(idx, 1), :]
            vmem = scratches[kind].at[s, pl.ds(r, 1), :]
            return pltpu.make_async_copy(
                vmem if to_hbm else hbm, hbm if to_hbm else vmem, sem)

        def valid(s, r):
            # in-bounds row of a real (non-sentinel) merged id; the
            # sentinel gate is the DMA analogue of mode="drop".  The id
            # read is clamped: logical_and evaluates both sides, so an
            # unclamped read would index SMEM out of bounds on the
            # padded tail of the last grid block.
            idx = ids_ref[s, jnp.minimum(base + r, kr - 1)]
            return jnp.logical_and(base + r < kr, idx < v)

        # Phase structure (slots unrolled INSIDE one row loop per phase —
        # two while-loops per DMA phase total, see multi_table_gather):
        # gather every touched row of every table into VMEM, update the
        # whole [S, block, D] tier vectorized on the VPU, write back.
        def phase(to_hbm):
            def start(r, _):
                for s in range(s_n):
                    @pl.when(valid(s, r))
                    def _(s=s):
                        for kind in range(kinds):
                            row_copy(kind, s, r, to_hbm).start()
                return 0

            jax.lax.fori_loop(0, block_rows, start, 0)

            def wait(r, _):
                for s in range(s_n):
                    @pl.when(valid(s, r))
                    def _(s=s):
                        for kind in range(kinds):
                            row_copy(kind, s, r, to_hbm).wait()
                return 0

            jax.lax.fori_loop(0, block_rows, wait, 0)

        phase(to_hbm=False)
        # rows of sentinel/garbage lanes are computed but never written
        compute(scratches, rows_ref, scalar_ref)
        phase(to_hbm=True)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(_cdiv(kr, block_rows),),
        in_specs=(
            [pl.BlockSpec(memory_space=pltpu.SMEM)]  # traced scalars
            + [pl.BlockSpec((s_n, block_rows, d),
                            lambda i, ids_ref: (0, i, 0))]  # merged rows
            + [pl.BlockSpec(memory_space=pl.ANY)] * (kinds * s_n)
        ),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (kinds * s_n),
        scratch_shapes=(
            [pltpu.VMEM((s_n, block_rows, d), dtype)] * kinds
            + [pltpu.SemaphoreType.DMA]
        ),
    )
    outs = pl.pallas_call(
        kernel,
        name="embedding_scatter_bwd",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((v, d), t.dtype)
                   for t in flat_tables],
        # inputs: 0 uids (prefetch), 1 scalars, 2 rows, 3.. the tables —
        # each table buffer IS its output (in-place HBM row updates)
        input_output_aliases={3 + i: i for i in range(kinds * s_n)},
        interpret=resolve(interpret)[1],
    )(uids, scalars, rows.astype(dtype), *flat_tables)
    return [outs[k * s_n:(k + 1) * s_n] for k in range(kinds)]


def multi_table_scatter_add(tables, uids, rows, scale, *, block_rows=None,
                            interpret=None):
    """One-launch `table[uid] += scale * row` over the whole group.
    uids/rows MUST be merged (duplicate-free per slot) — merge_slot_rows.
    scale is a traced scalar (the backward passes +1, sparse SGD -lr)."""
    import jax.numpy as jnp

    tables = list(tables)
    if not _kernel_ok(tables, interpret) or _apply_off_tpu(interpret):
        return multi_table_scatter_add_xla(tables, uids, rows, scale)
    dtype = tables[0].dtype

    def compute(scratches, rows_block, scalar_ref):
        scratches[0][...] = (
            scratches[0][...]
            + scalar_ref[0].astype(dtype) * rows_block[...].astype(dtype))

    scalars = jnp.asarray(scale, jnp.float32).reshape(1)
    (out,) = _apply_pallas([tables], uids, rows, scalars, compute,
                           block_rows, interpret)
    return list(out)


def multi_table_sparse_sgd(params, uids, rows, lr, **kw):
    """Fused row-sparse SGD: params[uid] -= lr * row, one launch for the
    group (sgd_op.h SelectedRows kernel, multi-table)."""
    return multi_table_scatter_add(params, uids, rows, -lr, **kw)


def multi_table_sparse_adam_xla(params, m1s, m2s, uids, mrows, lr_t,
                                beta1, beta2, epsilon):
    """Per-table reference: identical math to ops/optimizer_ops._adam_one's
    sparse branch on pre-merged rows."""
    import jax.numpy as jnp

    p_out, m1_out, m2_out = [], [], []
    for s, (p, m1, m2) in enumerate(zip(params, m1s, m2s)):
        grows = mrows[s].astype(p.dtype)
        u = uids[s]
        m1r = beta1 * jnp.take(m1, u, axis=0, mode="clip") + (1 - beta1) * grows
        m2r = beta2 * jnp.take(m2, u, axis=0, mode="clip") + (
            1 - beta2) * jnp.square(grows)
        step = lr_t * m1r / (jnp.sqrt(m2r) + epsilon)
        p_out.append(p.at[u].add(-step, mode="drop"))
        m1_out.append(m1.at[u].set(m1r, mode="drop"))
        m2_out.append(m2.at[u].set(m2r, mode="drop"))
    return p_out, m1_out, m2_out


def multi_table_sparse_adam(params, m1s, m2s, uids, mrows, lr_t, beta1,
                            beta2, epsilon, *, block_rows=None,
                            interpret=None):
    """Fused lazy-Adam apply: ONE launch updates param + both moments on
    every touched row of every table in the group (adam_op.h
    SparseAdamFunctor lazy mode, multi-table).  uids/mrows merged; lr_t
    is the bias-corrected rate lr*sqrt(1-b2^t)/(1-b1^t) (traced)."""
    import jax.numpy as jnp

    params, m1s, m2s = list(params), list(m1s), list(m2s)
    if (not all(_kernel_ok(t, interpret) for t in (params, m1s, m2s))
            or _apply_off_tpu(interpret)):
        return multi_table_sparse_adam_xla(
            params, m1s, m2s, uids, mrows, lr_t, beta1, beta2, epsilon)
    dtype = params[0].dtype
    # betas/eps are static op attrs: kept as Python floats so they inline
    # as kernel constants (a jnp scalar would be a captured traced const,
    # which pallas_call rejects)
    b1, b2, eps = float(beta1), float(beta2), float(epsilon)

    def compute(scratches, rows_block, scalar_ref):
        p_s, m1_s, m2_s = scratches
        g = rows_block[...].astype(dtype)
        m1n = b1 * m1_s[...] + (1 - b1) * g
        m2n = b2 * m2_s[...] + (1 - b2) * g * g
        lr = scalar_ref[0].astype(dtype)
        p_s[...] = p_s[...] - lr * m1n / (jnp.sqrt(m2n) + eps)
        m1_s[...] = m1n
        m2_s[...] = m2n

    scalars = jnp.asarray(lr_t, jnp.float32).reshape(1)
    p_out, m1_out, m2_out = _apply_pallas(
        [params, m1s, m2s], uids, mrows, scalars, compute, block_rows,
        interpret)
    return list(p_out), list(m1_out), list(m2_out)
