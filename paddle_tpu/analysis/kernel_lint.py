"""Pallas plan linter: static audit of every kernel plan in kernels/.

The kernel plan gates (_plan/_dot_plan/_auto_block_rows) decide
per-shape whether a Pallas kernel launches or the XLA fallback runs.  On
the CPU CI box those gates run in interpret mode, where Mosaic's real
constraints (lane/sublane tile alignment, VMEM capacity, aliasing) are
emulated away — PR 7/8 shipped kernels whose aliasing and revisited-block
accumulation invariants were "asserted only in interpret until a chip
run".  This linter closes that gap statically: it calls the REAL plan
gates under a pretended-TPU backend over the canonical model shape
matrix, then re-validates every accepted plan with independent
arithmetic:

  * grid/block divisibility (t % block == 0, rows % block_r == 0)
  * (8,128)/dtype tile alignment: lane blocks % 128, sublane blocks % 8
    (fp32) / % 16 (sub-4-byte dtypes); Mosaic dynamic-slice offsets on
    the lane dim need 128-aligned blocks
  * VMEM working set — recomputed here, NOT read from the gate, so a
    gate that under-estimates is itself caught — counted the way Mosaic
    allocates it (_tile_bytes/_vmem_use: sub-128 minor dims pad to 128
    lanes, second-minor dims to the dtype's sublane quantum, blocks
    whose window moves with the grid are double-buffered, whole-array
    blocks and scratch are held once) against the limit the kernel
    actually requests (`vmem_limit_bytes`, else the 16 MiB default
    scope)
  * input_output_aliases validity (embedding applies: every aliased
    table's shape/dtype must equal its output)
  * revisited-block accumulation: outputs revisited across grid steps
    (conv_bn stats tiles) must accumulate in f32

Every check function takes the CONFIG + the PLAN as data, so the
red-gate tests can feed a fabricated bad plan and assert the linter
names it (tests/test_static_analysis.py).

The matrices agree with the chip: every must_accept row compiled with
interpret=False on a TPU v5e and matched its reference (chip_smoke.py's
kernel leg walks these same rows), and a row Mosaic refused carries the
compiler's words in `mosaic_refusal` with must_accept=False — a gate
that re-accepts such a geometry is a finding, because the caller would
crash at compile time instead of falling back.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Tuple

import numpy as np

from .verifier import Finding


def _np_dtype(d) -> np.dtype:
    """np.dtype that also resolves 'bfloat16'/'float8*' via ml_dtypes
    (a jax dependency)."""
    try:
        return np.dtype(d)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, str(d)))

# hardware model (TPU v5e): the scoped-VMEM limit a kernel gets when it
# passes no vmem_limit_bytes, and the alignment the Mosaic lowering
# actually enforces
_SCOPED_VMEM_DEFAULT = 16 * 1024 * 1024
_LANE = 128


def _sublane(dtype) -> int:
    return 16 if _np_dtype(dtype).itemsize < 4 else 8


def _tile_bytes(shape, dtype) -> int:
    """Bytes one VMEM buffer of `shape` occupies: the minor dim pads to
    128 lanes and the second-minor to the dtype's sublane quantum (a
    [t, 8, 64] f32 tile costs what [t, 8, 128] does)."""
    shape = [int(d) for d in shape]
    if len(shape) < 2:
        shape = [1] * (2 - len(shape)) + shape
    sub = _sublane(dtype)
    lead = int(np.prod(shape[:-2], dtype=np.int64))
    rows = -(-shape[-2] // sub) * sub
    lanes = -(-shape[-1] // _LANE) * _LANE
    return lead * rows * lanes * _np_dtype(dtype).itemsize


def _vmem_use(blocked=(), held=()) -> int:
    """VMEM one launch allocates: `blocked` (shape, dtype) windows move
    with the grid, so the pipeline double-buffers them; `held` ones
    (whole-array blocks, constant-index accumulators, scratch,
    tile-sized body temporaries) exist once."""
    return (2 * sum(_tile_bytes(*b) for b in blocked)
            + sum(_tile_bytes(*h) for h in held))


def _check_refused(cfg, accepted, fam, findings) -> bool:
    """A geometry the compiler refused on the chip must stay rejected by
    its gate (the caller has no fallback past a compile error).  Returns
    True when the row is such a refusal (nothing else to audit)."""
    why = cfg.get("mosaic_refusal")
    if why and accepted:
        findings.append(_finding(
            "kernel-plan-accepts-refused",
            f"plan gate accepts a geometry Mosaic refused on the chip "
            f"({why})", fam, cfg["label"]))
    return bool(why)


@contextlib.contextmanager
def _pretend_tpu():
    """Run a plan gate as if jax.default_backend() were 'tpu', so the
    compiled-mode branches (alignment snapping, VMEM gating) execute on
    the CPU CI box.  The gates only read the backend NAME — no device is
    touched."""
    import jax

    real = jax.default_backend

    def fake(*a, **k):
        return "tpu"

    jax.default_backend = fake
    try:
        yield
    finally:
        jax.default_backend = real


def _spec(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), _np_dtype(dtype))


def _finding(check, msg, family, label):
    return Finding(check, "error", f"[{family}:{label}] {msg}",
                   op_type=family, var=label)


# ---------------------------------------------------------------------------
# per-family checks: (config, plan) -> findings.  Pure data in, data out —
# the red-gate fabricates bad plans through these same functions.
# ---------------------------------------------------------------------------


def check_attention_plan(cfg: dict, ok, block_q, block_k, interpret,
                         findings: List[Finding]):
    """Validate an (accepted) flash-attention plan for compiled TPU mode."""
    fam, label = "attention", cfg["label"]
    b, h, t, d = cfg["b"], cfg["h"], cfg["t"], cfg["d"]
    esize = _np_dtype(cfg["dtype"]).itemsize
    if cfg.get("must_accept", True) and not ok:
        findings.append(_finding(
            "kernel-plan-reject",
            f"plan gate rejects the canonical shape b={b} h={h} t={t} "
            f"d={d} {cfg['dtype']} (fmt {cfg['fmt']}) — the model would "
            f"silently run the XLA fallback", fam, label))
        return
    if not ok:
        return
    if t % block_q or t % block_k:
        findings.append(_finding(
            "kernel-grid-divisibility",
            f"blocks ({block_q},{block_k}) do not divide t={t}", fam,
            label))
    if d % 64:
        findings.append(_finding(
            "kernel-misaligned-block",
            f"head dim {d} is not a multiple of 64 (MXU lane occupancy)",
            fam, label))
    if cfg.get("mask"):
        # the masked walks take a tile to lie in one half of [noisy ;
        # clean] and the halves to be whole blocks
        length, offset = cfg["mask"]
        if (t != 2 * offset or offset % length or offset % block_q
                or offset % block_k):
            findings.append(_finding(
                "kernel-grid-divisibility",
                f"blocks ({block_q},{block_k}) or block length {length} "
                f"do not divide the half {offset} of t={t}", fam, label))
    if not interpret and (block_q % _LANE or block_k % _LANE):
        # backward kernels dynamic-slice lse/delta on the lane dim by
        # block_q and kv tiles by block_k: Mosaic needs 128-aligned blocks
        findings.append(_finding(
            "kernel-misaligned-block",
            f"compiled-mode blocks ({block_q},{block_k}) are not "
            f"128-lane aligned (Mosaic dynamic-slice constraint)", fam,
            label))
    dt = cfg["dtype"]
    if cfg["fmt"] == "bthd":
        # whole-head kv tiles [block, h, d]: the plan gate caps blocks so
        # the bwd working set fits; re-check with its own arithmetic
        kv_tile = block_k * h * d * esize
        if kv_tile > 256 * 1024:
            findings.append(_finding(
                "kernel-vmem-budget",
                f"bthd kv tile block_k*h*d = {kv_tile} bytes exceeds the "
                f"256 KB per-tile bound the bwd kernel compiles under",
                fam, label))
        seq, kv, plane = (t, h, d), (block_k, h, d), (h, block_q, block_k)
        stat = (h, t)
    else:
        seq, kv, plane = (t, d), (block_k, d), (block_q, block_k)
        stat = (8, t)  # lse/delta ride 8 replicated sublanes
    # worst launch (the dkv walk): q + do pinned per grid row, lse +
    # delta rows, k/v in + dk/dv out tiles — every window moves with
    # the grid — plus the s / p / dp score planes of the body
    used = _vmem_use(
        blocked=[(seq, dt)] * 2 + [(stat, "float32")] * 2 + [(kv, dt)] * 4,
        held=[(plane, "float32")] * 3)
    if used > _SCOPED_VMEM_DEFAULT:
        findings.append(_finding(
            "kernel-vmem-budget",
            f"dkv-walk working set {used} bytes (double-buffered, "
            f"lane-padded) exceeds the {_SCOPED_VMEM_DEFAULT}-byte "
            f"default scoped VMEM the kernel runs under", fam, label))


def check_conv_bn_plan(cfg: dict, plan, findings: List[Finding]):
    """conv_bn channel_stats / scale_shift_act tiling plan (a _Plan
    object or None), or the dot_col_stats (block_m, block_n, interp)
    tuple when cfg['kind'] == 'dot'."""
    fam, label = "conv_bn", cfg["label"]
    sub = _sublane(cfg["dtype"])
    if cfg.get("must_accept", True) and plan is None:
        findings.append(_finding(
            "kernel-plan-reject",
            f"plan gate rejects the canonical shape rows={cfg['rows']} "
            f"c={cfg['c']} {cfg['dtype']}", fam, label))
        return
    if plan is None:
        return
    if cfg.get("kind") == "dot":
        block_m, block_n, _ = plan
        m, oc = cfg["rows"], cfg["c"]
        bad_div = m % block_m or oc % block_n
        bad_align = block_m % sub or block_n % _LANE
        rows, ncols, block_r, block_c = m, oc, block_m, block_n
    else:
        rows, ncols = plan.rows, plan.ncols
        block_r, block_c = plan.block_r, plan.block_c
        bad_div = rows % block_r or ncols % block_c
        bad_align = block_r % sub or block_c % _LANE
        if plan.fold > 1 and (_LANE % cfg["c"]
                              or (cfg["rows"] * cfg["c"]) % _LANE):
            findings.append(_finding(
                "kernel-misaligned-block",
                f"lane fold {plan.fold} is invalid for c={cfg['c']} "
                f"(needs 128 %% c == 0 and rows*c %% 128 == 0)", fam,
                label))
    if bad_div:
        findings.append(_finding(
            "kernel-grid-divisibility",
            f"blocks ({block_r},{block_c}) do not divide "
            f"[{rows},{ncols}]", fam, label))
    if bad_align:
        findings.append(_finding(
            "kernel-misaligned-block",
            f"blocks ({block_r},{block_c}) violate ({sub},{_LANE}) "
            f"sublane/lane tiling for {cfg['dtype']}", fam, label))
    # stats tile is an (8, block_c) f32 output revisited on every M step
    if cfg.get("stats_dtype", "float32") != "float32":
        findings.append(_finding(
            "kernel-accum-dtype",
            f"revisited stats accumulator dtype "
            f"{cfg.get('stats_dtype')} != float32", fam, label))
    # worst launch (the scale_shift_act backward): g, x, out in + dx,
    # dres out tiles and the scale/shift + stats rows, all moving
    used = _vmem_use(
        blocked=[((block_r, block_c), cfg["dtype"])] * 5
        + [((8, block_c), "float32")] * 2)
    if used > _SCOPED_VMEM_DEFAULT:
        findings.append(_finding(
            "kernel-vmem-budget",
            f"five double-buffered [{block_r},{block_c}] tiles + stats "
            f"rows = {used} bytes exceed the default scoped VMEM", fam,
            label))


def check_dropout_plan(cfg: dict, ok, rows, ncols, block_r, interpret,
                       hw_prng, findings: List[Finding]):
    fam, label = "dropout_epilogue", cfg["label"]
    sub = _sublane(cfg["dtype"])
    if cfg.get("must_accept", True) and not ok:
        findings.append(_finding(
            "kernel-plan-reject",
            f"plan gate rejects the canonical shape {cfg['shape']} "
            f"{cfg['dtype']}", fam, label))
        return
    if not ok:
        return
    if ncols % _LANE or block_r % sub:
        findings.append(_finding(
            "kernel-misaligned-block",
            f"[{block_r},{ncols}] violates ({sub},{_LANE}) tiling", fam,
            label))
    if rows % block_r:
        findings.append(_finding(
            "kernel-grid-divisibility",
            f"block_r={block_r} does not divide rows={rows}", fam, label))
    if rows * ncols >= 2 ** 32:
        findings.append(_finding(
            "kernel-rng-wrap",
            f"mask plane {rows}x{ncols} wraps the uint32 hash index — "
            f"mask bits repeat", fam, label))
    used = _vmem_use(blocked=[((block_r, ncols), cfg["dtype"])] * 3)
    if used > _SCOPED_VMEM_DEFAULT:
        findings.append(_finding(
            "kernel-vmem-budget",
            f"x, residual and out [{block_r},{ncols}] tiles, "
            f"double-buffered, = {used} bytes exceed the default scoped "
            f"VMEM", fam, label))


def check_short_conv_plan(cfg: dict, ok, rows, chunk, interpret,
                          findings: List[Finding]):
    """Gated short convolution plan (kernels/short_conv.py _plan): time
    blocks of whole rows [rows, 3d] with 16-row halos, worked through in
    channel chunks; the backward holds X, dOut and dX blocks at once."""
    fam, label = "short_conv", cfg["label"]
    t, d = cfg["t"], cfg["d"]
    if cfg.get("must_accept", True) and not ok:
        findings.append(_finding(
            "kernel-plan-reject",
            f"plan gate rejects the canonical shape t={t} d={d} "
            f"{cfg['dtype']} — the model would silently run the XLA "
            f"composition", fam, label))
    if not ok:
        return
    if t % rows or rows % 16 or d % chunk or chunk % _LANE:
        findings.append(_finding(
            "kernel-grid-divisibility",
            f"rows={rows} / chunk={chunk} do not tile t={t}, d={d} in "
            f"(16,{_LANE}) tiles", fam, label))
    dt = cfg["dtype"]
    used = _vmem_use(
        blocked=[((rows, 3 * d), dt)] * 2 + [((rows, d), dt)]
        + [((16, d), dt)] * 4 + [((8, d), "float32")] * 2)
    if used > _SCOPED_VMEM_DEFAULT:
        findings.append(_finding(
            "kernel-vmem-budget",
            f"the backward's X, dX [{rows},{3 * d}] and dOut [{rows},{d}] "
            f"blocks with their halos, double-buffered, = {used} bytes "
            f"exceed the default scoped VMEM", fam, label))


def check_decode_plan(cfg: dict, ok, block_t, interpret,
                      findings: List[Finding]):
    """Flash-decode plan (kernels/decode_attention.py _decode_plan):
    single-query attention over the [b, max_t, h, dh] cache with
    scalar-prefetched lengths."""
    from ..kernels import decode_attention as kda

    fam, label = "decode_attention", cfg["label"]
    b, h, dh, max_t = cfg["b"], cfg["h"], cfg["dh"], cfg["max_t"]
    sub = _sublane(cfg["dtype"])
    if cfg.get("must_accept", True) and not ok:
        findings.append(_finding(
            "kernel-plan-reject",
            f"plan gate rejects the canonical cache shape b={b} h={h} "
            f"dh={dh} max_t={max_t} {cfg['dtype']} — decode would "
            f"silently run the XLA fallback and read the whole cache "
            f"instead of length-bounded blocks", fam, label))
        return
    if not ok:
        return
    if max_t % block_t:
        findings.append(_finding(
            "kernel-grid-divisibility",
            f"block_t={block_t} does not divide max_t={max_t} (the "
            f"length-masked tail must be the only partial block)", fam,
            label))
    if dh % 64:
        findings.append(_finding(
            "kernel-misaligned-block",
            f"head dim {dh} is not a multiple of 64 (dh is the lane dim "
            f"of every decode tile)", fam, label))
    if h % sub:
        findings.append(_finding(
            "kernel-misaligned-block",
            f"n_head {h} violates the {sub}-sublane tiling of the "
            f"in-register [h, t, d] view for {cfg['dtype']}", fam, label))
    _check_decode_vmem(cfg, block_t, kda._VMEM_LIMIT, fam, findings)


def _check_decode_vmem(cfg, block_t, limit, fam, findings):
    """Independent working-set re-estimate shared by the ring and the
    paged walk: the pipelined k and v [block_t, h, dh] tiles plus the
    body's tile-sized f32 temporaries (promoted k/v, k*q, p*v) against
    the limit the kernel requests — a gate that under-estimates is
    itself caught."""
    tile = (block_t, cfg["h"], cfg["dh"])
    used = _vmem_use(blocked=[(tile, cfg["dtype"])] * 2,
                     held=[(tile, "float32")] * 4)
    if used > limit:
        findings.append(_finding(
            "kernel-vmem-budget",
            f"decode working set {used} bytes (double-buffered, "
            f"lane-padded) exceeds the {limit}-byte vmem_limit_bytes the "
            f"kernel requests", fam, cfg["label"]))


def _megastep_vmem(cfg, bt, cbt, fuse_ffn):
    """What one megastep launch allocates: the resident weights enter as
    whole-array blocks (held once), every [t, h, dh] walk scratch is
    lane/sublane padded, and each walk keeps promoted + transposed f32
    copies of its k and v tile."""
    dm, h, dh, di = cfg["dm"], cfg["h"], cfg["dh"], cfg["di"]
    dt = cfg["dtype"]
    hd = h * dh
    walk = max(bt, cbt)
    held = [((dm, 3 * hd), dt), ((hd, dm), dt), ((dm, hd), dt),
            ((hd, dm), dt), ((h, dh), "float32"), ((2, h, dh), dt),
            ((bt, h, dh), dt), ((bt, h, dh), dt), ((cbt, h, dh), dt),
            ((cbt, h, dh), dt)] + [((h, walk, dh), "float32")] * 4
    if fuse_ffn:
        held += [((dm, di), dt), ((di, dm), dt)]
    return _vmem_use(held=held)


def check_megastep_plan(cfg: dict, plan, findings: List[Finding]):
    """Fused decode megastep plan (kernels/decode_step.py
    _megastep_plan): one whole decoder layer per launch — weights
    resident in VMEM, both walks block-DMA'd, the cache row written in
    place through input_output_aliases."""
    from ..kernels import decode_step as kds

    fam, label = "decode_step", cfg["label"]
    dm, h, dh, di = cfg["dm"], cfg["h"], cfg["dh"], cfg["di"]
    max_t, cross_t = cfg["max_t"], cfg["cross_t"]
    sub = _sublane(cfg["dtype"])
    if _check_refused(cfg, plan.ok, fam, findings):
        return
    if cfg.get("must_accept", True) and not plan.ok:
        findings.append(_finding(
            "kernel-plan-reject",
            f"plan gate rejects the canonical layer shape dm={dm} h={h} "
            f"dh={dh} di={di} max_t={max_t} cross_t={cross_t} "
            f"{cfg['dtype']} — decode would silently run the composed "
            f"XLA fallback and the per-token launch count stays at the "
            f"unfused wall", fam, label))
        return
    if not plan.ok:
        return
    if "expect_fuse_ffn" in cfg and plan.fuse_ffn != cfg["expect_fuse_ffn"]:
        findings.append(_finding(
            "kernel-fusion-mode",
            f"plan fuses the FFN={plan.fuse_ffn}, expected "
            f"{cfg['expect_fuse_ffn']} — the launch-count story this "
            f"shape was accepted under no longer holds", fam, label))
    if max_t % plan.block_t or cross_t % plan.cross_block_t:
        findings.append(_finding(
            "kernel-grid-divisibility",
            f"blocks ({plan.block_t},{plan.cross_block_t}) do not divide "
            f"(max_t={max_t}, cross_t={cross_t})", fam, label))
    if dh % 64 or dm % _LANE or di % _LANE:
        findings.append(_finding(
            "kernel-misaligned-block",
            f"dh {dh} %% 64 or dm {dm} %% 128 or di {di} %% 128 "
            f"misaligned (lane dims of the resident weight tiles)", fam,
            label))
    if h % sub:
        findings.append(_finding(
            "kernel-misaligned-block",
            f"n_head {h} violates the {sub}-sublane tiling of the "
            f"[h, t, d] walk views for {cfg['dtype']}", fam, label))
    if plan.block_t % 8 or plan.cross_block_t % 8:
        findings.append(_finding(
            "kernel-misaligned-block",
            f"blocks ({plan.block_t},{plan.cross_block_t}) are not "
            f"8-sublane aligned", fam, label))
    # independent working-set re-estimate vs the limit the launch
    # requests — the FFN weights count when the plan claims they fit
    used = _megastep_vmem(cfg, plan.block_t, plan.cross_block_t,
                          plan.fuse_ffn)
    if used > kds._VMEM_LIMIT:
        findings.append(_finding(
            "kernel-vmem-budget",
            f"megastep working set {used} bytes (lane-padded) exceeds "
            f"the {kds._VMEM_LIMIT}-byte vmem_limit_bytes the launch "
            f"requests (fuse_ffn={plan.fuse_ffn})", fam, label))


def check_paged_decode_plan(cfg: dict, ok, block_t, interpret,
                            findings: List[Finding]):
    """Paged flash-decode plan (kernels/decode_attention.py
    _paged_plan): single-query attention walking [num_blocks, block_t,
    h, dh] pool tiles at scalar-prefetched block-table addresses.
    block_t is fixed by the pool geometry — a misaligned pool must
    REJECT (no snapping), and an accepted table must fit the SMEM
    scalar-prefetch cap."""
    from ..kernels import decode_attention as kda

    fam, label = "paged_decode_attention", cfg["label"]
    b, h, dh = cfg["b"], cfg["h"], cfg["dh"]
    bt, mb = cfg["block_t"], cfg["max_blocks"]
    sub = _sublane(cfg["dtype"])
    if cfg.get("must_accept", True) and not ok:
        findings.append(_finding(
            "kernel-plan-reject",
            f"plan gate rejects the canonical pool shape b={b} h={h} "
            f"dh={dh} block_t={bt} max_blocks={mb} {cfg['dtype']} — "
            f"paged decode would silently gather the whole pool through "
            f"the XLA fallback", fam, label))
        return
    if not cfg.get("must_accept", True) and ok:
        findings.append(_finding(
            "kernel-plan-reject",
            f"plan gate ACCEPTS an off-contract pool (block_t={bt}, "
            f"b*max_blocks={b * mb}) it is required to reject — the "
            f"kernel would DMA misaligned tiles or overflow the SMEM "
            f"table", fam, label))
        return
    if not ok:
        return
    if block_t % 8 or dh % 64 or h % sub:
        findings.append(_finding(
            "kernel-misaligned-block",
            f"accepted plan violates tiling: block_t={block_t} %% 8, "
            f"dh={dh} %% 64 or n_head={h} %% {sub}", fam, label))
    if b * mb > kda._PAGED_TABLE_CAP:
        findings.append(_finding(
            "kernel-vmem-budget",
            f"accepted table {b}x{mb} exceeds the "
            f"{kda._PAGED_TABLE_CAP}-entry scalar-prefetch cap the gate "
            f"claims to enforce", fam, label))
    _check_decode_vmem(cfg, block_t, kda._VMEM_LIMIT, fam, findings)


def check_paged_megastep_plan(cfg: dict, plan, findings: List[Finding]):
    """Paged fused decode megastep plan (kernels/decode_step.py
    _paged_megastep_plan): the ring megastep's contract plus both
    flattened block tables under the scalar-prefetch cap; walk blocks
    are fixed by the pool geometry (reject, never snap)."""
    from ..kernels import decode_attention as kda
    from ..kernels import decode_step as kds

    fam, label = "paged_decode_step", cfg["label"]
    dm, h, dh, di = cfg["dm"], cfg["h"], cfg["dh"], cfg["di"]
    bt, cbt = cfg["block_t"], cfg["cross_block_t"]
    b, mb, cmb = cfg["b"], cfg["max_blocks"], cfg["cross_max_blocks"]
    sub = _sublane(cfg["dtype"])
    if _check_refused(cfg, plan.ok, fam, findings):
        return
    if cfg.get("must_accept", True) and not plan.ok:
        findings.append(_finding(
            "kernel-plan-reject",
            f"plan gate rejects the canonical paged layer shape dm={dm} "
            f"h={h} dh={dh} di={di} block_t={bt} cross_block_t={cbt} "
            f"tables {b}x{mb}/{b}x{cmb} {cfg['dtype']} — decode falls "
            f"back to the per-op launch storm the megastep exists to "
            f"collapse", fam, label))
        return
    if not cfg.get("must_accept", True) and plan.ok:
        findings.append(_finding(
            "kernel-plan-reject",
            f"plan gate ACCEPTS an off-contract paged layer (block_t="
            f"{bt}, tables {b * mb}/{b * cmb} entries) it is required "
            f"to reject", fam, label))
        return
    if not plan.ok:
        return
    if "expect_fuse_ffn" in cfg and plan.fuse_ffn != cfg["expect_fuse_ffn"]:
        findings.append(_finding(
            "kernel-fusion-mode",
            f"plan fuses the FFN={plan.fuse_ffn}, expected "
            f"{cfg['expect_fuse_ffn']}", fam, label))
    if plan.block_t % 8 or plan.cross_block_t % 8:
        findings.append(_finding(
            "kernel-misaligned-block",
            f"blocks ({plan.block_t},{plan.cross_block_t}) are not "
            f"8-sublane aligned", fam, label))
    if dh % 64 or dm % _LANE or di % _LANE or h % sub:
        findings.append(_finding(
            "kernel-misaligned-block",
            f"dh {dh} %% 64, dm {dm} %% 128, di {di} %% 128 or n_head "
            f"{h} %% {sub} misaligned", fam, label))
    if b * mb > kda._PAGED_TABLE_CAP or b * cmb > kda._PAGED_TABLE_CAP:
        findings.append(_finding(
            "kernel-vmem-budget",
            f"accepted tables {b}x{mb}/{b}x{cmb} exceed the "
            f"{kda._PAGED_TABLE_CAP}-entry scalar-prefetch cap", fam,
            label))
    used = _megastep_vmem(cfg, plan.block_t, plan.cross_block_t,
                          plan.fuse_ffn)
    if used > kds._VMEM_LIMIT:
        findings.append(_finding(
            "kernel-vmem-budget",
            f"paged megastep working set {used} bytes (lane-padded) "
            f"exceeds the {kds._VMEM_LIMIT}-byte vmem_limit_bytes the "
            f"launch requests (fuse_ffn={plan.fuse_ffn})", fam, label))


def check_embedding_group(cfg: dict, block_rows: int,
                          findings: List[Finding], accepted: bool = True):
    """Fused multi-table gather/apply group: the compiled-mode gate
    (`accepted` = embedding._kernel_ok under a pretended TPU), alias
    validity and the VMEM blocks the gate sizes."""
    fam, label = "embedding", cfg["label"]
    specs = cfg["tables"]  # list of (shape, dtype) per table
    t0_shape, t0_dtype = specs[0]
    if _check_refused(cfg, accepted, fam, findings):
        return
    if cfg.get("must_accept", True) and not accepted:
        findings.append(_finding(
            "kernel-plan-reject",
            f"group gate rejects {len(specs)} x {t0_shape} {t0_dtype} "
            f"tables — the sparse tier would silently run the per-table "
            f"XLA composition", fam, label))
        return
    if not accepted:
        return
    # input_output_aliases maps table input i -> output i verbatim: every
    # aliased pair must agree in shape AND dtype or the in-place HBM row
    # DMA writes through a mis-sized buffer
    for i, (shape, dtype) in enumerate(specs):
        if tuple(shape) != tuple(t0_shape) or _np_dtype(dtype) != \
                _np_dtype(t0_dtype):
            findings.append(_finding(
                "kernel-alias-mismatch",
                f"table {i} ({shape}, {dtype}) differs from table 0 "
                f"({t0_shape}, {t0_dtype}): input_output_aliases would "
                f"alias mismatched buffers", fam, label))
    if _np_dtype(t0_dtype).kind != "f":
        findings.append(_finding(
            "kernel-alias-mismatch",
            f"non-float table dtype {t0_dtype} on the aliased kernel "
            f"path (contract: float tables only)", fam, label))
    if t0_shape[0] >= 2 ** 31 - 1:
        findings.append(_finding(
            "kernel-misaligned-block",
            f"table height {t0_shape[0]} exceeds int32 row addressing",
            fam, label))
    s_n, d = len(specs), t0_shape[1]
    tiers = cfg.get("tiers", 1)
    # one [S, block, D] window moves with the grid (the gather's out
    # block / the apply's merged-rows block, double-buffered); the
    # remaining tiers are the apply's per-kind scratch, held once
    block = ((s_n, block_rows, d), t0_dtype)
    used = _vmem_use(blocked=[block], held=[block] * (tiers - 1))
    if used > _SCOPED_VMEM_DEFAULT:
        findings.append(_finding(
            "kernel-vmem-budget",
            f"{tiers} tier(s) of [{s_n},{block_rows},{d}] VMEM blocks = "
            f"{used} bytes (lane-padded, moving block double-buffered) "
            f"exceed the default scoped VMEM (gate under-estimates for "
            f"this group)", fam, label))
    if block_rows % 8 and block_rows != cfg.get("batch", block_rows):
        findings.append(_finding(
            "kernel-misaligned-block",
            f"block_rows={block_rows} is neither 8-sublane aligned nor "
            f"the whole batch (Mosaic blocks the row dim in 8s)", fam,
            label))
    if block_rows < 1:
        findings.append(_finding(
            "kernel-grid-divisibility",
            f"degenerate block_rows={block_rows}", fam, label))


# ---------------------------------------------------------------------------
# canonical shape matrix: the shapes the bundled models/workloads actually
# launch (models/, bench.py configs).  must_accept pins the plans the perf
# story depends on — a gate regression that silently falls back FAILS CI.
# ---------------------------------------------------------------------------

_ATTENTION_MATRIX = [
    # transformer-base self-attention (bench.py transformer config)
    dict(label="transformer-base-f32", b=4, h=8, t=256, d=64,
         dtype="float32", fmt="bhtd"),
    dict(label="transformer-base-bf16", b=4, h=8, t=256, d=64,
         dtype="bfloat16", fmt="bhtd"),
    # BERT-base under amp
    dict(label="bert-base-bf16", b=4, h=12, t=128, d=64,
         dtype="bfloat16", fmt="bhtd"),
    # the transpose-free convention (ring attention / CP chunks reuse it)
    dict(label="transformer-base-bthd", b=4, h=8, t=256, d=64,
         dtype="float32", fmt="bthd"),
    dict(label="ring-cp-chunk-bthd", b=2, h=8, t=128, d=64,
         dtype="float32", fmt="bthd"),
    # BERT-base's fused_qkv_attention sites under amp (their projections
    # are XLA dots round these kernels since PR 30).  No such row for
    # transformer-base (8 heads x 256 rows, bf16): the chip compiles its
    # non-causal dkv walk at the default scope (PERF.md PR 28 (2)) where
    # this file's model counts 18.9 MB
    dict(label="bert-base-bf16-bthd", b=4, h=12, t=128, d=64,
         dtype="bfloat16", fmt="bthd"),
    # long-sequence flash leg (BENCH flash-attn workload)
    dict(label="flash-longseq", b=1, h=8, t=4096, d=64,
         dtype="float32", fmt="bhtd"),
    # block-diffusion training (models/block_diffusion_decoder.py at the
    # SDAR-30B-A3B widths): 32 query heads over 4 key/value heads of 128,
    # rows [noisy ; clean] of 2 x 2048 in blocks of 4, masked by position
    dict(label="block-diffusion-gqa-bf16", b=1, h=32, h_kv=4, t=4096, d=128,
         dtype="bfloat16", fmt="bhtd", mask=(4, 2048)),
    # the hybrid convolution / attention stack's one attention kind
    # (models/hybrid_conv_decoder.py at the LFM2-8B-A1B widths): causal, 32
    # query heads over 8 key/value heads of 64, rows of 4096
    dict(label="causal-gqa-d64-bf16", b=1, h=32, h_kv=8, t=4096, d=64,
         dtype="bfloat16", fmt="bhtd"),
    # h*d*esize > 2048: even a 128-block kv tile busts the 256 KB bound —
    # compiled mode must REJECT to XLA (the cap-floor regression class);
    # if the gate ever re-accepts this, the kv-tile check fires
    dict(label="transformer-big-f32-bthd", b=2, h=16, t=256, d=64,
         dtype="float32", fmt="bthd", must_accept=False),
    # pipeline micro-batch shapes (parallel/pipeline): transformer-base
    # under pp splits runs the SAME flash kernels per stage on the
    # micro-batch slice — batch 32 / K=16 -> b=2, K=8 -> b=4 (covered by
    # transformer-base-* above); the b=2 leg pins the smallest slice
    dict(label="pp-microbatch-b2-bthd", b=2, h=8, t=256, d=64,
         dtype="float32", fmt="bthd"),
    dict(label="pp-microbatch-b2-bf16", b=2, h=8, t=256, d=64,
         dtype="bfloat16", fmt="bhtd"),
]

_CONV_BN_MATRIX = [
    # resnet-50 NHWC batch 32 stage shapes (models/resnet.py)
    dict(label="stem-c64", rows=32 * 112 * 112, c=64, dtype="float32"),
    dict(label="stage1-c256", rows=32 * 56 * 56, c=256, dtype="float32"),
    dict(label="stage3-c1024", rows=32 * 14 * 14, c=1024,
         dtype="bfloat16"),
    dict(label="stage4-c2048", rows=32 * 7 * 7, c=2048, dtype="float32"),
    # lane-folded narrow-channel case (c < 128)
    dict(label="fold-c64-bf16", rows=32 * 56 * 56, c=64,
         dtype="bfloat16"),
    # 1x1-conv-as-dot epilogue
    dict(label="dot-stage2-c512", kind="dot", rows=32 * 28 * 28, c=512,
         dtype="bfloat16"),
    # oc < 128 has no lane-fold on the dot path (unlike channel_stats):
    # the stage-1 1x1/64 reduce convs run the XLA fallback by design —
    # numerically identical, a perf (not correctness) gap
    dict(label="dot-stage1-c64", kind="dot", rows=32 * 56 * 56, c=64,
         dtype="float32", must_accept=False),
]

# ring attention: the sharded entry splits the sequence axis over the sp
# mesh axis and each rank runs the single-device flash kernels on its
# chunk via the SAME _plan gate (kernels/ring_attention.py _plan reuse) —
# audit the per-rank CHUNK shapes the CP configs actually produce
_RING_MATRIX = [
    # long-context CP leg: t=4096 over sp=8 -> 512-token chunks
    dict(label="cp8-longseq-chunk", b=1, h=8, t=512, d=64,
         dtype="float32", fmt="bhtd"),
    dict(label="cp8-longseq-chunk-bthd", b=1, h=8, t=512, d=64,
         dtype="float32", fmt="bthd"),
    # transformer CP over sp=2 (the dryrun_multichip shape)
    dict(label="cp2-transformer-chunk-bthd", b=4, h=8, t=128, d=64,
         dtype="float32", fmt="bthd"),
    # CP chunk under a pp split's micro-batching (pp x cp composition:
    # the per-rank ring chunk sees the micro-batch slice)
    dict(label="pp2-cp2-microbatch-chunk-bthd", b=2, h=8, t=128, d=64,
         dtype="float32", fmt="bthd"),
]

_DROPOUT_MATRIX = [
    dict(label="transformer-residual", shape=(4, 256, 512),
         dtype="float32"),
    dict(label="bert-residual-bf16", shape=(4, 128, 768),
         dtype="bfloat16"),
]

# flash-decode: the generation-tier cache shapes bench.py --model decode
# actually launches (transformer-base geometry; max_t is the ring-buffer
# row count, rounded to the 128-row block quantum by the model builders)
_SHORT_CONV_MATRIX = [
    # LFM2-8B-A1B's convolution blocks under amp (models/
    # hybrid_conv_decoder.py): rows of 4096, 2048 channels, 3 taps
    dict(label="lfm2-conv-bf16", b=2, t=4096, d=2048, taps=3,
         dtype="bfloat16"),
    dict(label="lfm2-conv-f32-s2048", b=2, t=2048, d=2048, taps=3,
         dtype="float32"),
    # rows that are no whole 16-row tiles: the XLA composition
    dict(label="conv-ragged-rows", b=2, t=1000, d=2048, taps=3,
         dtype="bfloat16", must_accept=False),
]

_DECODE_MATRIX = [
    # the ROADMAP metric pair: tokens/sec decode at batch 1 and 64
    dict(label="decode-base-b1", b=1, h=8, dh=64, max_t=128,
         dtype="float32"),
    dict(label="decode-base-b64", b=64, h=8, dh=64, max_t=128,
         dtype="float32"),
    # cross-attention reads during decode (src_seq_len=256 cache)
    dict(label="decode-cross-b64", b=64, h=8, dh=64, max_t=256,
         dtype="float32"),
    # bf16 cache with h=8: 16-sublane tiling rejects by design (the
    # in-register [h, t, d] view would violate Mosaic tiling) -> XLA
    # fallback, numerically identical
    dict(label="decode-base-bf16-h8", b=8, h=8, dh=64, max_t=128,
         dtype="bfloat16", must_accept=False),
    # dh not 64-aligned rejects by design
    dict(label="decode-dh48-reject", b=4, h=8, dh=48, max_t=128,
         dtype="float32", must_accept=False),
]

# what Mosaic said (libtpu 0.0.34, TPU v5e) to a hand-written DMA out of an
# HBM cache whose minor dim is d_head 64: the megastep walks refuse at
# every d_head that is not a multiple of 128
_REFUSED_MINOR_64 = ("Slice shape along dimension 3 must be aligned to "
                     "tiling (128), but is 64")

# fused decode megastep: whole-decoder-layer-per-launch plans
# (kernels/decode_step.py).  The transformer-base geometries (d_head 64)
# were refused by the compiler on the chip and run the XLA composition
# (+ the flash-decode kernel, which takes d_head 64); the d_head-128
# rows are the geometries the megastep compiles and matches at
_MEGASTEP_MATRIX = [
    dict(label="megastep-base", dm=512, h=8, dh=64, di=2048, max_t=128,
         cross_t=256, dtype="float32", must_accept=False,
         mosaic_refusal=_REFUSED_MINOR_64),
    dict(label="megastep-fused-ffn", dm=128, h=8, dh=64, di=256,
         max_t=128, cross_t=128, dtype="float32", must_accept=False,
         mosaic_refusal=_REFUSED_MINOR_64),
    # d_head 128: the FFN weights (~8 MB) split into a second launch
    dict(label="megastep-dh128-split", dm=256, h=8, dh=128, di=4096,
         max_t=128, cross_t=256, dtype="float32", expect_fuse_ffn=False),
    dict(label="megastep-dh128-fused-ffn", dm=128, h=8, dh=128, di=256,
         max_t=128, cross_t=128, dtype="float32", expect_fuse_ffn=True),
    # the CI smoke config (dm=128, h=4, dh=32): dh %% 64 rejects by
    # design -> composed XLA fallback, numerically identical
    dict(label="megastep-smoke-dh32", dm=128, h=4, dh=32, di=256,
         max_t=128, cross_t=128, dtype="float32", must_accept=False),
    # bf16 with h=8 violates the 16-sublane [h, t, d] walk tiling ->
    # rejects by design (same contract as decode_attention bf16-h8)
    dict(label="megastep-bf16-h8", dm=512, h=8, dh=64, di=2048,
         max_t=128, cross_t=256, dtype="bfloat16", must_accept=False),
]

# paged flash-decode: block-pool walks at FLAGS_kv_block_t granularity
# (kernels/decode_attention.py _paged_plan).  block_t comes from the pool
# and is never snapped, so the misaligned-pool and oversized-table rows
# are MUST-REJECTS: accepting either would DMA off-tile or overflow the
# SMEM-resident table
_PAGED_MATRIX = [
    # the ROADMAP metric pair on the paged layout (128 logical rows =
    # 8 blocks of 16)
    dict(label="paged-base-b1", b=1, h=8, dh=64, block_t=16,
         max_blocks=8, dtype="float32"),
    dict(label="paged-base-b64", b=64, h=8, dh=64, block_t=16,
         max_blocks=8, dtype="float32"),
    # pool built with block_t % 8 != 0: reject, never snap
    dict(label="paged-bt12-reject", b=4, h=8, dh=64, block_t=12,
         max_blocks=8, dtype="float32", must_accept=False),
    # table past the scalar-prefetch cap (64 * 128 = 8192 entries)
    dict(label="paged-table-overflow-reject", b=64, h=8, dh=64,
         block_t=16, max_blocks=128, dtype="float32",
         must_accept=False),
]

# paged fused decode megastep (kernels/decode_step.py
# _paged_megastep_plan): both walks block-indexed, both flattened
# tables scalar-prefetched
_PAGED_MEGASTEP_MATRIX = [
    dict(label="paged-megastep-base", dm=512, h=8, dh=64, di=2048,
         block_t=16, cross_block_t=16, b=64, max_blocks=8,
         cross_max_blocks=16, dtype="float32", must_accept=False,
         mosaic_refusal=_REFUSED_MINOR_64),
    dict(label="paged-megastep-fused-ffn", dm=128, h=8, dh=64, di=256,
         block_t=16, cross_block_t=16, b=4, max_blocks=8,
         cross_max_blocks=8, dtype="float32", must_accept=False,
         mosaic_refusal=_REFUSED_MINOR_64),
    dict(label="paged-megastep-dh128-b64", dm=256, h=8, dh=128, di=4096,
         block_t=16, cross_block_t=16, b=64, max_blocks=8,
         cross_max_blocks=16, dtype="float32", expect_fuse_ffn=True),
    dict(label="paged-megastep-dh128-fused-ffn", dm=128, h=8, dh=128,
         di=256, block_t=16, cross_block_t=16, b=4, max_blocks=8,
         cross_max_blocks=8, dtype="float32", expect_fuse_ffn=True),
    dict(label="paged-megastep-bt12-reject", dm=128, h=8, dh=128, di=256,
         block_t=12, cross_block_t=16, b=4, max_blocks=8,
         cross_max_blocks=8, dtype="float32", must_accept=False),
    dict(label="paged-megastep-table-overflow-reject", dm=128, h=8,
         dh=128, di=256, block_t=16, cross_block_t=16, b=64,
         max_blocks=128, cross_max_blocks=8, dtype="float32",
         must_accept=False),
]

# what Mosaic said to a per-row DMA out of a [V, 10] table
_REFUSED_MINOR_10 = ("Slice shape along dimension 1 must be aligned to "
                     "tiling (128), but is 10")

_EMBEDDING_MATRIX = [
    # deepfm: 26 slots x [10001, 10] emb tables + [10001, 1] w1 tables.
    # A per-row DMA out of a table whose row is narrower than 128 lanes
    # was refused by the compiler on the chip: DeepFM's sparse tier runs
    # the per-table XLA composition there, by design of the gate
    dict(label="deepfm-emb", tables=[((10001, 10), "float32")] * 26,
         batch=256, tiers=1, must_accept=False,
         mosaic_refusal=_REFUSED_MINOR_10),
    dict(label="deepfm-w1", tables=[((10001, 1), "float32")] * 26,
         batch=256, tiers=1, must_accept=False,
         mosaic_refusal=_REFUSED_MINOR_10),
    # lazy-adam apply: param + m1 + m2 tiers + the merged-rows block
    dict(label="deepfm-adam-apply", tables=[((10001, 10), "float32")] * 26,
         batch=256, tiers=4, must_accept=False,
         mosaic_refusal=_REFUSED_MINOR_10),
    # the geometry the kernels compile and match at: 128-lane rows.  The
    # b4096 leg carries the 26 x 4096 int32 (426 KB) scalar-prefetched id
    # table of bench.py's DeepFM batch
    dict(label="wide-emb-d128", tables=[((10001, 128), "float32")] * 26,
         batch=256, tiers=1),
    dict(label="wide-emb-d128-b4096",
         tables=[((10001, 128), "float32")] * 26, batch=4096, tiers=1),
    dict(label="wide-adam-apply-d128",
         tables=[((10001, 128), "float32")] * 26, batch=256, tiers=4),
]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def lint_kernel_plans() -> Tuple[List[Finding], Dict[str, Any]]:
    """Audit every Pallas plan family over the canonical matrix.  Returns
    (findings, report); report maps family -> audited configs with the
    plan each gate produced (the CI artifact payload)."""
    from ..kernels import attention as att
    from ..kernels import conv_bn as cbn
    from ..kernels import decode_attention as kda
    from ..kernels import dropout_epilogue as de
    from ..kernels import embedding as emb
    from ..kernels import short_conv as sc

    findings: List[Finding] = []
    report: Dict[str, Any] = {}

    def audit_attention_matrix(matrix):
        """Shared by the attention and ring-attention families (ring
        chunks run the single-device kernels through the SAME gate)."""
        rows = []
        for cfg in matrix:
            shape = ((cfg["b"], cfg["t"], cfg["h"], cfg["d"])
                     if cfg["fmt"] == "bthd"
                     else (cfg["b"], cfg["h"], cfg["t"], cfg["d"]))
            q = k = _spec(shape, cfg["dtype"])
            if "h_kv" in cfg:  # grouped-query rows are bhtd
                k = _spec((cfg["b"], cfg["h_kv"]) + shape[2:], cfg["dtype"])
            with _pretend_tpu():
                ok, bq, bk, interp = att._plan(q, k, 512, 512, None,
                                               cfg["fmt"], None,
                                               cfg.get("mask"))
            check_attention_plan(cfg, ok, bq, bk, interp, findings)
            rows.append(dict(label=cfg["label"], fmt=cfg["fmt"],
                             accepted=bool(ok), block_q=int(bq),
                             block_k=int(bk)))
        return rows

    report["attention"] = audit_attention_matrix(_ATTENTION_MATRIX)

    rows = []
    for cfg in _CONV_BN_MATRIX:
        with _pretend_tpu():
            if cfg.get("kind") == "dot":
                plan = cbn._dot_plan(cfg["rows"], cfg["c"], cfg["dtype"],
                                     None)
            else:
                plan = cbn._plan(cfg["rows"], cfg["c"], cfg["dtype"], None)
        check_conv_bn_plan(cfg, plan, findings)
        if plan is None:
            rows.append(dict(label=cfg["label"], accepted=False))
        elif cfg.get("kind") == "dot":
            rows.append(dict(label=cfg["label"], accepted=True,
                             block_m=plan[0], block_n=plan[1]))
        else:
            rows.append(dict(label=cfg["label"], accepted=True,
                             block_r=plan.block_r, block_c=plan.block_c,
                             fold=plan.fold))
    report["conv_bn"] = rows

    rows = []
    for cfg in _DROPOUT_MATRIX:
        with _pretend_tpu():
            ok, r, nc, br, interp, hw = de._plan(cfg["shape"],
                                                 cfg["dtype"], None)
        check_dropout_plan(cfg, ok, r, nc, br, interp, hw, findings)
        rows.append(dict(label=cfg["label"], accepted=bool(ok),
                         block_r=int(br), hw_prng=bool(hw)))
    report["dropout_epilogue"] = rows

    rows = []
    for cfg in _SHORT_CONV_MATRIX:
        with _pretend_tpu():
            ok, r, chunk, interp = sc._plan(
                _spec((cfg["b"], cfg["t"], 3 * cfg["d"]), cfg["dtype"]),
                _spec((cfg["d"], cfg["taps"]), "float32"), None)
        check_short_conv_plan(cfg, ok, r, chunk, interp, findings)
        rows.append(dict(label=cfg["label"], accepted=bool(ok),
                         rows=int(r), chunk=int(chunk)))
    report["short_conv"] = rows

    rows = []
    for cfg in _EMBEDDING_MATRIX:
        (v, d), dtype = cfg["tables"][0]
        block = emb._auto_block_rows(cfg["tiers"], len(cfg["tables"]), d,
                                     dtype, cfg["batch"])
        with _pretend_tpu():
            ok = emb._kernel_ok([_spec(*t) for t in cfg["tables"]])
        check_embedding_group(cfg, block, findings, accepted=ok)
        rows.append(dict(label=cfg["label"], tables=len(cfg["tables"]),
                         accepted=bool(ok), block_rows=int(block),
                         tiers=cfg["tiers"]))
    report["embedding"] = rows

    rows = []
    for cfg in _DECODE_MATRIX:
        q = _spec((cfg["b"], cfg["h"], cfg["dh"]), cfg["dtype"])
        kc = _spec((cfg["b"], cfg["max_t"], cfg["h"], cfg["dh"]),
                   cfg["dtype"])
        with _pretend_tpu():
            ok, bt, interp = kda._decode_plan(q, kc, 256, None)
        check_decode_plan(cfg, ok, bt, interp, findings)
        rows.append(dict(label=cfg["label"], accepted=bool(ok),
                         block_t=int(bt)))
    report["decode_attention"] = rows

    from ..kernels import decode_step as kds

    rows = []
    for cfg in _MEGASTEP_MATRIX:
        with _pretend_tpu():
            plan = kds._megastep_plan(
                cfg["dm"], cfg["h"], cfg["dh"], cfg["di"], cfg["max_t"],
                cfg["cross_t"], cfg["dtype"])
        check_megastep_plan(cfg, plan, findings)
        rows.append(dict(label=cfg["label"], accepted=bool(plan.ok),
                         fuse_ffn=bool(plan.fuse_ffn),
                         block_t=int(plan.block_t),
                         cross_block_t=int(plan.cross_block_t)))
    report["decode_step"] = rows

    rows = []
    for cfg in _PAGED_MATRIX:
        q = _spec((cfg["b"], cfg["h"], cfg["dh"]), cfg["dtype"])
        pool = _spec((cfg["b"] * cfg["max_blocks"], cfg["block_t"],
                      cfg["h"], cfg["dh"]), cfg["dtype"])
        table = _spec((cfg["b"], cfg["max_blocks"]), "int32")
        with _pretend_tpu():
            ok, bt, interp = kda._paged_plan(q, pool, table, None)
        check_paged_decode_plan(cfg, ok, bt, interp, findings)
        rows.append(dict(label=cfg["label"], accepted=bool(ok),
                         block_t=int(bt)))
    report["paged_decode_attention"] = rows

    rows = []
    for cfg in _PAGED_MEGASTEP_MATRIX:
        with _pretend_tpu():
            plan = kds._paged_megastep_plan(
                cfg["dm"], cfg["h"], cfg["dh"], cfg["di"],
                cfg["block_t"], cfg["cross_block_t"], cfg["b"],
                cfg["max_blocks"], cfg["cross_max_blocks"], cfg["dtype"])
        check_paged_megastep_plan(cfg, plan, findings)
        rows.append(dict(label=cfg["label"], accepted=bool(plan.ok),
                         fuse_ffn=bool(plan.fuse_ffn),
                         block_t=int(plan.block_t),
                         cross_block_t=int(plan.cross_block_t)))
    report["paged_decode_step"] = rows

    # ring attention reuses the attention _plan gate per sequence CHUNK
    # (kernels/ring_attention.py); audit the real per-rank chunk shapes
    report["ring_attention"] = audit_attention_matrix(_RING_MATRIX)
    return findings, report
