#!/usr/bin/env python
"""Dump + analyze the optimized HLO of a bench workload's compiled scan
step: counts copy/transpose/custom-call instructions by shape and locates
them relative to the flash-attention custom-calls.  Perf tooling for
PERF.md leads 1-2 (attention layout copies, scan-carry copies).

Usage: python tools/hlo_diag.py [transformer|transformer_smoke
           |transformer_noflash|resnet50|deepfm] [out.txt]
           [--bn-fusion] [--sparse] [--copy-census]

--copy-census: the round-9 while-body copy-byte attribution, automated
(PERF.md's hand-done "Remaining copy inventory").  Every HLO copy is
attributed to a site class by its metadata + enclosing computation:
  projection   copies whose source metadata points into ops/math_ops.py
               (the mul lowering) — the dot-preferred<->custom-call
               relayouts.  NOTE: this keys on the DOT TIER, so any FFN/
               head mul relayouts land here too; the attention-projection
               subset is what a diff against a build without the
               attention sites leaves (fused_qkv_attention's own dots
               carry that op's scope, not the mul lowering's)
  pallas       copies sourced from kernels/ (the pallas_call operand/
               result relayouts into alternate memory)
  entry        copies living in the ENTRY computation whose operand is a
               program parameter — the donated-param entry copies ("XLA
               copies donated params at entry despite may-alias")
  other        everything else
The JSON lands next to the dump as <out>.census.json so CI can archive
it (read in tests/test_fused_qkv_attention.py).

--bn-fusion (resnet50): the round-7 BN-wall attribution report — counts
the BN-statistics channel reductions (full passes over 3/4-D activations
producing per-channel vectors), the layout-dual filter copies (the same
[O,I,kh,kw] filter held in two layouts for fwd vs bwd conv — the r04
"momentum chain in two layout duals" finding), and the activation bytes
those reduction passes re-read.  Run it with FLAGS_fused_bn=0 vs =1 (env
var) and diff the counters: the A/B attribution of the fused-BN levers is
mechanical (tests/test_conv_bn.py asserts the fused path removes the
reduction passes).

--sparse (deepfm): the round-8 dispatch/launch census of the CTR step —
graph-level op counts (per-slot lookup_table / grad / optimizer chains
vs their fused_* group forms) and the HLO instruction census the sparse
tier lowers to (gather / scatter / dynamic-slice tiers + the bytes the
gathers move + int64->int32 convert count).  Run with
FLAGS_fused_embedding=0 vs =1 and diff: the fused path must show the
launch-count collapse (one fused gather per table group, the per-table
sort+segment+scatter optimizer chains collapsed to one group apply) —
asserted in tests/test_fused_embedding.py.
"""

import os
import re
import sys
import collections

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def compile_transformer(scan_steps=8, batch_size=64, seq_len=256,
                        use_flash=True):
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as T

    cfg = dict(n_layer=6, n_head=8, d_key=64, d_value=64, d_model=512,
               d_inner_hid=2048, vocab=32000)
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        avg_cost, _, feeds = T.transformer(
            src_vocab_size=cfg["vocab"], trg_vocab_size=cfg["vocab"],
            max_length=seq_len, n_layer=cfg["n_layer"], n_head=cfg["n_head"],
            d_key=cfg["d_key"], d_value=cfg["d_value"], d_model=cfg["d_model"],
            d_inner_hid=cfg["d_inner_hid"], dropout_rate=0.1,
            src_seq_len=seq_len, trg_seq_len=seq_len, use_flash=use_flash,
        )
        pt.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    pt.amp.enable(prog)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    batches = [
        T.make_batch(batch_size, seq_len, seq_len, cfg["n_head"],
                     cfg["vocab"], cfg["vocab"], rng=np.random.RandomState(s))
        for s in range(scan_steps)
    ]
    feed = {k: np.stack([b[k] for b in batches]) for k in batches[0]}
    return exe, prog, feed, [avg_cost], scope


def compile_transformer_smoke(scan_steps=2, batch_size=2, seq_len=64,
                              use_flash=True):
    """Tiny-but-representative transformer for the CI copy-census leg:
    d_model/head shapes keep the bthd flash plan feasible
    (d_head 64), everything else shrinks so a CPU box compiles it in
    seconds."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as T

    cfg = dict(n_layer=1, n_head=2, d_key=64, d_value=64, d_model=128,
               d_inner_hid=256, vocab=512)
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        avg_cost, _, feeds = T.transformer(
            src_vocab_size=cfg["vocab"], trg_vocab_size=cfg["vocab"],
            max_length=seq_len, n_layer=cfg["n_layer"], n_head=cfg["n_head"],
            d_key=cfg["d_key"], d_value=cfg["d_value"], d_model=cfg["d_model"],
            d_inner_hid=cfg["d_inner_hid"], dropout_rate=0.1,
            src_seq_len=seq_len, trg_seq_len=seq_len, use_flash=use_flash,
        )
        pt.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    pt.amp.enable(prog)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    batches = [
        T.make_batch(batch_size, seq_len, seq_len, cfg["n_head"],
                     cfg["vocab"], cfg["vocab"], rng=np.random.RandomState(s))
        for s in range(scan_steps)
    ]
    feed = {k: np.stack([b[k] for b in batches]) for k in batches[0]}
    return exe, prog, feed, [avg_cost], scope


def compile_resnet50(scan_steps=4, batch_size=256, image_size=224,
                     depth=50, data_format="NHWC"):
    import paddle_tpu as pt
    from paddle_tpu.models import resnet as R

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        img, label, avg_cost, acc, _ = R.build_train_net(
            class_dim=1000, image_shape=(3, image_size, image_size),
            depth=depth, lr=0.1, data_format=data_format)
    pt.amp.enable(prog)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feed = {
        "image": rng.rand(scan_steps, batch_size, 3, image_size,
                          image_size).astype("float32"),
        "label": rng.randint(0, 1000,
                             (scan_steps, batch_size, 1)).astype("int64"),
    }
    return exe, prog, feed, [avg_cost], scope


def compile_deepfm(scan_steps=2, batch_size=256, hash_dim=10001,
                   embedding_size=10, optimizer="adam"):
    import paddle_tpu as pt
    from paddle_tpu.models import deepfm as D

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        avg_cost, _, _, _ = D.build_train_net(
            hash_dim=hash_dim, embedding_size=embedding_size,
            optimizer=optimizer)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    batches = [D.make_batch(batch_size, hash_dim=hash_dim,
                            rng=np.random.RandomState(s))
               for s in range(scan_steps)]
    feed = {k: np.stack([b[k] for b in batches]) for k in batches[0]}
    return exe, prog, feed, [avg_cost], scope


def lower_entry(exe, prog, feed, fetch_list, scope, return_compiled=False):
    """Compile via run_steps (populates the cache), then AOT-lower the
    cached jitted fn on the same args to get optimized HLO text (and the
    compiled object, whose memory_analysis() the --memory report
    reads)."""
    exe.run_steps(prog, feed=feed, fetch_list=fetch_list, scope=scope)
    from paddle_tpu.core.executor import latest_jitted_entry

    entry = latest_jitted_entry(exe)
    rw = [scope.find_var(n) for n in entry.rw_state]
    ro = [scope.find_var(n) for n in entry.ro_state]
    import jax

    feed_names = sorted(feed)
    feed_vals = [exe._to_device_array(prog, n, feed[n]) for n in feed_names]
    key = jax.random.PRNGKey(0)
    lowered = entry.jitted.lower(feed_vals, rw, ro, key)
    compiled = lowered.compile()
    if return_compiled:
        return compiled.as_text(), compiled
    return compiled.as_text()


INSTR_RE = re.compile(
    r"%?([\w.-]+) = ([a-z0-9]+)\[([\d,]*)\](\S*) ([\w-]+)\(")
DT_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1,
            "f16": 2, "s8": 1, "u8": 1, "u64": 8, "s64": 8}


def analyze(txt):
    lines = txt.splitlines()
    copies = collections.Counter()
    copy_bytes = collections.Counter()
    copy_src = collections.Counter()
    custom_calls = collections.Counter()
    transposes = collections.Counter()
    for ln in lines:
        s = ln.strip()
        m = INSTR_RE.match(s)
        if not m:
            continue
        name, dt, dims, layout, opcode = m.groups()
        shape = f"{dt}[{dims}]{layout or ''}"
        nbytes = DT_BYTES.get(dt, 4) * int(
            np.prod([int(x) for x in dims.split(",") if x] or [1]))
        if opcode == "copy":
            copies[shape] += 1
            copy_bytes[shape] += nbytes
            sm = re.search(r'op_name="([^"]+)"', s)
            srcm = re.search(r'source_file="[^"]*/([\w.]+)" source_line=(\d+)',
                             s)
            label = (sm.group(1).split("/")[-1] if sm else "?")
            src = f"{srcm.group(1)}:{srcm.group(2)}" if srcm else "?"
            copy_src[(label, src)] += nbytes
        elif opcode == "transpose":
            transposes[shape] += 1
        elif opcode == "custom-call":
            cm = re.search(r'custom_call_target="([^"]+)"', s)
            custom_calls[(cm.group(1) if cm else "?", shape)] += 1
    out = []
    out.append("== copy instructions (count x shape, total MB) ==")
    for shape, n in copies.most_common(30):
        out.append(f"  {n:4d} x {shape}  ({copy_bytes[shape] / 1e6:.1f} MB)")
    out.append(f"  TOTAL copies: {sum(copies.values())} "
               f"({sum(copy_bytes.values()) / 1e6:.1f} MB static)")
    out.append("== copy bytes by op_name/source ==")
    for (label, src), b in copy_src.most_common(25):
        out.append(f"  {b / 1e6:8.1f} MB  {label}  {src}")
    out.append("== transpose instructions ==")
    for shape, n in transposes.most_common(15):
        out.append(f"  {n:4d} x {shape}")
    out.append(f"  TOTAL transposes: {sum(transposes.values())}")
    out.append("== custom calls ==")
    for (tgt, shape), n in custom_calls.most_common(20):
        out.append(f"  {n:4d} x {tgt} -> {shape}")
    return "\n".join(out)


# --bn-fusion: BN-statistics / layout-dual attribution ----------------------

# `%name = f32[64]{0} reduce(f32[2,8,8,64]{3,2,1,0} %op, f32[] %init), ...`
_REDUCE_RE = re.compile(
    r"= ([a-z0-9]+)\[([\d,]*)\][^ ]* reduce\(([a-z0-9]+)\[([\d,]*)\]")
_COPY_RE = re.compile(
    r"= ([a-z0-9]+)\[([\d,]*)\](\{[\d,]+\})? copy\(")
_SRC_RE = re.compile(r'source_file="([^"]*)" source_line=(\d+)')
_FILTER_KSIZES = (1, 3, 7)
_FLOAT_DTS = ("f32", "bf16", "f16", "f64")


def _dims(s):
    return tuple(int(x) for x in s.split(",") if x)


def analyze_bn_fusion(txt):
    """BN-wall counters from optimized-HLO text (the whole dump is
    scanned, so reductions inside fusion computation bodies count too):

      channel_reduces      float reduce instrs producing a 1-D per-channel
                           vector (>= 8 lanes) — the BN sum/sum²/dgamma/
                           dbeta tier, fwd AND bwd, wherever it came from
                           (XLA freely bitcasts the activation first, so
                           the rule keys on the OUTPUT shape)
      channel_reduce_read_mb  MB of inputs those reductions re-read (each
                           is a full pass over the activation it consumes)
      bn_stat_reduces      the subset whose source metadata points into
                           ops/nn_ops.py — i.e. emitted by the batch_norm
                           lowering itself; the fused path must drive this
                           to ZERO (its statistics ride the conv_bn.py
                           kernels; interpret-mode emulation attributes to
                           conv_bn.py, compiled Mosaic emits no reduce)
      filter_copies / filter_copy_mb / filter_layout_duals
                           copy instrs of 4-D [O,I,kh,kw] filter-shaped
                           tensors, and the dim-shapes held in >= 2
                           distinct layouts — the fwd/bwd layout duals of
                           the r04 momentum-chain finding
    """
    channel_reduces = 0
    read_bytes = 0
    bn_stat_reduces = 0
    bn_read_bytes = 0
    filter_copies = 0
    filter_copy_bytes = 0
    layouts_by_filter = collections.defaultdict(set)
    for ln in txt.splitlines():
        s = ln.strip()
        m = _REDUCE_RE.search(s)
        if m:
            out_dt, out_dims, in_dt, in_dims = m.groups()
            od, idm = _dims(out_dims), _dims(in_dims)
            if (out_dt in _FLOAT_DTS and len(od) == 1 and od[0] >= 8
                    and len(idm) >= 2):
                nbytes = DT_BYTES.get(in_dt, 4) * int(np.prod(idm))
                channel_reduces += 1
                read_bytes += nbytes
                src = _SRC_RE.search(s)
                if src and src.group(1).endswith("nn_ops.py"):
                    bn_stat_reduces += 1
                    bn_read_bytes += nbytes
            continue
        m = _COPY_RE.search(s)
        if m:
            dt, dims, layout = m.groups()
            d = _dims(dims)
            if (len(d) == 4 and d[2] == d[3] and d[2] in _FILTER_KSIZES
                    and d[0] >= 8 and d[1] >= 8):
                filter_copies += 1
                filter_copy_bytes += DT_BYTES.get(dt, 4) * int(np.prod(d))
                layouts_by_filter[d].add(layout or "{default}")
    duals = {d: sorted(ls) for d, ls in layouts_by_filter.items()
             if len(ls) >= 2}
    return {
        "channel_reduces": channel_reduces,
        "channel_reduce_read_mb": round(read_bytes / 1e6, 1),
        "bn_stat_reduces": bn_stat_reduces,
        "bn_stat_read_mb": round(bn_read_bytes / 1e6, 1),
        "filter_copies": filter_copies,
        "filter_copy_mb": round(filter_copy_bytes / 1e6, 1),
        "filter_layout_duals": len(duals),
        "filter_layout_dual_shapes": {
            "x".join(map(str, d)): ls for d, ls in sorted(duals.items())},
    }


def format_bn_fusion(rep):
    out = ["== BN-fusion report (PERF.md r07 attribution) =="]
    out.append(f"  channel-stat reduction passes: {rep['channel_reduces']} "
               f"(re-reading {rep['channel_reduce_read_mb']} MB)")
    out.append(f"  ... emitted by the batch_norm lowering: "
               f"{rep['bn_stat_reduces']} ({rep['bn_stat_read_mb']} MB) "
               "— 0 on the fused path")
    out.append(f"  filter-shaped copies: {rep['filter_copies']} "
               f"({rep['filter_copy_mb']} MB)")
    out.append(f"  filter layout duals: {rep['filter_layout_duals']}")
    for shape, layouts in rep["filter_layout_dual_shapes"].items():
        out.append(f"    {shape}: {', '.join(layouts)}")
    return "\n".join(out)


# --copy-census: the round-9 copy-byte attribution by site ------------------

_COMP_RE = re.compile(r"^(ENTRY\s+)?%?[\w.-]+\s*\(.*\)\s*->.*\{\s*$")
_PARAM_RE = re.compile(r"^%?([\w.-]+)\s*=\s*\S+\s+parameter\(\d+\)")
_COPY_OPND_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([\d,]*)\](\{[\d,]+\})?\s+copy\(%?([\w.-]+)")
_KERNEL_FILES = ("attention.py", "conv_bn.py", "dropout_epilogue.py",
                 "embedding.py", "ring_attention.py", "matmul_stats.py")


def _census_site(src_file, op_name, in_entry, operand_is_param):
    """Site class of one copy: 'projection' (the dot tier — the mul
    lowering in ops/math_ops.py; dominated by the qkv/output projection
    dots, but FFN/head muls land here too — diff fused vs unfused to
    isolate the attention subset), 'pallas' (custom-call operand/result
    relayout, sourced from kernels/), 'entry' (ENTRY-computation copies
    of program parameters — the donated-param entry copies), 'other'."""
    if in_entry and operand_is_param:
        return "entry"
    base = src_file.rsplit("/", 1)[-1] if src_file else ""
    if base == "math_ops.py":
        return "projection"
    if base in _KERNEL_FILES or "/kernels/" in (src_file or ""):
        return "pallas"
    return "other"


def analyze_copy_census(txt):
    """Bytes-per-site copy census of one optimized-HLO dump (the
    automated form of PERF.md's hand-done 'Remaining copy inventory').
    Returns a JSON-able dict."""
    sites = {k: {"count": 0, "mb": 0.0}
             for k in ("projection", "pallas", "entry", "other")}
    top = collections.Counter()
    entry_params = set()
    in_entry = False
    total = 0
    total_bytes = 0
    for ln in txt.splitlines():
        s = ln.strip()
        if _COMP_RE.match(ln):
            in_entry = ln.lstrip().startswith("ENTRY")
            continue
        if in_entry:
            pm = _PARAM_RE.match(s)
            if pm:
                entry_params.add(pm.group(1))
                continue
        m = _COPY_OPND_RE.search(s)
        if not m:
            continue
        dt, dims, _, operand = m.groups()
        nbytes = DT_BYTES.get(dt, 4) * int(
            np.prod([int(x) for x in dims.split(",") if x] or [1]))
        srcm = _SRC_RE.search(s)
        src_file = srcm.group(1) if srcm else ""
        src = (f"{src_file.rsplit('/', 1)[-1]}:{srcm.group(2)}"
               if srcm else "?")
        om = re.search(r'op_name="([^"]+)"', s)
        op_name = om.group(1).split("/")[-1] if om else "?"
        site = _census_site(src_file, op_name, in_entry,
                            operand in entry_params)
        sites[site]["count"] += 1
        sites[site]["mb"] = round(sites[site]["mb"] + nbytes / 1e6, 3)
        top[(site, op_name, src)] += nbytes
        total += 1
        total_bytes += nbytes
    return {
        "total_copies": total,
        "total_mb": round(total_bytes / 1e6, 3),
        "sites": sites,
        "top": [
            {"site": site, "op": op, "src": src, "mb": round(b / 1e6, 3)}
            for (site, op, src), b in top.most_common(15)
        ],
    }


def format_copy_census(rep):
    out = ["== copy census by site (PERF.md r09 attribution) =="]
    for site, d in rep["sites"].items():
        out.append(f"  {site:11s} {d['count']:4d} copies  {d['mb']:10.3f} MB")
    out.append(f"  {'TOTAL':11s} {rep['total_copies']:4d} copies  "
               f"{rep['total_mb']:10.3f} MB")
    out.append("  top attribution (site, op, source):")
    for t in rep["top"]:
        out.append(f"    {t['mb']:8.3f} MB  {t['site']:10s} {t['op']}  "
                   f"{t['src']}")
    return "\n".join(out)


# --sparse: the round-8 dispatch/launch census of the sparse CTR tier ------

_SPARSE_GRAPH_OPS = (
    "lookup_table", "fused_lookup_table",
    "lookup_table_grad", "fused_lookup_table_grad",
    "sgd", "adam", "fused_sparse_sgd", "fused_sparse_adam",
)
# HLO opcodes the per-slot sparse tier lowers to.  `sort` counts the
# per-table MergeAdd argsorts (the fused path runs ONE batched sort per
# group); the dynamic-slice tiers are where the fused kernels' emulated /
# compiled row DMAs land.
_SPARSE_HLO_OPS = ("gather", "scatter", "dynamic-slice",
                   "dynamic-update-slice", "convert", "sort", "while")
# tuple-result instructions (sort/while): `%x = (f32[8]{0}, ...) sort(`
_TUPLE_INSTR_RE = re.compile(r"%?[\w.-]+ = \(.*?\)(?:\{[\d,]*\})? ([a-z0-9-]+)\(")


def analyze_sparse(txt, program=None):
    """Dispatch census from optimized-HLO text (+ graph-level op counts
    when the Program is given): how many gather/scatter/optimizer
    dispatches one CTR step issues, and the bytes the gathers move.
    Diff FLAGS_fused_embedding=0 vs =1: the fused path collapses the
    52-launch lookup tier to one fused gather per table group and the
    per-table optimizer chains to one group apply."""
    hlo = {f"hlo_{k}": 0 for k in _SPARSE_HLO_OPS}
    gather_bytes = 0
    for ln in txt.splitlines():
        s = ln.strip()
        m = INSTR_RE.match(s)
        if not m:
            # sort (variadic argsort) and while carry TUPLE-shaped
            # results — `%x = (f32[8]{0}, s32[8]{0}) sort(...)` — which
            # INSTR_RE's array-shape pattern never matches
            m2 = _TUPLE_INSTR_RE.match(s)
            if m2 and m2.group(1) in _SPARSE_HLO_OPS:
                hlo[f"hlo_{m2.group(1)}"] += 1
            continue
        _, dt, dims, _, opcode = m.groups()
        if opcode in _SPARSE_HLO_OPS:
            hlo[f"hlo_{opcode}"] += 1
            if opcode == "gather":
                gather_bytes += DT_BYTES.get(dt, 4) * int(
                    np.prod([int(x) for x in dims.split(",") if x] or [1]))
    rep = {
        "graph": {},
        **hlo,
        "hlo_gather_mb": round(gather_bytes / 1e6, 3),
    }
    if program is not None:
        ops = [op.type for op in program.global_block().ops]
        rep["graph"] = {t: ops.count(t) for t in _SPARSE_GRAPH_OPS}
        g = rep["graph"]
        rep["graph"]["gather_launches"] = (
            g["lookup_table"] + g["fused_lookup_table"])
        rep["graph"]["sparse_grad_launches"] = (
            g["lookup_table_grad"] + g["fused_lookup_table_grad"])
        rep["graph"]["optimizer_launches"] = (
            g["sgd"] + g["adam"] + g["fused_sparse_sgd"]
            + g["fused_sparse_adam"])
    return rep


def format_sparse(rep):
    out = ["== sparse dispatch census (PERF.md r08 attribution) =="]
    g = rep.get("graph") or {}
    if g:
        out.append(
            f"  graph: gather launches {g['gather_launches']} "
            f"(lookup_table {g['lookup_table']} + fused "
            f"{g['fused_lookup_table']}), grad launches "
            f"{g['sparse_grad_launches']}, optimizer launches "
            f"{g['optimizer_launches']} (fused sparse "
            f"{g['fused_sparse_sgd'] + g['fused_sparse_adam']})")
    out.append(
        f"  HLO: {rep['hlo_gather']} gather ({rep['hlo_gather_mb']} MB "
        f"moved/step-call), {rep['hlo_scatter']} scatter, "
        f"{rep['hlo_sort']} sort, {rep['hlo_dynamic-slice']}/"
        f"{rep['hlo_dynamic-update-slice']} dyn-slice/update, "
        f"{rep['hlo_convert']} convert, {rep['hlo_while']} while")
    return "\n".join(out)


# --memory: planner table + memory_analysis() ground truth -----------------


def analyze_memory(prog, feed, compiled, txt, fetch_names):
    """The memory-tier report of one bench workload: the static
    planner's table next to the XLA executable's CompiledMemoryStats
    ground truth, with the long-open donated-param ENTRY-COPY bytes
    folded in as a named row (the copy census already attributes them;
    PERF.md's 'cause not yet found' aside becomes a tracked number).

    The planner models ONE step program; the compiled entry is the
    run_steps scan (leading [K] feed axis), so the delta also carries
    the K-stacked feed bytes — both recorded, labeled, never conflated.
    """
    from paddle_tpu import memory as M

    feed_names = sorted(feed)
    import numpy as _np

    first = _np.asarray(feed[feed_names[0]])
    batch = int(first.shape[1]) if first.ndim >= 2 else None
    plan = M.plan_program(prog, feed_names, fetch_names, batch_size=batch)
    stats = M.xla_memory_stats(compiled)
    census = analyze_copy_census(txt)
    entry_mb = census["sites"]["entry"]["mb"]
    rep = {
        "batch_size": batch,
        "planner": plan.to_dict(),
        "memory_analysis": stats,
        "planner_peak_bytes": plan.peak_bytes,
        "memory_analysis_peak_bytes": stats["peak_bytes"],
        "ratio": (round(plan.peak_bytes / stats["peak_bytes"], 4)
                  if stats["peak_bytes"] else None),
        # the donation question, now a named row instead of a PERF aside
        "entry_copy_mb": entry_mb,
        "entry_copy_count": census["sites"]["entry"]["count"],
        "table": plan.table(),
    }
    return rep


def format_memory(rep):
    out = ["== memory report (planner vs memory_analysis) =="]
    out.append(rep["table"])
    ma = rep["memory_analysis"]
    out.append(
        f"  XLA executable: args {ma['argument_bytes'] / 1e6:.2f} MB, "
        f"temp {ma['temp_bytes'] / 1e6:.2f} MB, out "
        f"{ma['output_bytes'] / 1e6:.2f} MB, alias "
        f"{ma['alias_bytes'] / 1e6:.2f} MB -> peak "
        f"{ma['peak_bytes'] / 1e6:.2f} MB")
    out.append(f"  planner/XLA ratio: {rep['ratio']}")
    out.append(
        f"  donated-param entry copies: {rep['entry_copy_count']} "
        f"({rep['entry_copy_mb']:.3f} MB) — the PERF.md donation row")
    return "\n".join(out)


def main():
    argv = [a for a in sys.argv[1:] if not a.startswith("--")]
    bn_fusion = "--bn-fusion" in sys.argv[1:]
    sparse = "--sparse" in sys.argv[1:]
    copy_census = "--copy-census" in sys.argv[1:]
    memory_report = "--memory" in sys.argv[1:]
    which = argv[0] if argv else "transformer"
    out_path = argv[1] if len(argv) > 1 else f"/tmp/hlo_{which}.txt"
    if which == "transformer":
        args = compile_transformer()
    elif which == "transformer_smoke":
        args = compile_transformer_smoke()
    elif which == "transformer_noflash":
        args = compile_transformer(use_flash=False)
    elif which == "resnet50":
        args = compile_resnet50()
    elif which == "deepfm":
        args = compile_deepfm()
    else:
        raise SystemExit(f"unknown workload {which}")
    txt, compiled = lower_entry(*args, return_compiled=True)
    with open(out_path, "w") as f:
        f.write(txt)
    print(f"[hlo_diag] optimized HLO -> {out_path} ({len(txt)} bytes)")
    print(analyze(txt))
    if bn_fusion:
        print(format_bn_fusion(analyze_bn_fusion(txt)))
    if sparse:
        print(format_sparse(analyze_sparse(txt, args[1])))
    if copy_census:
        import json

        rep = analyze_copy_census(txt)
        rep["workload"] = which
        census_path = out_path + ".census.json"
        with open(census_path, "w") as f:
            json.dump(rep, f, indent=1)
        print(format_copy_census(rep))
        print(f"[hlo_diag] copy census -> {census_path}")
    if memory_report:
        import json

        exe_, prog_, feed_, fetch_, scope_ = args
        fetch_names = [getattr(v, "name", v) for v in fetch_]
        mrep = analyze_memory(prog_, feed_, compiled, txt, fetch_names)
        mrep["workload"] = which
        mem_path = out_path + ".memory.json"
        with open(mem_path, "w") as f:
            json.dump(mrep, f, indent=1)
        print(format_memory(mrep))
        print(f"[hlo_diag] memory report -> {mem_path}")


if __name__ == "__main__":
    main()
