"""Numerics instrumentation pass: rewrite a Program so every step also
computes tensor-health statistics, fetched as ONE packed [N, 4] tensor.

The reference framework's FLAGS_check_nan_inf (operator.cc:943) walks
every operator's outputs on the host after each op executes — free in an
interpreter, impossible in a whole-block XLA world where ops never
individually return to the host.  This pass is that capability rebuilt
as a graph rewrite (same family as memory/recompute.py): behind
FLAGS_check_numerics, each instrumented tensor gets one fused
`numerics_stat` reduction ([nonfinite_count, abs_max, abs_mean, l2] —
ops/numerics_ops.py) and all rows pack into a single stats tensor the
executor fetches alongside the user's fetches — one device->host
transfer per step, not N.

Two levels:

  * `summary` — training-dynamics telemetry: per-parameter grad rows,
    post-update weight rows, and update rows (delta stats over
    `ParamOut - Param`, via a pre-optimizer snapshot `assign`), feeding
    the per-param-group gauges monitor/numerics.py publishes (grad-norm,
    weight-norm, update-to-weight ratio, overflow counts).
  * `locate` — full per-op-output instrumentation: every op output in
    the global block and in depth-1 `while` sub-blocks gets a row, so
    the first op in topological order with a non-finite output can be
    named.  Used by the watchdog's failing-step replay
    (monitor/numerics.py locate_in_program), not for steady-state runs.

Packing splits by op role so `Executor.run_accumulated`'s prefix/suffix
partition stays clean: rows produced by non-Optimize ops pack into
`__numerics_stats__` (prefix — returned stacked [K, N, 4] per
micro-batch), rows produced by Optimize-role ops pack into
`__numerics_stats_opt__` (suffix — single post-update [M, 4]).  Each
stat op carries its producer's role attr.

While sub-blocks ride loop-carried accumulators: the [4] row var is
seeded by `numerics_zeros` in the outer block right before the `while`
op, and the in-loop `numerics_stat` combines with the carry
([add, max, max, max]) — `lower_while` picks the var up as a carry
(written + present in the outer env) and pushes the final value back to
the outer env, so inner tensors are observed with zero per-iteration
host traffic.  `conditional_block` branches and nested (depth>1) while
loops return only their declared outputs, so their interiors are NOT
instrumented — a NaN born there localizes to the control-flow op itself.

Zero-cost contract (the recompute-pass idiom): `maybe_instrument` reads
FLAGS.check_numerics ONCE and returns None without touching the program
when it is 'off' — graphs stay byte-identical (same fingerprint), no
registry or flight writes, asserted in tests/test_numerics.py.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from ..core import framework as fw
from ..core import registry
from ..ops.numerics_ops import STAT_WIDTH

# the packed stats tensors the executor auto-fetches; order matters
# (non-Optimize rows first — program order)
STATS_VAR = "__numerics_stats__"
STATS_OPT_VAR = "__numerics_stats_opt__"

# op types whose outputs are never instrumented (our own machinery)
_SELF_TYPES = frozenset({"numerics_stat", "numerics_pack", "numerics_zeros"})

_GRAD_SUFFIX = "@GRAD"  # fw.grad_var_name's suffix


def is_instrumented(program) -> bool:
    return getattr(program, "_numerics_meta", None) is not None


def param_group(name: str) -> str:
    """Param-group key for gauge aggregation: the var-name prefix up to
    the first '.' (layer_helper names params '<layer>.w_0' / '<layer>.b_0',
    so this groups by layer)."""
    return name.split(".", 1)[0] if "." in name else name


def _role(op) -> int:
    try:
        return int(op.attrs.get(fw.OpRole.ROLE_ATTR_NAME, 0))
    except (TypeError, ValueError):
        return 0


def _is_opt(op) -> bool:
    return bool(_role(op) & fw.OpRole.Optimize)


class _Builder:
    """Accumulates stat rows for one instrumentation run over a program."""

    def __init__(self, program, level: str):
        self.program = program
        self.block = program.global_block()
        self.level = level
        self.k = 0          # unique-name counter
        self.pos = 0        # global topological row position
        self.rows: List[str] = []       # non-Optimize row var names
        self.rows_opt: List[str] = []
        self.meta: List[dict] = []      # rows for STATS_VAR, in order
        self.meta_opt: List[dict] = []
        self.while_blocks = 0

    def _row_var(self) -> str:
        name = f"__numerics_s{self.k}"
        self.k += 1
        self.block.create_var(name=name, shape=(STAT_WIDTH,),
                              dtype="float32", stop_gradient=True)
        return name

    def stat_op(self, block, x_name: str, *, ref: Optional[str] = None,
                acc: Optional[str] = None, out: Optional[str] = None,
                role: int = 0, meta: Optional[dict] = None) -> fw.Operator:
        """Build (don't splice) a numerics_stat op + its row var/meta."""
        out = out or self._row_var()
        inputs = {"X": [x_name]}
        if ref:
            inputs["Ref"] = [ref]
        if acc:
            inputs["Acc"] = [acc]
        attrs = {}
        if role:
            attrs[fw.OpRole.ROLE_ATTR_NAME] = role
        op = fw.Operator(block, "numerics_stat", inputs, {"Out": [out]},
                         attrs)
        m = dict(meta or {})
        m.setdefault("kind", "op")
        m["pos"] = self.pos
        self.pos += 1
        m["row_var"] = out
        if role & fw.OpRole.Optimize:
            self.rows_opt.append(out)
            self.meta_opt.append(m)
        else:
            self.rows.append(out)
            self.meta.append(m)
        return op

    def finish(self) -> dict:
        """Append the pack op(s), stamp program attrs, return the report."""
        block = self.block
        stats_vars = []
        if self.rows:
            block.create_var(name=STATS_VAR,
                             shape=(len(self.rows), STAT_WIDTH),
                             dtype="float32", stop_gradient=True)
            pack = fw.Operator(block, "numerics_pack",
                               {"X": list(self.rows)},
                               {"Out": [STATS_VAR]},
                               {"n": len(self.rows)})
            block.ops.append(pack)
            stats_vars.append(STATS_VAR)
        if self.rows_opt:
            block.create_var(name=STATS_OPT_VAR,
                             shape=(len(self.rows_opt), STAT_WIDTH),
                             dtype="float32", stop_gradient=True)
            pack = fw.Operator(block, "numerics_pack",
                               {"X": list(self.rows_opt)},
                               {"Out": [STATS_OPT_VAR]},
                               {"n": len(self.rows_opt),
                                fw.OpRole.ROLE_ATTR_NAME:
                                    fw.OpRole.Optimize})
            block.ops.append(pack)
            stats_vars.append(STATS_OPT_VAR)
        meta = {
            "level": self.level,
            "tensors": {STATS_VAR: self.meta,
                        STATS_OPT_VAR: self.meta_opt},
            "while_blocks": self.while_blocks,
        }
        self.program._numerics_meta = meta
        self.program._numerics_stats_vars = stats_vars
        block._bump()
        return {
            "level": self.level,
            "rows": len(self.rows) + len(self.rows_opt),
            "tensors": {n: len(meta["tensors"][n]) for n in stats_vars},
            "while_blocks": self.while_blocks,
        }


def _instrument_locate(b: _Builder) -> None:
    """Every op output in the global block + depth-1 while sub-blocks."""
    block = b.block
    new_ops: List[fw.Operator] = []
    for op_idx, op in enumerate(list(block.ops)):
        if op.type in _SELF_TYPES:
            new_ops.append(op)
            continue
        role = _role(op)
        if op.type == "while":
            sub = op.attrs.get("sub_block")
            if sub is not None:
                new_ops.extend(
                    _instrument_while(b, op_idx, op, sub, role))
        new_ops.append(op)
        seen = set()
        for slot in op.outputs:
            if slot in registry.unfilled_slots(op.type):
                continue  # may hold elements nothing has written
            for name in op.outputs[slot]:
                if not name or name in seen:
                    continue
                seen.add(name)
                sop = b.stat_op(
                    block, name, role=role,
                    meta={"block": block.idx, "op_index": op_idx,
                          "op_type": op.type, "var": name})
                new_ops.append(sop)
    block.ops[:] = new_ops


def _instrument_while(b: _Builder, op_idx: int, while_op, sub,
                      role: int) -> List[fw.Operator]:
    """Instrument a depth-1 while sub-block via loop-carried accumulator
    rows.  Returns the `numerics_zeros` seed ops that must precede the
    while op in the outer block."""
    b.while_blocks += 1
    seeds: List[fw.Operator] = []
    new_sub_ops: List[fw.Operator] = []
    for in_idx, iop in enumerate(list(sub.ops)):
        new_sub_ops.append(iop)
        if iop.type in _SELF_TYPES:
            continue
        seen = set()
        for slot in iop.outputs:
            if slot in registry.unfilled_slots(iop.type):
                continue
            for name in iop.outputs[slot]:
                if not name or name in seen:
                    continue
                seen.add(name)
                acc = b._row_var()  # lives in the OUTER block
                seeds.append(fw.Operator(b.block, "numerics_zeros", {},
                                         {"Out": [acc]}))
                sop = b.stat_op(
                    sub, name, acc=acc, out=acc, role=role,
                    meta={"block": sub.idx, "op_index": in_idx,
                          "op_type": iop.type, "var": name,
                          "in_loop": True,
                          "while_op_index": op_idx})
                new_sub_ops.append(sop)
    sub.ops[:] = new_sub_ops
    return seeds


def _instrument_summary(b: _Builder) -> None:
    """Grad / weight / update rows for every Parameter the program's
    Optimize suffix updates (plus grad rows for params with a grad but no
    optimizer op — e.g. a forward+backward-only program)."""
    block = b.block
    params = {p.name for p in block.all_parameters()}

    # last writer of each param grad (grad-accumulation sums rewrite the
    # same name; the LAST write is the grad the optimizer consumes)
    last_grad_writer: Dict[str, int] = {}
    opt_op_for_param: Dict[str, int] = {}
    for i, op in enumerate(block.ops):
        if op.type in _SELF_TYPES:
            continue
        if not _is_opt(op):
            for name in op.output_arg_names():
                if name.endswith(_GRAD_SUFFIX) and \
                        name[: -len(_GRAD_SUFFIX)] in params:
                    last_grad_writer[name] = i
        else:
            for pname in op.inputs.get("Param", []):
                if pname in params and pname not in opt_op_for_param and \
                        pname in op.outputs.get("ParamOut", []):
                    opt_op_for_param[pname] = i

    before: Dict[int, List[fw.Operator]] = {}
    after: Dict[int, List[fw.Operator]] = {}

    def _emit(idx, op, where):
        where.setdefault(idx, []).append(op)

    for gname, idx in sorted(last_grad_writer.items(),
                             key=lambda kv: (kv[1], kv[0])):
        pname = gname[: -len(_GRAD_SUFFIX)]
        sop = b.stat_op(block, gname, role=_role(block.ops[idx]),
                        meta={"kind": "grad", "param": pname,
                              "group": param_group(pname), "var": gname,
                              "block": block.idx, "op_index": idx,
                              "op_type": block.ops[idx].type})
        _emit(idx, sop, after)

    for pname, idx in sorted(opt_op_for_param.items(),
                             key=lambda kv: (kv[1], kv[0])):
        opt_op = block.ops[idx]
        role = _role(opt_op)
        # optimizer updates are in-place (ParamOut name == Param name),
        # so the pre-update value must be snapshotted for the delta row
        snap = f"__numerics_prev{b.k}"
        b.k += 1
        pvar = block._find_var_recursive(pname)
        b.block.create_var(name=snap,
                           shape=getattr(pvar, "shape", None),
                           dtype=getattr(pvar, "dtype", "float32"),
                           stop_gradient=True)
        asn = fw.Operator(block, "assign", {"X": [pname]},
                          {"Out": [snap]},
                          {fw.OpRole.ROLE_ATTR_NAME: role})
        _emit(idx, asn, before)
        upd = b.stat_op(block, pname, ref=snap, role=role,
                        meta={"kind": "update", "param": pname,
                              "group": param_group(pname), "var": pname,
                              "block": block.idx, "op_index": idx,
                              "op_type": opt_op.type})
        _emit(idx, upd, after)
        wgt = b.stat_op(block, pname, role=role,
                        meta={"kind": "weight", "param": pname,
                              "group": param_group(pname), "var": pname,
                              "block": block.idx, "op_index": idx,
                              "op_type": opt_op.type})
        _emit(idx, wgt, after)

    new_ops: List[fw.Operator] = []
    for i, op in enumerate(block.ops):
        new_ops.extend(before.get(i, ()))
        new_ops.append(op)
        new_ops.extend(after.get(i, ()))
    block.ops[:] = new_ops


def instrument_program(program, level: str) -> dict:
    """Mutate `program` IN PLACE with `level` instrumentation
    ('summary' | 'locate'); returns a report dict.  Idempotent guard:
    an already-instrumented program raises (re-instrumenting would
    double-count rows)."""
    if level not in ("summary", "locate"):
        raise ValueError(
            f"check_numerics level must be 'off', 'summary' or 'locate', "
            f"got {level!r}")
    if is_instrumented(program):
        raise ValueError("program is already numerics-instrumented")
    b = _Builder(program, level)
    if level == "locate":
        _instrument_locate(b)
    else:
        _instrument_summary(b)
    return b.finish()


def maybe_instrument(program, level: Optional[str] = None):
    """Flag-gated entry point (FLAGS_check_numerics).  Off (the default)
    costs ONE flag read and leaves the program byte-identical — the
    zero-cost contract, same shape as memory.maybe_optimize_memory.

    'locate' arms the executor's failing-step capture+replay but does
    NOT rewrite the steady-state program (full per-op instrumentation
    is replay-only); 'summary' rewrites in place.  Returns the report
    dict, or None when off."""
    if level is None:
        from ..flags import FLAGS

        level = FLAGS.check_numerics
    if not level or level == "off":
        return None
    if level == "locate":
        # steady-state graph unchanged: the watchdog-trip replay
        # (monitor/numerics.py) instruments a CLONE of the failing
        # program; arming is flag-driven inside the executor
        return {"level": "locate", "rows": 0, "deferred": True}
    return instrument_program(program, level)


__all__ = [
    "STATS_VAR",
    "STATS_OPT_VAR",
    "instrument_program",
    "maybe_instrument",
    "is_instrumented",
    "param_group",
]
