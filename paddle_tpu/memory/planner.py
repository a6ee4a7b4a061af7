"""Static HBM liveness planner over the Program IR.

Capability parity with the reference's memory-optimization transpiler tier
(reference: python/paddle/fluid/transpiler/memory_optimization_transpiler.py
— a liveness analysis over the ProgramDesc that re-uses dead var buffers —
plus the inplace passes of ir/memory_optimize_pass), redesigned TPU-first:

  * The reference REWRITES the graph to share buffers because its executor
    allocates one buffer per var.  Here XLA owns buffer assignment — sharing
    is automatic — so the planner's product is the *plan*, not a rewrite:
    per-op live sets, the peak-live watermark, and a per-var lifetime table
    that the two graph-level memory rewrites (recompute.py, offload.py)
    consume to decide WHAT to recompute or offload.
  * Estimates come from declared IR shapes (the verifier's infer-shape
    contract keeps those honest); an op/var with unknown shapes degrades to
    a NAMED warning and a 0-byte contribution — never a silently wrong
    number.  Ground truth is `compiled.memory_analysis()` from the XLA
    executable (xla_cross_check below); the delta rides the plan artifact
    and CI asserts agreement within PLANNER_XLA_TOLERANCE on the dense
    models.

Footprint classes:
    params      Parameter vars (trainable weights)
    opt_state   persistable non-Parameter state (optimizer moments, lr
                vars, BN running stats — everything the scope carries)
    activations non-persistable values produced by Forward-role ops (the
                fwd->bwd stash that bounds model size on a fixed-HBM chip)
    workspace   backward/optimizer temporaries (grads, @RENAME partials,
                recompute clones' outputs)
    feeds       the fed batch
    host        values parked in host memory by offload.py's memcpy_d2h
                (excluded from the device peak)
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from ..core import framework as fw

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "float16": 2, "bfloat16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1,
}

# Stated estimator-vs-XLA agreement contract (asserted in CI on the dense
# models, tests/test_memory.py): the planner's peak must land within this
# FACTOR of the XLA executable's accounted bytes.  The slack is honest:
# the planner counts declared IR vars while XLA counts post-fusion buffers
# (fusion elides most elementwise intermediates; donation aliases param
# in/outs) — the estimator's job is ranking rewrites and catching
# order-of-magnitude regressions, not byte-exact accounting.
PLANNER_XLA_TOLERANCE = 3.0

#: classes, in table order
CLASSES = ("params", "opt_state", "kv_cache", "activations", "workspace",
           "feeds", "host")


def var_bytes(v: Optional[fw.Variable], warn=None, name: str = "?",
              batch_size: Optional[int] = None) -> int:
    """Bytes of one declared var.  A -1 LEADING dim is the conventional
    dynamic batch axis: the caller-provided `batch_size` substitutes for
    it (bench/tools pass the batch they actually run).  Anything else
    unknown/dynamic contributes 0 bytes and a NAMED warning — never a
    fabricated number."""
    if v is None or v.shape is None:
        if warn is not None:
            warn("unknown-shape", name,
                 f"var {name!r} has no declared shape; it contributes 0 "
                 f"bytes to the plan")
        return 0
    n = 1
    for idx, d in enumerate(v.shape):
        d = int(d) if d is not None else -1
        if d < 0:
            if idx == 0 and batch_size:
                d = int(batch_size)
            else:
                if warn is not None:
                    warn("dynamic-dim", name,
                         f"var {name!r} shape {tuple(v.shape)} has a "
                         f"dynamic dim (pass batch_size= for a -1 batch "
                         f"axis); it contributes 0 bytes to the plan")
                return 0
        n *= d
    return n * _DTYPE_BYTES.get(v.dtype, 4)


def _role(op) -> int:
    return int(op.attrs.get(fw.OpRole.ROLE_ATTR_NAME, fw.OpRole.Forward))


def _is_opt(op) -> bool:
    return bool(_role(op) & fw.OpRole.Optimize)


def _is_bwd(op) -> bool:
    return (bool(_role(op) & fw.OpRole.Backward) and not _is_opt(op)) \
        or op.type.endswith("_grad")


def _sub_blocks(op):
    for a in op.attrs.values():
        if isinstance(a, fw.Block):
            yield a


def _op_reads(op) -> List[str]:
    """Names the op reads, including inside its sub-blocks (a while body's
    reads are uses at the parent op's position)."""
    names = [n for n in op.input_arg_names() if n]
    for sub in _sub_blocks(op):
        for sop in sub.ops:
            names.extend(_op_reads(sop))
    return names


class VarLife:
    """One var's planned lifetime."""

    __slots__ = ("name", "bytes", "klass", "def_idx", "last_use",
                 "last_fwd_use", "first_bwd_use")

    def __init__(self, name, nbytes, klass, def_idx):
        self.name = name
        self.bytes = nbytes
        self.klass = klass
        self.def_idx = def_idx
        self.last_use = def_idx
        self.last_fwd_use: Optional[int] = None
        self.first_bwd_use: Optional[int] = None

    @property
    def fwd_bwd_gap(self) -> int:
        """Op-count gap between the last forward read and the first
        backward read — the offload tier's 'long-lived stash' signal."""
        if self.first_bwd_use is None:
            return 0
        origin = (self.last_fwd_use if self.last_fwd_use is not None
                  else self.def_idx)
        return max(0, self.first_bwd_use - origin)

    def to_dict(self):
        return {"name": self.name, "bytes": self.bytes, "class": self.klass,
                "def": self.def_idx, "last_use": self.last_use,
                "first_bwd_use": self.first_bwd_use,
                "gap": self.fwd_bwd_gap}


class MemoryPlan:
    """The planner's product: peak watermark + lifetime table + class
    split, with the XLA cross-check delta attached when available."""

    def __init__(self, program: fw.Program):
        self.program = program
        self.peak_bytes = 0
        self.peak_op_index = 0
        self.peak_op_type = ""
        # bytes live AT the watermark, split by class
        self.peak_by_class: Dict[str, int] = {c: 0 for c in CLASSES}
        # class maxima over the whole program (activation peak is THE
        # number recompute optimizes; it need not coincide with the
        # total-peak op)
        self.class_peaks: Dict[str, int] = {c: 0 for c in CLASSES}
        self.lifetimes: Dict[str, VarLife] = {}
        self.warnings: List[dict] = []
        self.n_ops = 0
        # estimated forward-matmul-dominant FLOPs (recompute cost model)
        self.fwd_flops = 0.0
        self.bwd_flops = 0.0
        self.recompute_flops = 0.0
        # ground truth, attached by xla_cross_check
        self.xla: Optional[Dict[str, int]] = None

    # -- convenience ------------------------------------------------------
    @property
    def activation_peak_bytes(self) -> int:
        return self.class_peaks["activations"]

    @property
    def offloaded_bytes(self) -> int:
        return self.class_peaks["host"]

    @property
    def total_flops(self) -> float:
        return self.fwd_flops + self.bwd_flops + self.recompute_flops

    def warn(self, check: str, var: str, message: str):
        # one warning per (check, var): a var read 40 times is one problem
        key = (check, var)
        if not any((w["check"], w["var"]) == key for w in self.warnings):
            self.warnings.append(
                {"check": check, "severity": "warning", "var": var,
                 "message": message})

    def to_dict(self) -> dict:
        d = {
            "peak_bytes": self.peak_bytes,
            "peak_op_index": self.peak_op_index,
            "peak_op_type": self.peak_op_type,
            "peak_by_class": dict(self.peak_by_class),
            "class_peaks": dict(self.class_peaks),
            "activation_peak_bytes": self.activation_peak_bytes,
            "offloaded_bytes": self.offloaded_bytes,
            "n_ops": self.n_ops,
            "est_flops": {"fwd": self.fwd_flops, "bwd": self.bwd_flops,
                          "recompute": self.recompute_flops},
            "warnings": list(self.warnings),
        }
        if self.xla is not None:
            d["xla"] = dict(self.xla)
            if self.xla.get("peak_bytes"):
                d["xla_ratio"] = round(
                    self.peak_bytes / self.xla["peak_bytes"], 3)
        return d

    def table(self, top: int = 12) -> str:
        """Human-readable plan table (trace_report / hlo_diag render
        this)."""
        mb = 1.0 / 1e6
        lines = [
            f"peak {self.peak_bytes * mb:10.2f} MB at op "
            f"{self.peak_op_index} ({self.peak_op_type})",
        ]
        for c in CLASSES:
            if self.class_peaks[c] or self.peak_by_class[c]:
                lines.append(
                    f"  {c:11s} at-peak {self.peak_by_class[c] * mb:9.2f}"
                    f" MB   class-peak {self.class_peaks[c] * mb:9.2f} MB")
        if self.xla is not None:
            lines.append(
                f"  xla ground truth {self.xla['peak_bytes'] * mb:9.2f} MB"
                f" (args {self.xla['argument_bytes'] * mb:.2f}"
                f" + temp {self.xla['temp_bytes'] * mb:.2f}"
                f" + out {self.xla['output_bytes'] * mb:.2f}"
                f" - alias {self.xla['alias_bytes'] * mb:.2f})")
        livers = sorted(self.lifetimes.values(), key=lambda l: -l.bytes)
        lines.append("  largest vars (bytes, class, def->last_use, gap):")
        for lf in livers[:top]:
            lines.append(
                f"    {lf.bytes * mb:9.2f} MB  {lf.klass:11s} "
                f"[{lf.def_idx:4d},{lf.last_use:4d}] gap {lf.fwd_bwd_gap:4d}"
                f"  {lf.name}")
        for w in self.warnings[:8]:
            lines.append(f"  warning:{w['check']} {w['message']}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# FLOP estimate (the recompute pass's <= 1.35x cost-model input)
# ---------------------------------------------------------------------------


def _shape_prod(shape) -> float:
    n = 1.0
    for d in shape or ():
        if d and int(d) > 0:
            n *= int(d)
    return n


def op_flops(op, block) -> float:
    """Analytic matmul-dominant FLOPs of one op (2 FLOPs/MAC for the dot
    tier; output size for everything else — the elementwise tier is HBM-
    not FLOP-bound, so this under-counts it deliberately)."""
    def shp(name):
        v = block._find_var_recursive(name) if name else None
        return v.shape if v is not None and v.shape else ()

    t = op.type
    if t in ("mul", "matmul", "mul_grad", "matmul_grad"):
        xs = shp(op.input("X")[0] if op.input("X") else "")
        ys = shp(op.input("Y")[0] if op.input("Y") else "")
        if xs and ys:
            f = 2.0 * _shape_prod(xs) * _shape_prod(ys[1:] or ys)
            return f * (2.0 if t.endswith("_grad") else 1.0)
    if t in ("fused_attention", "fused_qkv_attention"):
        qs = shp((op.input("X") or op.input("Q") or [""])[0])
        if qs:
            b_t = _shape_prod(qs[:-1])
            d = qs[-1] if qs else 1
            return 4.0 * b_t * b_t / max(_shape_prod(qs[:1]), 1.0) * d
    total = 0.0
    for n in op.output_arg_names():
        total += _shape_prod(shp(n))
    return total


# ---------------------------------------------------------------------------
# the planner proper
# ---------------------------------------------------------------------------


def _classify(name: str, v: Optional[fw.Variable], producer_op,
              feed_set, host_names) -> str:
    if name in host_names:
        return "host"
    if name in feed_set or (v is not None and v.is_data
                            and producer_op is None):
        return "feeds"
    if v is not None and isinstance(v, fw.Parameter):
        return "params"
    if v is not None and getattr(v, "is_kv_cache", False):
        # KV cache pools/tables (KVCache / PagedKVCache vars_in tag):
        # the capacity denominator serving plans slot budgets against —
        # split out from opt_state so hlo_diag --memory shows the
        # resident decode footprint as its own row
        return "kv_cache"
    if v is not None and v.persistable:
        return "opt_state"
    if producer_op is not None and not _is_bwd(producer_op) \
            and not _is_opt(producer_op):
        return "activations"
    return "workspace"


def _sub_block_peak(block: fw.Block, plan: MemoryPlan,
                    batch_size: Optional[int] = None) -> int:
    """Self-footprint of a sub-block (while/conditional body): the body's
    own peak over its interior vars — charged as a transient at the
    parent op's position.  Vars resolved from outer scopes are charged by
    the outer walk (their reads are parent-op uses)."""
    interior = set(block.vars)
    live: Dict[str, int] = {}
    last_use: Dict[str, int] = {}
    for i, op in enumerate(block.ops):
        for n in _op_reads(op):
            if n in interior:
                last_use[n] = i
        for n in op.output_arg_names():
            if n and n in interior:
                last_use[n] = max(last_use.get(n, i), i)
    peak = cur = 0
    freed_at: Dict[int, List[str]] = {}
    for n, i in last_use.items():
        freed_at.setdefault(i, []).append(n)
    defined: set = set()
    for i, op in enumerate(block.ops):
        for n in op.output_arg_names():
            if n and n in interior and n not in defined:
                defined.add(n)
                b = var_bytes(block.vars.get(n), None, n, batch_size)
                live[n] = b
                cur += b
        nested = 0
        for sub in _sub_blocks(op):
            nested += _sub_block_peak(sub, plan, batch_size)
        peak = max(peak, cur + nested)
        for n in freed_at.get(i, ()):
            cur -= live.pop(n, 0)
    return peak


def plan_program(
    program: fw.Program,
    feed_names: Sequence[str] = (),
    fetch_names: Sequence[str] = (),
    scope=None,
    batch_size: Optional[int] = None,
) -> MemoryPlan:
    """Liveness-sweep the global block and return the MemoryPlan.

    Model (matches the executor's compiled-entry reality):
      * persistable/scope state (params, moments) is resident for the
        whole call — donated rw buffers never leave HBM;
      * feeds are resident from call start to their last read;
      * every other var is live from its producing op to its last read
        (fetch targets stay live to the end);
      * a while/conditional body contributes its own interior peak as a
        transient at the parent op's position.
    """
    plan = MemoryPlan(program)
    block = program.global_block()
    ops = block.ops
    plan.n_ops = len(ops)
    feed_set = set(feed_names)
    fetch_set = set(
        v.name if isinstance(v, fw.Variable) else v for v in fetch_names)
    host_names: set = set()
    for op in ops:
        if op.type == "memcpy_d2h":
            host_names.update(n for n in op.output_arg_names() if n)

    producer: Dict[str, Any] = {}
    for op in ops:
        for n in op.output_arg_names():
            if n and n not in producer:
                producer[n] = op

    # ---- lifetimes ------------------------------------------------------
    lifetimes = plan.lifetimes

    def _life(name: str, idx: int) -> Optional[VarLife]:
        lf = lifetimes.get(name)
        if lf is not None:
            return lf
        v = block._find_var_recursive(name)
        op = producer.get(name)
        klass = _classify(name, v, op, feed_set, host_names)
        persistable = (v is not None and v.persistable) \
            or (scope is not None and scope.has_var(name))
        if persistable and klass in ("params", "opt_state"):
            def_idx = 0
        elif klass == "feeds":
            def_idx = 0
        else:
            def_idx = idx
        lf = VarLife(name, var_bytes(v, None, name, batch_size), klass,
                     def_idx)
        lifetimes[name] = lf
        return lf

    read_names: set = set()
    for i, op in enumerate(ops):
        for n in _op_reads(op):
            read_names.add(n)
            lf = lifetimes.get(n)
            if lf is None:
                # read before any producer: feed / state / boundary input
                lf = _life(n, 0)
            lf.last_use = max(lf.last_use, i)
            if _is_bwd(op) or _is_opt(op):
                if lf.first_bwd_use is None:
                    lf.first_bwd_use = i
            else:
                lf.last_fwd_use = i
        for n in op.output_arg_names():
            if not n:
                continue
            lf = _life(n, i)
            lf.last_use = max(lf.last_use, i)
        f = op_flops(op, block)
        if _is_bwd(op):
            if op.attrs.get("recompute_segment") is not None:
                plan.recompute_flops += f
            else:
                plan.bwd_flops += f
        elif not _is_opt(op):
            plan.fwd_flops += f
    for n in fetch_set:
        lf = lifetimes.get(n)
        if lf is not None:
            lf.last_use = len(ops) - 1
    # persistable state lives to the end (written back to the scope)
    for lf in lifetimes.values():
        if lf.klass in ("params", "opt_state"):
            lf.last_use = len(ops) - 1
    # named degradation: a READ (or fetched) var whose bytes degraded to
    # 0 gets a warning naming it; write-only outputs stay silent (XLA
    # DCEs them — 0 is the honest post-DCE number)
    for lf in lifetimes.values():
        if lf.bytes == 0 and (lf.name in read_names
                              or lf.name in fetch_set):
            var_bytes(block._find_var_recursive(lf.name), plan.warn,
                      lf.name, batch_size)

    # ---- sweep ----------------------------------------------------------
    freed_at: Dict[int, List[VarLife]] = {}
    born_at: Dict[int, List[VarLife]] = {}
    for lf in lifetimes.values():
        born_at.setdefault(lf.def_idx, []).append(lf)
        freed_at.setdefault(lf.last_use, []).append(lf)
    cur_by_class = {c: 0 for c in CLASSES}
    for i, op in enumerate(ops):
        for lf in born_at.get(i, ()):
            cur_by_class[lf.klass] += lf.bytes
        nested = 0
        for sub in _sub_blocks(op):
            nested += _sub_block_peak(sub, plan, batch_size)
        # device peak excludes the host class
        cur = sum(v for c, v in cur_by_class.items() if c != "host") + nested
        if cur > plan.peak_bytes:
            plan.peak_bytes = cur
            plan.peak_op_index = i
            plan.peak_op_type = op.type
            plan.peak_by_class = dict(cur_by_class)
            plan.peak_by_class["workspace"] += nested
        for c in CLASSES:
            extra = nested if c == "workspace" else 0
            plan.class_peaks[c] = max(plan.class_peaks[c],
                                      cur_by_class[c] + extra)
        for lf in freed_at.get(i, ()):
            cur_by_class[lf.klass] -= lf.bytes
    return plan


# ---------------------------------------------------------------------------
# call-mode variants
# ---------------------------------------------------------------------------


def plan_accumulated(program: fw.Program, feed_names: Sequence[str] = (),
                     fetch_names: Sequence[str] = (),
                     accumulate_steps: int = 1, scope=None,
                     batch_size: Optional[int] = None) -> dict:
    """Footprint of Executor.run_accumulated's scan-carry form: the
    fwd/bwd prefix's per-micro-batch peak rides next to the K-independent
    carries (grad sums + rw state) and the K-stacked feed arrays."""
    plan = plan_program(program, feed_names, fetch_names, scope=scope,
                        batch_size=batch_size)
    block = program.global_block()
    grad_names = sorted({
        n for op in block.ops if _is_opt(op)
        for n in op.inputs.get("Grad", []) if n})
    grad_sum_bytes = sum(
        var_bytes(block._find_var_recursive(n), plan.warn, n, batch_size)
        for n in grad_names)
    feed_bytes = sum(
        var_bytes(block._find_var_recursive(n), plan.warn, n, batch_size)
        for n in feed_names)
    k = max(int(accumulate_steps), 1)
    return {
        "accumulate_steps": k,
        "prefix_peak_bytes": plan.peak_bytes,
        "grad_sum_bytes": grad_sum_bytes,
        "feed_stack_bytes": feed_bytes * k,
        "peak_bytes": plan.peak_bytes + grad_sum_bytes
        + feed_bytes * max(k - 1, 0),
        "activation_peak_bytes": plan.activation_peak_bytes,
        "plan": plan,
    }


def plan_stages(stages, schedule: str = "gpipe",
                micro_batches: int = 1,
                batch_size: Optional[int] = None) -> List[dict]:
    """Per-stage footprint of a pipeline partition (PipelineStages from
    parallel/pipeline/split_program): each stage's own plan PLUS its
    stash bytes multiplied by the schedule's in-flight micro-batch bound
    (GPipe stashes all K on stage 0; 1F1B caps at min(K, S)) — the
    activation-aware cost split_program's auto-balancer can consume."""
    from ..parallel.pipeline.schedule import max_in_flight

    out = []
    n_stages = len(list(stages))
    for st in stages:
        blk = st.program.global_block()
        feedish = (list(st.feeds) + [n for n, _, _ in st.fwd_inputs]
                   + [n for n, _, _ in st.bwd_inputs] + list(st.bwd_feeds))
        plan = plan_program(st.program, feedish,
                            [n for n, _, _ in st.fwd_outputs]
                            + [n for n, _, _ in st.bwd_outputs],
                            batch_size=batch_size)
        stash_bytes = sum(
            var_bytes(blk._find_var_recursive(n), plan.warn, n, batch_size)
            for n in st.stash)
        inflight = max_in_flight(n_stages, max(micro_batches, 1), schedule)
        out.append({
            "stage": st.index,
            "peak_bytes": plan.peak_bytes,
            "activation_peak_bytes": plan.activation_peak_bytes,
            "param_bytes": plan.class_peaks["params"],
            "stash_bytes": stash_bytes,
            "in_flight": inflight,
            "stash_total_bytes": stash_bytes * inflight,
            "total_bytes": plan.peak_bytes
            + stash_bytes * max(inflight - 1, 0),
            "plan": plan,
        })
    return out


# ---------------------------------------------------------------------------
# XLA ground truth
# ---------------------------------------------------------------------------


def xla_memory_stats(compiled) -> Dict[str, int]:
    """Normalize jax's CompiledMemoryStats into the plan artifact's
    ground-truth dict.  peak_bytes = arguments + temps + non-aliased
    outputs: donated rw-state outputs alias their argument buffers, so
    alias bytes are counted once."""
    ma = compiled.memory_analysis()
    arg = int(getattr(ma, "argument_size_in_bytes", 0))
    temp = int(getattr(ma, "temp_size_in_bytes", 0))
    out = int(getattr(ma, "output_size_in_bytes", 0))
    alias = int(getattr(ma, "alias_size_in_bytes", 0))
    return {
        "argument_bytes": arg,
        "temp_bytes": temp,
        "output_bytes": out,
        "alias_bytes": alias,
        "host_temp_bytes": int(getattr(ma, "host_temp_size_in_bytes", 0)),
        "peak_bytes": arg + temp + max(out - alias, 0),
    }


def xla_cross_check(plan: MemoryPlan, exe, program, feed, fetch_list,
                    scope) -> Dict[str, int]:
    """Attach the XLA executable's memory accounting to `plan`.

    Compiles the plain Executor.run entry AOT on the SAME (feed, fetch,
    scope) signature and reads CompiledMemoryStats — the ground truth the
    CI agreement gate compares the estimator against
    (PLANNER_XLA_TOLERANCE).  Costs one extra XLA compile; call it from
    tools/bench paths, never hot loops."""
    # the run materializes scope state the lowering reads (and compiles
    # the entry `lower` then finds)
    exe.run(program, feed=feed, fetch_list=fetch_list, scope=scope)
    lowered = exe.lower(program, feed, fetch_list, scope)
    stats = xla_memory_stats(lowered.compile())
    plan.xla = stats
    return stats


# ---------------------------------------------------------------------------
# telemetry (zero-cost with FLAGS_monitor off)
# ---------------------------------------------------------------------------


def publish_plan(plan: MemoryPlan, name: str = "main") -> None:
    """Export the plan as gauges + a flight `memory.plan` event.  One
    enabled() read when FLAGS_monitor is off — the zero-cost contract."""
    from .. import monitor
    from ..monitor import flight

    if not monitor.enabled():
        return
    monitor.gauge("memory.activation_peak_bytes").set(
        plan.activation_peak_bytes)
    monitor.gauge("memory.peak_bytes").set(plan.peak_bytes)
    monitor.gauge("memory.offloaded_bytes").set(plan.offloaded_bytes)
    flight.record(
        "memory.plan", name=name, peak_bytes=plan.peak_bytes,
        peak_op_index=plan.peak_op_index, peak_op_type=plan.peak_op_type,
        activation_peak_bytes=plan.activation_peak_bytes,
        offloaded_bytes=plan.offloaded_bytes,
        peak_by_class={c: plan.peak_by_class[c] for c in CLASSES},
        warnings=len(plan.warnings))
