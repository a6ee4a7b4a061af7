"""Static roofline / launch-cost model over the Program IR.

Reference role: the reference framework's profiler/timeline tier
(paddle/fluid/platform/profiler.cc) records what DID happen; this module
predicts what MUST happen from the declared IR alone — per-op analytic
FLOPs and HBM traffic, a roofline classification against a declared
device model, and a launch-cost term — so "where does the next
millisecond come from?" is answerable before a chip is ever attached.

The model, per op:

    t_compute = flops / peak_flops            (MXU residency floor)
    t_memory  = bytes / hbm_bytes_per_s       (HBM residency floor)
    bound     = "launch"  if max(t_compute, t_memory) < launch_overhead
                "compute" if t_compute >= t_memory
                "memory"  otherwise

and per program (the ISSUE's contract, verbatim):

    predicted_s = max(total_flops/peak, total_bytes/bw)
                  + n_launches * launch_overhead

The launch term is the additive dispatch cost XLA pays once per fused
computation; statically we charge one launch per IR op, which makes the
predicted time an UPPER bound on launch cost (fusion merges launches) and
`launch_bound_fraction` the pessimistic bound ROADMAP item 1 wants before
committing to the decode megakernel.

Inputs are reused, not re-derived: FLOPs come from the memory planner's
`op_flops` (2 FLOPs/MAC on the dot tier, output-size on the elementwise
tier), bytes from its `var_bytes` (declared IR shapes; -1 leading dim =
batch axis; unknown shapes contribute 0 bytes + a NAMED warning, never a
fabricated number), and shape honesty from the verifier's infer-shape
contract.  Device constants live in DEVICE_MODELS; the per-launch
overhead of the host entry is MEASURED by `python bench.py --model
dispatch` (CPU-measurable today, re-armed on chip) and overridable via
FLAGS_launch_overhead_us.

Zero-cost contract: `publish_cost` writes gauges + one flight event only
when FLAGS_monitor is on — one flag read otherwise (same shape as
memory.planner.publish_plan).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from ..core import framework as fw
from ..core import registry as _op_registry
from ..flags import FLAGS
from ..memory.planner import _sub_blocks, op_flops, var_bytes


class DeviceModel:
    """One device's roofline constants.

    peak_flops        bf16 peak FLOP/s per chip
    hbm_bytes_per_s   HBM (or host DRAM) bandwidth in bytes/s
    launch_overhead_s additive per-dispatch cost of one fused computation
    source            where the constants came from ("datasheet",
                      "measured", "flags") — rides every report so a
                      number is never quoted without its provenance
    """

    __slots__ = ("name", "peak_flops", "hbm_bytes_per_s",
                 "launch_overhead_s", "source")

    def __init__(self, name: str, peak_flops: float, hbm_bytes_per_s: float,
                 launch_overhead_s: float, source: str = "datasheet"):
        self.name = name
        self.peak_flops = float(peak_flops)
        self.hbm_bytes_per_s = float(hbm_bytes_per_s)
        self.launch_overhead_s = float(launch_overhead_s)
        self.source = source

    def replace(self, **kw) -> "DeviceModel":
        d = {s: getattr(self, s) for s in self.__slots__}
        d.update(kw)
        return DeviceModel(**d)

    def to_dict(self) -> dict:
        return {s: getattr(self, s) for s in self.__slots__}

    def __repr__(self):  # pragma: no cover - debugging nicety
        return (f"DeviceModel({self.name!r}, peak={self.peak_flops:.3g}, "
                f"bw={self.hbm_bytes_per_s:.3g}, "
                f"launch={self.launch_overhead_s:.2g}s, {self.source})")


#: keyed by PJRT device_kind (datasheet bf16 peaks + HBM bandwidth); the
#: "cpu-host" entry is the CPU backend's model, whose launch overhead the
#: dispatch microbench measures — its compute/bandwidth constants are
#: order-of-magnitude host numbers, good enough to CLASSIFY ops while the
#: launch term (the thing we can measure on CPU today) stays honest.
DEVICE_MODELS: Dict[str, DeviceModel] = {
    "TPU v4": DeviceModel("TPU v4", 275e12, 1228e9, 2e-6),
    "TPU v5 lite": DeviceModel("TPU v5 lite", 197e12, 819e9, 2e-6),
    "TPU v5e": DeviceModel("TPU v5e", 197e12, 819e9, 2e-6),
    "TPU v5p": DeviceModel("TPU v5p", 459e12, 2765e9, 2e-6),
    "TPU v5": DeviceModel("TPU v5", 459e12, 2765e9, 2e-6),
    "TPU v6 lite": DeviceModel("TPU v6 lite", 918e12, 1640e9, 2e-6),
    "TPU v6e": DeviceModel("TPU v6e", 918e12, 1640e9, 2e-6),
    # launch constant measured by `python bench.py --model dispatch` on
    # the committed dev box (300 cache-hit runs x3: 148 us/call mean,
    # +-12 us spread); compute/bandwidth are order-of-magnitude host
    # numbers — good enough to CLASSIFY ops off-chip
    "cpu-host": DeviceModel("cpu-host", 1e11, 2e10, 148e-6,
                            source="measured"),
}


def resolve_device_model(name: Optional[str] = None) -> DeviceModel:
    """Resolution order: explicit arg > FLAGS_device_model > the jax
    backend's device_kind.  FLAGS_peak_flops / FLAGS_launch_overhead_us
    then override individual constants (source becomes "flags").

    "cpu-host" is chosen only when the backend IS the CPU (or by name).
    An accelerator whose device_kind is not in the table, or an unknown
    name, raises: a roofline quoted against the wrong machine is worse
    than none."""
    key = name or FLAGS.device_model
    if not key:
        import jax

        dev = jax.devices()[0]
        key = "cpu-host" if dev.platform == "cpu" else dev.device_kind
    dm = DEVICE_MODELS.get(key)
    if dm is None:
        raise LookupError(
            f"no device model for {key!r} (known: "
            f"{sorted(DEVICE_MODELS)}); add its peaks with their source "
            f"to analysis/costmodel.py DEVICE_MODELS")
    if FLAGS.peak_flops > 0:
        dm = dm.replace(peak_flops=float(FLAGS.peak_flops), source="flags")
    if FLAGS.launch_overhead_us > 0:
        dm = dm.replace(launch_overhead_s=FLAGS.launch_overhead_us * 1e-6,
                        source="flags")
    return dm


class OpCost:
    """One op's analytic cost and roofline classification."""

    __slots__ = ("index", "type", "flops", "bytes", "t_compute", "t_memory",
                 "bound")

    def __init__(self, index: int, type_: str, flops: float, nbytes: int,
                 device: DeviceModel):
        self.index = index
        self.type = type_
        self.flops = float(flops)
        self.bytes = int(nbytes)
        self.t_compute = self.flops / device.peak_flops
        self.t_memory = self.bytes / device.hbm_bytes_per_s
        if max(self.t_compute, self.t_memory) < device.launch_overhead_s:
            self.bound = "launch"
        elif self.t_compute >= self.t_memory:
            self.bound = "compute"
        else:
            self.bound = "memory"

    @property
    def t_roofline(self) -> float:
        return max(self.t_compute, self.t_memory)

    def to_dict(self) -> dict:
        return {"index": self.index, "type": self.type, "flops": self.flops,
                "bytes": self.bytes, "t_compute": self.t_compute,
                "t_memory": self.t_memory, "bound": self.bound}


#: op families XLA reliably folds into a neighboring kernel's prologue/
#: epilogue: elementwise arithmetic and activations, dtype casts, pure
#: layout moves, constant fills, aliasing bookkeeping, and the feed/
#: fetch markers (host transfers, not launches).  The fusion-corrected
#: launch count charges these ZERO and everything else (dots, Pallas
#: kernels, reductions, gathers) ONE — the r13-documented fix for the
#: one-launch-per-IR-op decode bias (predicted-vs-measured 10.5x on
#: decode b1).  `n_launches` stays the honest upper bound; the corrected
#: figure is reported NEXT to it, never instead of it.
FUSED_EPILOGUE_OPS = frozenset({
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min", "scale",
    "cast", "reshape", "reshape2", "transpose", "transpose2", "split",
    "concat", "expand", "squeeze", "squeeze2", "unsqueeze", "unsqueeze2",
    "stack", "slice", "fill_constant",
    "fill_constant_batch_size_like", "assign", "equal", "not_equal",
    "less_than", "greater_than", "sign", "abs", "relu", "gelu",
    "sigmoid", "tanh", "exp", "sqrt", "square", "clip", "dropout",
    "feed", "fetch",
})


class ProgramCost:
    """The cost model's product for one program."""

    def __init__(self, name: str, device: DeviceModel):
        self.name = name
        self.device = device
        self.ops: List[OpCost] = []
        self.total_flops = 0.0
        self.total_bytes = 0
        self.n_launches = 0
        self.n_launches_fused = 0
        self.warnings: List[dict] = []

    # -- derived ----------------------------------------------------------
    @property
    def launch_seconds(self) -> float:
        return self.n_launches * self.device.launch_overhead_s

    @property
    def launch_seconds_fused(self) -> float:
        return self.n_launches_fused * self.device.launch_overhead_s

    @property
    def roofline_seconds(self) -> float:
        return max(self.total_flops / self.device.peak_flops,
                   self.total_bytes / self.device.hbm_bytes_per_s)

    @property
    def predicted_seconds(self) -> float:
        """The ISSUE contract: max(flops/peak, bytes/bw) + n·overhead."""
        return self.roofline_seconds + self.launch_seconds

    @property
    def predicted_seconds_fused(self) -> float:
        """Roofline + the fusion-corrected launch count (compiler-fused
        epilogue ops charged zero) — the better point estimate; the
        plain predicted_seconds stays the upper bound."""
        return self.roofline_seconds + self.launch_seconds_fused

    @property
    def launch_bound_fraction(self) -> float:
        """Fraction of the predicted step spent on dispatch — ROADMAP
        item 1's go/no-go number for the decode megakernel."""
        p = self.predicted_seconds
        return (self.launch_seconds / p) if p > 0 else 0.0

    @property
    def launch_bound_fraction_fused(self) -> float:
        p = self.predicted_seconds_fused
        return (self.launch_seconds_fused / p) if p > 0 else 0.0

    def bound_counts(self) -> Dict[str, int]:
        out = {"compute": 0, "memory": 0, "launch": 0}
        for oc in self.ops:
            out[oc.bound] += 1
        return out

    def warn(self, check: str, var: str, message: str):
        # one warning per (check, var), like MemoryPlan.warn
        key = (check, var)
        if not any((w["check"], w["var"]) == key for w in self.warnings):
            self.warnings.append(
                {"check": check, "severity": "warning", "var": var,
                 "message": message})

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "device": self.device.to_dict(),
            "n_ops": len(self.ops),
            "n_launches": self.n_launches,
            "n_launches_fused": self.n_launches_fused,
            "total_flops": self.total_flops,
            "total_bytes": self.total_bytes,
            "roofline_seconds": self.roofline_seconds,
            "launch_seconds": self.launch_seconds,
            "predicted_seconds": self.predicted_seconds,
            "predicted_seconds_fused": self.predicted_seconds_fused,
            "launch_bound_fraction": round(self.launch_bound_fraction, 4),
            "launch_bound_fraction_fused":
                round(self.launch_bound_fraction_fused, 4),
            "bound_counts": self.bound_counts(),
            "ops": [oc.to_dict() for oc in self.ops],
            "warnings": list(self.warnings),
        }

    def table(self, top: int = 12) -> str:
        """Human-readable roofline table (perf_report/trace_report render
        this)."""
        us = 1e6
        bc = self.bound_counts()
        lines = [
            f"program {self.name!r} on {self.device.name} "
            f"({self.device.source}: peak {self.device.peak_flops:.3g} "
            f"FLOP/s, bw {self.device.hbm_bytes_per_s:.3g} B/s, launch "
            f"{self.device.launch_overhead_s * us:.1f} us)",
            f"  predicted {self.predicted_seconds * us:10.1f} us = "
            f"roofline {self.roofline_seconds * us:.1f} us + "
            f"{self.n_launches} launches x "
            f"{self.device.launch_overhead_s * us:.1f} us",
            f"  fusion-corrected {self.predicted_seconds_fused * us:6.1f} "
            f"us ({self.n_launches_fused} launches after compiler fusion "
            f"of epilogue ops)",
            f"  launch-bound fraction {self.launch_bound_fraction:.1%} "
            f"(corrected {self.launch_bound_fraction_fused:.1%})   "
            f"ops: {bc['compute']} compute / {bc['memory']} memory / "
            f"{bc['launch']} launch",
            f"  total {self.total_flops:.3g} FLOPs, "
            f"{self.total_bytes / 1e6:.2f} MB HBM traffic",
        ]
        heavy = sorted(self.ops, key=lambda o: -o.t_roofline)[:top]
        if heavy:
            lines.append(
                "  heaviest ops (roofline us, bound, flops, bytes):")
        for oc in heavy:
            lines.append(
                f"    {oc.t_roofline * us:9.2f} us  {oc.bound:7s} "
                f"{oc.flops:10.3g}  {oc.bytes / 1e6:8.3f} MB  "
                f"[{oc.index:3d}] {oc.type}")
        for w in self.warnings[:8]:
            lines.append(f"  warning:{w['check']} {w['message']}")
        return "\n".join(lines)


def _op_bytes(op, block: fw.Block, cost: ProgramCost,
              batch_size: Optional[int]) -> int:
    """HBM traffic of one op: every distinct input read + output write,
    sized from the declared IR shapes.  Deliberately ignores cache reuse
    (a roofline model charges main-memory traffic once per touch)."""
    total = 0
    seen = set()
    for arg in list(op.input_arg_names()) + list(op.output_arg_names()):
        if not arg or arg in seen:
            continue
        seen.add(arg)
        v = block._find_var_recursive(arg)
        total += var_bytes(v, cost.warn, arg, batch_size)
    return total


def _walk_block(block: fw.Block, cost: ProgramCost,
                batch_size: Optional[int], index_base: int) -> int:
    """Cost every op in `block` (and, once, each sub-block body); returns
    the running op index."""
    idx = index_base
    for op in block.ops:
        if _op_registry.lookup(op.type) is None \
                and _op_registry.get_grad_lowering(op.type) is None \
                and op.type not in ("feed", "fetch"):
            cost.warn("unregistered-op", op.type,
                      f"op {op.type!r} is not in the op registry; its "
                      f"FLOPs ride the elementwise (output-size) estimate")
        flops = op_flops(op, block)
        nbytes = _op_bytes(op, block, cost, batch_size)
        cost.ops.append(OpCost(idx, op.type, flops, nbytes, cost.device))
        cost.total_flops += flops
        cost.total_bytes += nbytes
        cost.n_launches += 1
        if op.type not in FUSED_EPILOGUE_OPS:
            cost.n_launches_fused += 1
        idx += 1
        for sub in _sub_blocks(op):
            cost.warn("sub-block", op.type,
                      f"op {op.type!r} carries a sub-block; its body is "
                      f"costed ONCE (trip count unmodeled) — treat this "
                      f"program's prediction as a per-iteration floor")
            idx = _walk_block(sub, cost, batch_size, idx)
    return idx


def cost_program(
    program: fw.Program,
    name: str = "main",
    batch_size: Optional[int] = None,
    device: Optional[DeviceModel] = None,
    feed_names: Sequence[str] = (),
) -> ProgramCost:
    """Roofline-cost every op of `program`'s global block (sub-block
    bodies once each) against `device` (default: resolve_device_model()).

    batch_size substitutes for -1 leading dims exactly as the memory
    planner does; feed_names is accepted for signature parity with
    plan_program (feeds are costed at their consuming ops either way).
    """
    del feed_names  # sizes come from declared shapes; kept for parity
    dm = device or resolve_device_model()
    cost = ProgramCost(name, dm)
    _walk_block(program.global_block(), cost, batch_size, 0)
    return cost


# ---------------------------------------------------------------------------
# telemetry (zero-cost with FLAGS_monitor off)
# ---------------------------------------------------------------------------


def publish_cost(cost: ProgramCost, name: Optional[str] = None) -> None:
    """Export per-program attribution gauges + a flight `cost.program`
    event.  One enabled() read when FLAGS_monitor is off — the zero-cost
    contract (mirrors memory.planner.publish_plan)."""
    from .. import monitor
    from ..monitor import flight

    if not monitor.enabled():
        return
    tag = name or cost.name
    monitor.gauge(f"cost.{tag}.op_count").set(len(cost.ops))
    monitor.gauge(f"cost.{tag}.launch_count").set(cost.n_launches)
    monitor.gauge(f"cost.{tag}.launch_count_fused").set(
        cost.n_launches_fused)
    monitor.gauge(f"cost.{tag}.predicted_step_seconds").set(
        cost.predicted_seconds)
    monitor.gauge(f"cost.{tag}.predicted_step_seconds_fused").set(
        cost.predicted_seconds_fused)
    monitor.gauge(f"cost.{tag}.launch_bound_fraction").set(
        cost.launch_bound_fraction)
    monitor.gauge(f"cost.{tag}.launch_bound_fraction_fused").set(
        cost.launch_bound_fraction_fused)
    monitor.gauge(f"cost.{tag}.total_flops").set(cost.total_flops)
    monitor.gauge(f"cost.{tag}.hbm_bytes").set(cost.total_bytes)
    flight.record(
        "cost.program", name=tag, device=cost.device.name,
        device_source=cost.device.source, n_ops=len(cost.ops),
        n_launches=cost.n_launches,
        n_launches_fused=cost.n_launches_fused,
        total_flops=cost.total_flops,
        total_bytes=cost.total_bytes,
        roofline_seconds=cost.roofline_seconds,
        launch_seconds=cost.launch_seconds,
        predicted_seconds=cost.predicted_seconds,
        predicted_seconds_fused=cost.predicted_seconds_fused,
        launch_bound_fraction=round(cost.launch_bound_fraction, 4),
        launch_bound_fraction_fused=round(
            cost.launch_bound_fraction_fused, 4),
        bound_counts=cost.bound_counts(), warnings=len(cost.warnings))
