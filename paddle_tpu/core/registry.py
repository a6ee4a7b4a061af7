"""Op registry: each op type carries a JAX lowering, optional shape inference,
and a grad-op maker.

Capability parity with the reference's OpRegistry / OpInfoMap / GradOpDescMaker
(reference: paddle/fluid/framework/op_registry.h:197-240, op_info.h,
grad_op_desc_maker.h:34-159), redesigned TPU-first:

  * Instead of per-place kernel maps (OpKernelType{place,dtype,layout,library},
    op_kernel_type.h:27), an op has ONE lowering: a pure JAX function.  XLA owns
    device placement, layout, dtype promotion and fusion — the whole kernel-
    dispatch/data-transform layer (operator.cc:878-971) is deleted by design.
  * The default grad maker does not require hand-written grad kernels: it emits
    a `<type>_grad` op whose lowering calls `jax.vjp` of the forward lowering.
    Hand-written grad makers remain possible for ops with structured sparse
    gradients (e.g. lookup_table -> SelectedRows-style row updates).
  * An op whose kernel writes what its backward needs (a logsumexp, a
    context) names those output slots as `residuals`; the default grad
    maker hands them to the grad op, registered with `residual_grad`, which
    then calls the backward kernels directly instead of re-running the
    forward under `jax.vjp` for them.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence

from . import framework as fw

# ---------------------------------------------------------------------------


class LowerContext:
    """Handed to op lowerings at trace time.

    inputs:  slot -> list of jax values (or None for missing optional slots)
    attrs:   op attrs dict
    op:      the IR Operator being lowered
    executor_ctx: trace-scoped state (rng key counter, is_test, mesh, ...)
    """

    def __init__(self, op, attrs, executor_ctx):
        self.op = op
        self.attrs = attrs
        self.executor_ctx = executor_ctx

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def next_rng_key(self):
        return self.executor_ctx.next_rng_key(self.op)

    @property
    def is_test(self):
        return self.executor_ctx.is_test


class OpDef:
    def __init__(
        self,
        type: str,
        lower: Callable,
        infer_shape: Optional[Callable] = None,
        grad_maker: Optional[Callable] = None,
        no_grad: bool = False,
        inplace_outputs: Optional[Dict[str, str]] = None,
        derives_rng=False,
        residuals: Sequence[str] = (),
        unfilled: Sequence[str] = (),
        doc: str = "",
    ):
        self.type = type
        self.lower = lower
        self.infer_shape = infer_shape
        self.grad_maker = grad_maker
        self.no_grad = no_grad
        # output slot -> input slot aliases (optimizer in-place updates)
        self.inplace_outputs = inplace_outputs or {}
        # RNG contract metadata: whether the LOWERING may call
        # ctx.next_rng_key() (draw from the step key).  Either a bool or a
        # predicate `fn(op) -> bool` for ops whose randomness is attr-gated
        # (fused attention weights-dropout).  The executor's step-key
        # threading (executor.op_threads_rng) must cover every op for which
        # this is true — the static verifier (paddle_tpu/analysis) checks
        # that, turning the PR-4 "random op missing from _RANDOM_OPS" bug
        # class into a pre-compile error.
        self.derives_rng = derives_rng
        # output slots the grad op reads back as INPUTS: what the forward
        # lowering computed anyway and the backward needs (a kernel's
        # logsumexp, its context).  default_grad_maker wires them through
        # when the op instance declares them all; the grad op itself is
        # registered with residual_grad below.
        self.residuals = tuple(residuals)
        # output slots that may hold elements nothing has written (a
        # buffer the op allocates and fills only as far as its data
        # reaches; its grad op knows how far).  The finite checks skip
        # them: check_nan_inf (core/executor.py) and the numerics tier's
        # per-op-output rows (analysis/numerics.py).
        self.unfilled = tuple(unfilled)
        self.doc = doc

    def op_derives_rng(self, op) -> bool:
        """Whether THIS op instance may draw PRNG bits when lowered."""
        if callable(self.derives_rng):
            return bool(self.derives_rng(op))
        return bool(self.derives_rng)


_registry: Dict[str, OpDef] = {}


def register(
    type: str,
    infer_shape=None,
    grad_maker=None,
    no_grad=False,
    inplace_outputs=None,
    derives_rng=False,
    residuals=(),
    unfilled=(),
    doc="",
):
    """Decorator registering `fn` as the lowering for op `type`.

    The lowering signature is `fn(ctx, ins) -> {out_slot: [values]}` where
    `ins` maps input slot -> list of traced jax values.  Lowerings that
    call ctx.next_rng_key() MUST declare derives_rng (bool or
    `fn(op) -> bool`); the static verifier cross-checks the declaration
    against the executor's step-key threading.
    """

    def deco(fn):
        if type in _registry:
            raise ValueError(f"op {type!r} already registered")
        _registry[type] = OpDef(
            type,
            fn,
            infer_shape=infer_shape,
            grad_maker=grad_maker,
            no_grad=no_grad,
            inplace_outputs=inplace_outputs,
            derives_rng=derives_rng,
            residuals=residuals,
            unfilled=unfilled,
            doc=doc or (fn.__doc__ or ""),
        )
        return fn

    return deco


def lookup(type: str) -> Optional[OpDef]:
    return _registry.get(type)


def get(type: str) -> OpDef:
    opdef = _registry.get(type)
    if opdef is None:
        raise KeyError(
            f"Operator {type!r} has no registered lowering. "
            f"Registered: {sorted(_registry)[:40]}..."
        )
    return opdef


def unfilled_slots(type: str) -> tuple:
    """The output slots op `type` registers as `unfilled` (none for a
    type that has no OpDef of its own, as a grad op's)."""
    opdef = _registry.get(type)
    return opdef.unfilled if opdef is not None else ()


def all_ops() -> List[str]:
    return sorted(_registry)


# ---------------------------------------------------------------------------
# Generic grad machinery
# ---------------------------------------------------------------------------
#
# For forward op X with inputs I, outputs O, the default grad maker emits:
#     X_grad(inputs = I  +  O@GRAD slots) -> I@GRAD slots
# Its lowering re-traces X's forward lowering under jax.vjp and pulls back the
# incoming output cotangents.  This mirrors DefaultGradOpDescMaker
# (grad_op_desc_maker.h:159) but needs no per-op grad code, and because the
# whole program is compiled as one XLA computation, the re-traced forward is
# fused/DCE'd by XLA (no double compute for most ops).


GRAD_SUFFIX = "@GRAD"


def _grad_slot(slot: str) -> str:
    return slot + GRAD_SUFFIX


def default_grad_maker(op, no_grad_set, grad_sub_block_map=None):
    """Build the grad op desc(s) for `op`.  Returns a list of dicts:
    {type, inputs, outputs, attrs} using variable *names*.

    Inputs: all forward input slots (same names) + grad slots for each forward
    output.  Outputs: grad slots for each forward input not in no_grad_set.
    An op that declares every slot of its type's `residuals` hands those
    outputs on as inputs too, under their own slot names (a residual that
    carries no gradient gets no grad slot).
    """
    inputs = {slot: list(names) for slot, names in op.inputs.items()}
    opdef = lookup(op.type)
    residuals = opdef.residuals if opdef is not None else ()
    if not all(op.outputs.get(slot) for slot in residuals):
        residuals = ()
    for slot, names in op.outputs.items():
        if slot in residuals:
            inputs[slot] = list(names)
            if all(n in no_grad_set for n in names):
                continue
        # forward outputs may be needed for the vjp of stateful ops; pass grads
        inputs[_grad_slot(slot)] = [fw.grad_var_name(n) for n in names]
    outputs = {}
    for slot, names in op.inputs.items():
        outs = []
        for n in names:
            if n in no_grad_set:
                outs.append("")  # hole: no grad wanted for this input
            else:
                outs.append(fw.grad_var_name(n))
        outputs[_grad_slot(slot)] = outs
    attrs = dict(op.attrs)
    attrs[fw.OpRole.ROLE_ATTR_NAME] = fw.OpRole.Backward
    return [
        {
            "type": op.type + "_grad",
            "inputs": inputs,
            "outputs": outputs,
            "attrs": attrs,
        }
    ]


def lower_generic_grad(fwd_type: str, ctx: LowerContext, ins):
    """Lowering for `<fwd_type>_grad` ops emitted by default_grad_maker."""
    import jax

    _note_grad_route("grad_generic")
    opdef = get(fwd_type)
    fwd_slots = [s for s in ins if not s.endswith(GRAD_SUFFIX)]
    grad_slots = [s for s in ins if s.endswith(GRAD_SUFFIX)]

    fwd_ins = {s: ins[s] for s in fwd_slots}

    # Flatten forward inputs into a list for vjp; remember structure.
    flat_names: List[tuple] = []  # (slot, idx)
    flat_vals: List[Any] = []
    for s in fwd_slots:
        for i, v in enumerate(fwd_ins[s]):
            if v is not None:
                flat_names.append((s, i))
                flat_vals.append(v)

    grad_out_slots = {s: ctx.op.output(s) for s in ctx.op.outputs}

    def fwd_flat(*vals):
        rebuilt = {s: list(fwd_ins[s]) for s in fwd_slots}
        for (s, i), v in zip(flat_names, vals):
            rebuilt[s][i] = v
        sub = LowerContext(ctx.op, ctx.attrs, ctx.executor_ctx)
        outs = opdef.lower(sub, rebuilt)
        # Order output cotangent structure canonically by slot name
        flat_outs = []
        out_index = []
        for slot in sorted(outs):
            for j, ov in enumerate(outs[slot]):
                if ov is None:  # an optional output this route leaves unwritten
                    continue
                flat_outs.append(ov)
                out_index.append((slot, j))
        return tuple(flat_outs), out_index

    # Probe to learn output structure (cheap: tracing only)
    _, out_index = fwd_flat(*flat_vals)

    def fwd_only(*vals):
        return fwd_flat(*vals)[0]

    primal_outs, vjp_fn = jax.vjp(fwd_only, *flat_vals)

    # Assemble cotangents for each forward output from incoming grad slots;
    # missing grads (fetch not reached) become zeros.
    import jax.numpy as jnp

    cotangents = []
    for (slot, j), primal in zip(out_index, primal_outs):
        gslot = _grad_slot(slot)
        gvals = ins.get(gslot) or []
        g = gvals[j] if j < len(gvals) else None
        if g is None:
            g = jnp.zeros_like(primal)
        g = jnp.asarray(g, primal.dtype)
        if g.shape != primal.shape:
            g = g.reshape(primal.shape)
        cotangents.append(g)

    in_grads = vjp_fn(tuple(cotangents))

    out: Dict[str, List[Any]] = {}
    grads_by_name = {}
    for (s, i), g in zip(flat_names, in_grads):
        grads_by_name[(s, i)] = g
    for s in fwd_slots:
        gs = []
        for i in range(len(fwd_ins[s])):
            gs.append(grads_by_name.get((s, i)))
        out[_grad_slot(s)] = gs
    return out


def _note_grad_route(route: str) -> None:
    """Count one grad op lowered by `route` (`grad_direct`: a registered
    grad op fed from its forward's residuals; `grad_generic`:
    lower_generic_grad) among the compile totals, so that a miss call's
    `executor.compile` flight event says which way its grad ops went."""
    from ..monitor import flight

    flight.note_compile_count(route)


def residual_grad(fwd_type: str):
    """Decorator registering `<fwd_type>_grad` for a forward op that names
    `residuals`: `direct(ctx, ins) -> {slot@GRAD: [values]}` computes the
    gradients from the forward's inputs, its residual outputs and the
    output cotangents, or returns None where it cannot (a shape its
    kernel plan rejects).  The route follows what the lowering observes:
    direct when every residual and cotangent is bound, else the generic
    vjp of the forward lowering, as for a program whose op never declared
    the slots (built before the hand-off, or by a graph pass) or whose
    forward took a route that writes no residual."""

    def deco(direct):
        fwd = get(fwd_type)

        def lower(ctx, ins):
            # a cotangent that never arrived is the generic route's to
            # fill with zeros
            needs = fwd.residuals + tuple(
                s for s in ins if s.endswith(GRAD_SUFFIX))
            if all((ins.get(slot) or [None])[0] is not None
                   for slot in needs):
                out = direct(ctx, ins)
                if out is not None:
                    _note_grad_route("grad_direct")
                    return out
            return lower_generic_grad(
                fwd_type, ctx,
                {s: v for s, v in ins.items() if s not in fwd.residuals})

        register(fwd_type + "_grad", no_grad=True,
                 derives_rng=fwd.derives_rng, doc=direct.__doc__)(lower)
        return direct

    return deco


def get_grad_lowering(grad_type: str) -> Optional[Callable]:
    """Resolve a lowering for a grad op: registered explicitly, or generic."""
    opdef = lookup(grad_type)
    if opdef is not None:
        return opdef.lower
    if grad_type.endswith("_grad"):
        fwd_type = grad_type[: -len("_grad")]
        if lookup(fwd_type) is not None:
            return functools.partial(lower_generic_grad, fwd_type)
    return None
