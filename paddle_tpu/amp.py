"""Automatic mixed precision (bf16) for traced programs.

Capability parity with the reference's float16 support (reference:
paddle/fluid/platform/float16.h — a software half type that op kernels can
compute in), redesigned TPU-first:

  * TPU MXU peak throughput is bf16; fp32 matmuls run at a fraction of peak.
    Instead of per-kernel half-precision variants, we apply an **autocast
    policy at trace time**: matmul/conv-family ops compute in bf16,
    numerically sensitive ops (norms, softmax, losses, optimizer updates)
    compute in fp32.
  * Parameters remain fp32 **master weights** in HBM; the fp32->bf16 cast of
    each weight happens inside the compiled step and XLA fuses it into the
    convolution/matmul (one extra HBM read of the fp32 weight, no extra
    round-trip).
  * Gradients: a grad op's inputs are cast by its forward's policy, so a
    white-listed op's backward also computes in bf16, whether it re-traces
    the forward lowering under jax.vjp (the generic grad) or reads the
    forward's residuals (the kernel attention ops: their float32 Lse is
    the one slot left as it is, SLOT_WHITE_OPS).  Optimizer ops are
    black-listed, so gradients are cast back to fp32 before moment/param
    updates — fp32 accumulation, the standard mixed-precision recipe.
  * bf16 keeps fp32's exponent range, so no loss scaling is required
    (unlike fp16).

Usage::

    prog = pt.default_main_program()
    pt.amp.enable(prog)          # all subsequent Executor.run calls use bf16
    # or: with pt.amp.bf16_guard(prog): exe.run(...)
"""

from __future__ import annotations

import contextlib

# Ops whose FLOPs dominate and map onto the MXU: compute in bf16.
WHITE_OPS = frozenset({
    "conv2d",
    "depthwise_conv2d",
    "conv2d_transpose",
    "conv3d",
    "mul",
    "matmul",
    "ring_attention",
})

# Numerically sensitive ops: compute in fp32 (reductions over many elements,
# exponentials, running statistics, parameter updates).
BLACK_OPS = frozenset({
    # batch_norm/layer_norm are NOT black-listed: their lowerings accumulate
    # statistics in fp32 internally while producing outputs in the input
    # dtype, so bf16 conv/residual chains stay bf16 without precision loss
    # in the stats.
    "group_norm",
    "data_norm",
    "lrn",
    "softmax",
    "log_softmax",
    # softmax_with_cross_entropy is NOT black-listed: its lowering does the
    # exp-sum/loss in fp32 internally while the [N, V] logits stay bf16 —
    # black-listing it would materialize a ~2 GB fp32 logits copy per
    # transformer-base step (see ops/nn_ops.py lower_softmax_with_ce).
    "cross_entropy",
    "sigmoid_cross_entropy_with_logits",
    "bpr_loss",
    "huber_loss",
    "log_loss",
    "hinge_loss",
    "margin_rank_loss",
    "mean",
    "sum",
    "reduce_sum",
    "reduce_mean",
    "reduce_prod",
    "exp",
    "log",
    "cumsum",
    "accuracy",
    "auc",
    "fused_layer_norm_gelu",
    # the router's scores decide a top-k: float32, as published
    "moe_router",
    # optimizer ops: fp32 master-weight updates
    "sgd",
    "momentum",
    "lars_momentum",
    "adam",
    "adamax",
    "adagrad",
    "decayed_adagrad",
    "adadelta",
    "rmsprop",
    "ftrl",
    "proximal_gd",
    "proximal_adagrad",
})


def enable(program=None) -> None:
    """Mark `program` (default: the default main program) for bf16 autocast."""
    from .core import framework as fw

    program = program or fw.default_main_program()
    program._amp_bf16 = True
    program._mod_count += 1  # invalidate _mod_count-keyed compile caches


def disable(program=None) -> None:
    from .core import framework as fw

    program = program or fw.default_main_program()
    program._amp_bf16 = False
    program._mod_count += 1


def is_enabled(program) -> bool:
    return bool(getattr(program, "_amp_bf16", False))


@contextlib.contextmanager
def bf16_guard(program=None):
    from .core import framework as fw

    program = program or fw.default_main_program()
    prev = getattr(program, "_amp_bf16", False)
    program._amp_bf16 = True
    try:
        yield
    finally:
        program._amp_bf16 = prev


def _cast_value(v, dtype):
    import jax.numpy as jnp

    if v is None or not hasattr(v, "dtype"):
        return v
    if v.dtype == jnp.float32 and dtype == jnp.bfloat16:
        return v.astype(jnp.bfloat16)
    if v.dtype == jnp.bfloat16 and dtype == jnp.float32:
        return v.astype(jnp.float32)
    return v


# Slot-wise policies: ops that mix MXU compute with fp32 master state in
# ONE op.  conv2d_bn's conv operands and residual stream run bf16 exactly
# as the unfused conv2d + elementwise_add would, but Scale/Bias/Mean/
# Variance are the BN's fp32 running-stat state — a plain WHITE listing
# would downcast the stateful MeanOut/VarianceOut writebacks, BLACK would
# forfeit the MXU (the in-op statistics already accumulate in fp32, same
# as the batch_norm lowering).
#
# The two kernel attention ops are WHITE in every slot but one: Lse, the
# float32 logsumexp the forward kernel writes and the registered grad op
# reads back (ops/fused_ops.py), has to reach the backward kernels as the
# very array the forward wrote.
SLOT_WHITE_OPS = {
    "conv2d_bn": frozenset({"Input", "Filter", "Residual"}),
    "fused_attention": frozenset(
        {"Q", "K", "V", "Bias", "Out", "Out@GRAD"}),
    "fused_qkv_attention": frozenset(
        {"X", "WQkv", "WOut", "Bias", "Q", "K", "V", "Ctx", "Out@GRAD"}),
    # the expert matmuls run bf16; the pairs' weights stay float32
    "moe_experts": frozenset({"X", "WGateUp", "WDown", "H", "Out@GRAD"}),
}

# Multi-input elementwise ops follow their activations: if any float input is
# already bf16, cast the rest down instead of promoting the bf16 side to fp32
# (an fp32 bias would otherwise drag every post-matmul activation back to
# fp32, forfeiting the bf16 memory/fusion win on matmul-heavy chains).
GRAY_FOLLOW_OPS = frozenset({
    "dropout_add",  # dropout + residual add: follow the activation dtype
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "elementwise_max",
    "elementwise_min",
})


def apply_cast_policy(op_type: str, ins: dict) -> dict:
    """Cast the float inputs of one op per the autocast policy.  Grad ops
    (`X_grad`) inherit X's policy so forward and backward agree."""
    import jax.numpy as jnp

    base = op_type[:-5] if op_type.endswith("_grad") else op_type
    slots = SLOT_WHITE_OPS.get(base)
    if slots is not None:
        return {
            slot: ([_cast_value(v, jnp.bfloat16) for v in vals]
                   if slot in slots else list(vals))
            for slot, vals in ins.items()
        }
    if base in WHITE_OPS:
        target = jnp.bfloat16
    elif base in BLACK_OPS:
        target = jnp.float32
    elif base in GRAY_FOLLOW_OPS:
        if any(
            getattr(v, "dtype", None) == jnp.bfloat16
            for vals in ins.values()
            for v in vals
        ):
            target = jnp.bfloat16
        else:
            return ins
    else:
        return ins
    return {
        slot: [_cast_value(v, target) for v in vals]
        for slot, vals in ins.items()
    }


class LossScaler:
    """Dynamic loss scaling (reference: fluid.contrib.mixed_precision
    DynamicLossScale).  bf16 autocast does not need it — bf16 keeps
    fp32's exponent range — but fp16-style recipes and user-driven
    scaling do, and the numerics tier needs a place to route overflow
    verdicts: monitor/numerics.publish_step_stats calls `update(found)`
    once per step with whether any low-precision grad held Inf/NaN.

    Host-side state only: the user multiplies the loss by `scale` (and
    un-scales grads) in their own graph or feed; this object just runs
    the grow/backoff policy and exports the `amp.loss_scale` gauge.
    Skipped steps (overflow -> caller should drop the update) are
    counted in `amp.overflow_steps`.
    """

    def __init__(self, init_scale: float = 2.0 ** 15,
                 growth_factor: float = 2.0, backoff_factor: float = 0.5,
                 growth_interval: int = 2000,
                 min_scale: float = 1.0, max_scale: float = 2.0 ** 24):
        self.scale = float(init_scale)
        self.growth_factor = float(growth_factor)
        self.backoff_factor = float(backoff_factor)
        self.growth_interval = int(growth_interval)
        self.min_scale = float(min_scale)
        self.max_scale = float(max_scale)
        self.good_steps = 0
        self.overflow_steps = 0

    def update(self, found_overflow: bool) -> float:
        """Advance the policy one step; returns the new scale."""
        if found_overflow:
            self.overflow_steps += 1
            self.good_steps = 0
            self.scale = max(self.scale * self.backoff_factor,
                             self.min_scale)
        else:
            self.good_steps += 1
            if self.good_steps >= self.growth_interval:
                self.good_steps = 0
                self.scale = min(self.scale * self.growth_factor,
                                 self.max_scale)
        self._export()
        return self.scale

    def _export(self):
        from .monitor import registry as _registry

        if _registry.enabled():
            reg = _registry.default_registry()
            reg.gauge("amp.loss_scale").set(self.scale)
            reg.gauge("amp.overflow_steps").set(self.overflow_steps)


_loss_scaler = None


def set_loss_scaler(scaler) -> None:
    """Install (or clear, with None) the process-wide dynamic loss
    scaler consulted by the numerics tier's overflow publication."""
    global _loss_scaler
    _loss_scaler = scaler
    if scaler is not None:
        scaler._export()


def active_loss_scaler():
    return _loss_scaler
