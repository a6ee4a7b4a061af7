"""Static-analysis tier: catch miscompiles BEFORE trace time.

The reference framework's C++ runtime validates every ProgramDesc op
against its registered shape/dtype/attr contract before execution
(operator.cc RuntimeInferShape ENFORCE, framework.proto IR); this package
is the TPU-first equivalent for the Python IR:

  * verifier.py — walks a Program through the op registry: def-before-use
    / SSA across blocks, static shape+dtype contract re-inference,
    dead-var/dead-op detection, donation/fetch alias conflicts, and the
    RNG-determinism lint (key-deriving ops the executor would not thread
    the step key for — the PR-4 `dropout_add` bug class).
  * costmodel.py — static roofline / launch-cost model: per-op analytic
    FLOPs + HBM bytes from the declared IR shapes, compute/memory/launch
    classification against a declared device model, and the predicted
    step time `max(flops/peak, bytes/bw) + n_launches*overhead` that
    tools/perf_report.py renders (ROADMAP item 1's launch-bound
    fraction).
  * numerics.py — the FLAGS_check_numerics instrumentation pass: rewrite
    a Program to append fused per-tensor health reductions
    (ops/numerics_ops.py) packed into one [N, 4] stats fetch per step —
    per-op-output rows in `locate` mode (NaN/Inf origin localization),
    grad/weight/update rows in `summary` mode (training-dynamics
    gauges); `off` is zero-cost with a byte-identical fingerprint.
  * kernel_lint.py — statically audits every Pallas kernel plan in
    kernels/ (attention, conv_bn, dropout_epilogue, embedding,
    ring attention): VMEM budget vs the plan gate's estimate, (8,128)
    sublane/lane tile alignment, grid/block divisibility,
    input_output_aliases shape/dtype validity, and revisited-block
    accumulation dtypes — the checks that previously lived only in
    interpret-mode asserts until a chip run.

Wiring: Executor._maybe_verify (FLAGS_verify_program) gates every compile;
tools/graph_lint.py drives the full model matrix and emits the CI findings
artifact (ci_artifacts/graph_lint.json).
"""

from __future__ import annotations

from .verifier import (  # noqa: F401
    Finding,
    ProgramVerifyError,
    verify_or_raise,
    verify_program,
    verify_program_set,
)
from .kernel_lint import lint_kernel_plans  # noqa: F401
from .numerics import (  # noqa: F401
    instrument_program,
    is_instrumented,
    maybe_instrument,
)
from .costmodel import (  # noqa: F401
    DEVICE_MODELS,
    DeviceModel,
    OpCost,
    ProgramCost,
    cost_program,
    publish_cost,
    resolve_device_model,
)

__all__ = [
    "Finding",
    "ProgramVerifyError",
    "verify_program",
    "verify_or_raise",
    "verify_program_set",
    "lint_kernel_plans",
    "instrument_program",
    "is_instrumented",
    "maybe_instrument",
    "DEVICE_MODELS",
    "DeviceModel",
    "OpCost",
    "ProgramCost",
    "cost_program",
    "publish_cost",
    "resolve_device_model",
]
