import os

# Force a virtual 8-device CPU mesh for all tests (SURVEY.md §4 test plan:
# multi-host behavior simulated via xla_force_host_platform_device_count).
# PT_TEST_PLATFORM picks the backend: "cpu" (the default, deterministic)
# or "tpu" — through the chip tool, for the Test*TPU classes that drive
# the COMPILED Mosaic kernel paths:
#   chiprun -- env PT_TEST_PLATFORM=tpu python -m pytest \
#       tests/test_conv_bn.py::TestConvBnTPU \
#       tests/test_fused_qkv_attention.py::TestFusedQkvTPU \
#       tests/test_dropout_epilogue.py::TestHardwarePrngTPU
# (multi-device tests need the CPU mesh).
_platform = os.environ.get("PT_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform

# Executed-op recording for the op-contract gate (test_zz_op_gate.py):
# every op type the executor trace / imperative dispatcher lowers during
# the session lands in monitor.flight.lowered_op_types(), and the gate
# asserts registry.all_ops() ⊆ recorded ∪ CONTRACT_EXEMPT — enforcement
# by execution, not by grepping test files for op-name substrings.
os.environ.setdefault("FLAGS_record_lowered_ops", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest

import jax

# authoritative even if a pytest plugin imported jax before this file set
# the variable, as long as it runs before device init
jax.config.update("jax_platforms", _platform)

# Numeric tests compare against float64 numpy references; use full-precision
# matmuls (the framework default is device-native fast precision).
jax.config.update("jax_default_matmul_precision", "highest")

# NOTE: do NOT enable jax's persistent compilation cache here (the entry
# points do, through inference.enable_compile_cache; the suite never
# calls it in-process).  On jaxlib 0.4.37 a cache hit returned a stale
# interpret-mode Pallas kernel (callback pointers baked into the
# serialized CPU executable).  Re-checked on jaxlib 0.9.0: interpret mode
# lowers to plain HLO and cross-process hits are correct (fused-qkv
# fwd+grad, embedding gather), but every XLA:CPU hit logs a "Loading
# XLA:CPU AOT result ... could lead to execution errors such as SIGILL"
# error line, and a shared cache would couple tests through the disk —
# so the suite still compiles everything fresh.


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy tests excluded from the tier-1 quick gate "
        "(-m 'not slow'); tools/run_ci.sh runs the suite unfiltered")


def pytest_sessionfinish(session, exitstatus):
    """PT_DUMP_LOWERED_OPS=<path>: write the executed-op set observed this
    session (one op type per line) — the maintenance tool for the
    op-contract gate's CONTRACT_EXEMPT list."""
    path = os.environ.get("PT_DUMP_LOWERED_OPS")
    if path:
        from paddle_tpu.monitor import flight

        with open(path, "w") as f:
            f.write("\n".join(sorted(flight.lowered_op_types())) + "\n")


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Give every test fresh default programs + scope."""
    import paddle_tpu as pt
    from paddle_tpu.core import framework as fw
    from paddle_tpu.core import executor as ex

    old_main = fw.switch_main_program(fw.Program())
    old_startup = fw.switch_startup_program(fw.Program())
    old_scope = ex._global_scope
    ex._global_scope = ex.Scope()
    with fw.guard_unique_name():
        yield
    fw.switch_main_program(old_main)
    fw.switch_startup_program(old_startup)
    ex._global_scope = old_scope
    # serving warmup legitimately flips the verify gate off for its
    # process ("off in hot serving paths after warmup"); don't let that —
    # or its process-global did-we-drop-it bookkeeping — leak across tests
    import sys as _sys

    from paddle_tpu.flags import FLAGS

    FLAGS.reset("verify_program")
    # an InferenceServer turns telemetry on for its process; a later file
    # on the same worker must find the gate as the environment set it
    FLAGS.reset("monitor")
    _sv = _sys.modules.get("paddle_tpu.serving.server")
    if _sv is not None:
        _sv._VERIFY_DROPPED[0] = False


@pytest.fixture
def clean_ring():
    """The flight ring and the metrics registry, empty before and after;
    `FLAGS.monitor`, which a test may set, back to its default."""
    from paddle_tpu import monitor
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.monitor import flight

    assert not FLAGS.monitor
    flight.default_recorder().clear()
    monitor.default_registry().reset()
    yield flight.default_recorder()
    FLAGS.reset("monitor")
    flight.default_recorder().clear()
    monitor.default_registry().reset()
