"""chip_smoke.py on the CPU: what the chip check promises without a chip.

The script itself must FAIL here (no TPU) and name why; its leg functions
run at a tiny width with interpret=True — which relaxes only what a CPU
cannot show (TPU residency, Mosaic custom calls) — and return their
report dicts.  The guards this bring-up added around the device ride
along: the compile-cache helper never overrides a directory placed from
outside, a TPUPlace executor refuses to run on a CPU-only process, and an
accelerator the peaks table does not know is an error.
"""

import os
import subprocess
import sys
import types

import pytest

import bench
import chip_smoke
import paddle_tpu as pt
from paddle_tpu.core import framework as fw

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_script_fails_without_a_tpu_and_says_so():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr
    # no result line: the last stdout line is the device line, not JSON
    assert '"ok"' not in out.stdout
    assert "platform=cpu" in out.stdout


def test_train_and_generate_legs_tiny():
    # six steps at batch 2 under dropout: whether the loss falls hangs on
    # the masks, and those on how many random ops this process built
    # before (the rng ids count on) — start the count where a fresh
    # process does, whatever files this worker ran first
    fw._rng_id_counter[0] = 0
    rep = chip_smoke.train_leg(cfg=bench.TRANSFORMER_TINY, batch=2, seq=64,
                               scan_steps=2, calls=2, interpret=True)
    assert rep["leg"] == "train" and rep["ok"], rep
    assert rep["loss_last"] < rep["loss_first"] and rep["params"] > 0
    rep = chip_smoke.generate_leg(cfg=bench.DECODE_TINY, slots=4,
                                  interpret=True)
    assert rep["leg"] == "generate" and rep["ok"], rep
    assert rep["compile_flat"] and rep["requests"] == 6
    assert rep["logits_err_strict"] <= chip_smoke.TOL_LOGITS_STRICT


def test_kernel_leg_rows_follow_the_lint_matrix():
    rep = chip_smoke.kernel_leg(
        interpret=True, families=("decode_attention", "decode_step"))
    assert rep["leg"] == "kernels" and rep["ok"], rep
    status = dict(rep["rows"])
    assert status["decode_attention:decode-base-b64"] == "compiled"
    assert status["decode_step:megastep-dh128-split"] == "compiled"
    # rows the gate rejects — by design or on the compiler's word — are
    # reported, never run
    assert status["decode_step:megastep-base"] == "xla_by_design"
    assert status["decode_attention:decode-dh48-reject"] == "xla_by_design"
    # a reference that disagrees is a mismatch (and fails the leg)
    import jax

    def off_by_one(cfg, interpret, rng):
        kernel, ref, args, tol = chip_smoke._decode_row(cfg, interpret, rng)
        return kernel, lambda *a: ref(*a) + 1.0, args, tol

    cfg = dict(label="x", b=1, h=8, dh=64, max_t=128, dtype="float32")
    assert chip_smoke._run_row(cfg, off_by_one, True, jax)[0] == "mismatch"


@pytest.mark.slow  # ~20 s; tests/test_sharding.py pins tp loss parity
def test_sharded_leg_tiny_on_the_virtual_mesh():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs the forced host-platform mesh")
    rep = chip_smoke.sharded_leg(cfg=bench.TRANSFORMER_TINY, batch=4,
                                 seq=64, steps=2, interpret=True)
    assert rep["leg"] == "sharded" and rep["ok"], rep
    assert rep["devices"] == 4 and sum(rep["collectives"].values()) > 0


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code.
    Unset: <checkout>/.jax_cache.  (jax.config.update is recorded, not
    applied — the suite itself never enables the persistent cache.)"""
    import jax

    from paddle_tpu import inference

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setattr(inference, "reset_compilation_cache_singleton",
                        lambda: None)

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert inference.enable_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in dict(calls)

    calls.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO_ROOT, ".jax_cache")
    assert inference.enable_compile_cache() == want
    assert dict(calls)["jax_compilation_cache_dir"] == want


def test_tpu_place_refuses_a_cpu_only_process():
    with pytest.raises(RuntimeError, match="not the TPU"):
        pt.Executor(pt.TPUPlace(0))
    pt.Executor(pt.CPUPlace())  # the CPU place still constructs


def test_unknown_accelerator_is_an_error_not_cpu_host(monkeypatch):
    import jax

    from paddle_tpu.analysis.costmodel import resolve_device_model

    monkeypatch.setattr(jax, "devices", lambda *a: [types.SimpleNamespace(
        platform="tpu", device_kind="TPU v99")])
    with pytest.raises(LookupError, match="TPU v99"):
        resolve_device_model()
    with pytest.raises(LookupError, match="TPU v99"):
        bench._peak_flops()
    monkeypatch.setattr(jax, "devices", lambda *a: [types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite")])
    assert resolve_device_model().name == "TPU v5 lite"
    assert bench._peak_flops() == 197e12
