"""Static-analysis tier (paddle_tpu/analysis): red-gate + zero-false-positive
coverage.

Red gate: one seeded defect per analysis class — shape mismatch, use
before def, donated+fetched var, unthreaded RNG op, misaligned Pallas
block — and the verifier/linter must NAME each one.  Green gate: zero
findings across the bundled models and the built-in kernel plan matrix.
Wiring: the Executor pre-compile hook verifies once per signature, raises
on errors, and is skipped entirely (zero calls) with
FLAGS_verify_program off.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.analysis import (
    Finding,
    ProgramVerifyError,
    lint_kernel_plans,
    verify_or_raise,
    verify_program,
)
from paddle_tpu.analysis import kernel_lint
from paddle_tpu.core import registry
from paddle_tpu.flags import FLAGS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _checks(findings):
    return {f.check for f in findings}


def _small_train_net():
    x = layers.data(name="x", shape=[4], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    pred = layers.fc(x, size=1)
    loss = layers.mean(layers.square(pred - y))
    return x, y, loss


# ---------------------------------------------------------------------------
# red gate: the five seeded defect classes
# ---------------------------------------------------------------------------


class TestRedGate:
    def test_shape_mismatch_named(self):
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            _small_train_net()
        # corrupt the IR: a mul output's declared shape no longer matches
        # what its contract infers (deserialized/hand-edited program class)
        blk = prog.global_block()
        mul_op = next(op for op in blk.ops if op.type == "mul")
        out_name = mul_op.output("Out")[0]
        v = blk.var(out_name)
        v.shape = (7, 7)
        findings = verify_program(prog, feed_names=["x", "y"])
        hits = [f for f in findings if f.check == "shape-mismatch"]
        assert hits, findings
        assert hits[0].op_type == "mul" and hits[0].var == out_name
        assert "(7, 7)" in hits[0].message

    def test_shape_contract_failure_named(self):
        # a mul whose K dims disagree: infer_shape itself still produces a
        # shape, but corrupting the INPUT var makes a concat contract blow
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            a = layers.data(name="a", shape=[2, 3], dtype="float32")
            b = layers.data(name="b", shape=[2, 3], dtype="float32")
            layers.concat([a, b], axis=1)
        blk = prog.global_block()
        blk.var("a").shape = (-1, 2, 999)  # rank-consistent, dim mismatch
        findings = verify_program(prog, feed_names=["a", "b"])
        assert any(f.check in ("shape-contract", "shape-mismatch")
                   for f in findings), findings

    def test_use_before_def_named(self):
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            out = layers.relu(x)
        blk = prog.global_block()
        # seed: an op reading a name nothing defines
        blk.append_op("relu", inputs={"X": ["ghost_var"]},
                      outputs={"Out": [out.name]})
        findings = verify_program(prog, feed_names=["x"])
        hits = [f for f in findings if f.check == "use-before-def"]
        assert hits and hits[0].var == "ghost_var", findings

    def test_donated_fetched_var_named(self):
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            _, _, loss = _small_train_net()
            pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        param = prog.all_parameters()[0].name
        findings = verify_program(prog, feed_names=["x", "y"],
                                  fetch_names=[param])
        hits = [f for f in findings if f.check == "donated-fetch"]
        assert hits and hits[0].var == param, findings
        # without the conflicting fetch the program is clean of it
        clean = verify_program(prog, feed_names=["x", "y"],
                               fetch_names=[loss.name])
        assert "donated-fetch" not in _checks(clean)

    def test_unthreaded_rng_op_named(self):
        # the PR-4 bug class: an op whose lowering draws PRNG bits but is
        # invisible to executor.op_threads_rng
        @registry.register("test_rogue_rng_op", derives_rng=True,
                           no_grad=True)
        def _lower(ctx, ins):  # pragma: no cover - never traced here
            return {"Out": [ins["X"][0]]}

        try:
            prog, startup = pt.Program(), pt.Program()
            with pt.program_guard(prog, startup):
                x = layers.data(name="x", shape=[4], dtype="float32")
                out = prog.global_block().create_var(shape=x.shape,
                                                     dtype="float32")
                prog.global_block().append_op(
                    "test_rogue_rng_op", inputs={"X": [x.name]},
                    outputs={"Out": [out.name]})
            findings = verify_program(prog, feed_names=["x"])
            hits = [f for f in findings if f.check == "rng-unthreaded"]
            assert hits and hits[0].op_type == "test_rogue_rng_op", findings
            assert "register_random_op" in hits[0].message
            # the downstream remediation: declaring the op to the
            # executor's threading clears the finding
            from paddle_tpu.core import executor as ex

            ex.register_random_op("test_rogue_rng_op")
            try:
                clean = verify_program(prog, feed_names=["x"])
                assert "rng-unthreaded" not in _checks(clean)
                assert ex.program_uses_random(prog.global_block())
            finally:
                ex._EXTRA_RANDOM_OPS.discard("test_rogue_rng_op")
        finally:
            registry._registry.pop("test_rogue_rng_op", None)

    def test_threaded_but_undeclared_rng_named(self):
        """The reverse direction of the RNG cross-check: an op the
        executor threads a key for must carry derives_rng metadata."""
        from paddle_tpu.core import executor as ex

        @registry.register("test_undeclared_rng_op", no_grad=True)
        def _lower(ctx, ins):  # pragma: no cover - never traced here
            return {"Out": [ins["X"][0]]}

        ex.register_random_op("test_undeclared_rng_op")
        try:
            prog, startup = pt.Program(), pt.Program()
            with pt.program_guard(prog, startup):
                x = layers.data(name="x", shape=[4], dtype="float32")
                out = prog.global_block().create_var(shape=x.shape,
                                                     dtype="float32")
                prog.global_block().append_op(
                    "test_undeclared_rng_op", inputs={"X": [x.name]},
                    outputs={"Out": [out.name]})
            findings = verify_program(prog, feed_names=["x"])
            hits = [f for f in findings if f.check == "rng-undeclared"]
            assert hits and hits[0].op_type == "test_undeclared_rng_op", \
                findings
        finally:
            ex._EXTRA_RANDOM_OPS.discard("test_undeclared_rng_op")
            registry._registry.pop("test_undeclared_rng_op", None)

    def test_misaligned_pallas_block_named(self):
        # the kernel linter must reject a fabricated compiled-mode plan
        # whose blocks break the 128-lane Mosaic alignment
        cfg = dict(label="seeded-misaligned", b=2, h=4, t=192, d=64,
                   dtype="float32", fmt="bhtd")
        findings = []
        kernel_lint.check_attention_plan(cfg, True, 96, 96, False,
                                         findings)
        assert any(f.check == "kernel-misaligned-block" for f in findings), \
            findings
        assert any("128-lane" in f.message for f in findings)

    def test_kernel_vmem_budget_named(self):
        # a bthd plan whose dkv walk holds q and dO whole past the default
        # scope: two [8192, 128] f32 blocks, double-buffered, are 16 MiB
        # alone (the whole-head tile's 256 KB bound went with that tile)
        cfg = dict(label="seeded-vmem", b=1, h=16, t=8192, d=128,
                   dtype="float32", fmt="bthd")
        findings = []
        kernel_lint.check_attention_plan(cfg, True, 128, 128, False,
                                         findings)
        assert any(f.check == "kernel-vmem-budget" for f in findings), \
            findings

    def test_gate_accepting_refused_geometry_named(self):
        # a gate that re-accepts a geometry the compiler refused on the
        # chip would crash at compile time, not fall back
        cfg = next(c for c in kernel_lint._MEGASTEP_MATRIX
                   if c["label"] == "megastep-base")
        from paddle_tpu.kernels.decode_step import MegastepPlan

        findings = []
        kernel_lint.check_megastep_plan(
            cfg, MegastepPlan(True, False, 128, 256, False), findings)
        assert [f.check for f in findings] == [
            "kernel-plan-accepts-refused"], findings

    def test_vmem_model_counts_lane_padding_and_double_buffers(self):
        # a [t, 8, 64] f32 tile costs what [t, 8, 128] does; bf16 pads
        # its second-minor dim to 16 sublanes
        assert kernel_lint._tile_bytes((256, 8, 64), "float32") \
            == 256 * 8 * 128 * 4
        assert kernel_lint._tile_bytes((256, 8, 64), "bfloat16") \
            == 256 * 16 * 128 * 2
        blk = ((128, 512), "float32")
        assert kernel_lint._vmem_use(blocked=[blk], held=[blk]) \
            == 3 * 128 * 512 * 4
        # a decode walk at a block the requested limit cannot hold: the
        # four double-buffered 8 MiB tiles alone pass 32 MiB
        cfg = dict(label="seeded-walk", b=1, h=8, dh=64, max_t=2048,
                   dtype="float32")
        findings = []
        kernel_lint.check_decode_plan(cfg, True, 2048, False, findings)
        assert any(f.check == "kernel-vmem-budget"
                   and "vmem_limit_bytes" in f.message
                   for f in findings), findings

    @pytest.mark.parametrize("chunk", [64, 128])
    def test_kda_vmem_counts_the_kept_factors_in_both_kernels(self, chunk):
        # the forward writes and the backward reads one [chunk, chunk]
        # float32 block of T and Aqk a grid step, double-buffered both
        # ways (at 64 its lanes pad to 128; heads of 256 keep every other
        # block another shape); K's pair fits with it
        cfg = next(c for c in kernel_lint._KDA_MATRIX
                   if c["label"] == f"kimi-kda-c{chunk}-bf16")
        factors = ((chunk, chunk), "float32")
        for name, (blocked, held) in kernel_lint.kda_blocks(
                dict(cfg, dk=256)).items():
            assert blocked.count(factors) == 1, name
            rest = [b for b in blocked if b != factors]
            assert kernel_lint._vmem_use(blocked=blocked, held=held) \
                - kernel_lint._vmem_use(blocked=rest, held=held) \
                == 2 * chunk * 128 * 4, name
        findings = []
        used = kernel_lint.check_kda_plan(cfg, True, False, findings)
        assert findings == [] and set(used) == {"kda_fwd", "kda_bwd"}
        assert max(used.values()) <= kernel_lint._SCOPED_VMEM_DEFAULT

    def test_kernel_alias_mismatch_named(self):
        cfg = dict(label="seeded-alias",
                   tables=[((100, 8), "float32"), ((100, 8), "bfloat16")],
                   batch=32, tiers=1)
        findings = []
        kernel_lint.check_embedding_group(cfg, 32, findings)
        assert any(f.check == "kernel-alias-mismatch" for f in findings), \
            findings

    def test_unregistered_op_named(self):
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
        prog.global_block().append_op("no_such_op_type",
                                      inputs={"X": [x.name]},
                                      outputs={"Out": ["o"]})
        findings = verify_program(prog, feed_names=["x"])
        assert "unregistered-op" in _checks(findings), findings

    def test_fetch_unreachable_named(self):
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            layers.relu(x)
        findings = verify_program(prog, feed_names=["x"],
                                  fetch_names=["never_made"])
        hits = [f for f in findings if f.check == "fetch-unreachable"]
        assert hits and hits[0].var == "never_made"


# ---------------------------------------------------------------------------
# green gate: zero findings on the bundled models + kernel matrix
# ---------------------------------------------------------------------------


class TestNoFalsePositives:
    def _verify_clean(self, prog, feeds, fetch, startup=None):
        findings = verify_program(prog, feed_names=feeds,
                                  fetch_names=fetch, check_dead=True)
        assert findings == [], [str(f) for f in findings]
        if startup is not None:
            sfind = verify_program(startup, check_dead=True)
            assert sfind == [], [str(f) for f in sfind]

    def test_mnist_clean(self):
        from paddle_tpu.models import mnist as M

        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            _, _, avg_cost, acc, _ = M.build_train_net()
            pt.optimizer.SGD(learning_rate=0.01).minimize(avg_cost)
        self._verify_clean(prog, ["pixel", "label"],
                           [avg_cost.name, acc.name], startup)

    def test_deepfm_clean(self):
        from paddle_tpu.models import deepfm as D

        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            avg_cost, auc_var, _, feeds = D.build_train_net()
        self._verify_clean(prog, feeds, [avg_cost.name, auc_var.name],
                           startup)

    def test_seq2seq_clean(self):
        from paddle_tpu.models import seq2seq as S

        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            avg_cost = S.build_train_net()
            pt.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
        self._verify_clean(prog, ["src_word", "trg_word", "trg_next"],
                           [avg_cost.name], startup)

    def test_weighted_loss_has_no_dead_grad_branch(self):
        """The transformer/BERT pattern that used to leave dead grad ops:
        a stop-gradient weights feed reshaped once and consumed twice
        (numerator mul + denominator reduce_sum).  append_backward must
        prune the branch (backward.py no-grad-branch pruning)."""
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            w = layers.data(name="w", shape=[1], dtype="float32")
            cost = layers.square(layers.fc(x, size=1))
            w2 = layers.reshape(w, [-1, 1])
            weighted = layers.elementwise_mul(cost, w2)
            avg = layers.elementwise_div(
                layers.reduce_sum(weighted), layers.reduce_sum(w2))
            pt.optimizer.SGD(learning_rate=0.1).minimize(avg)
        w2_grad = pt.core.framework.grad_var_name(w2.name)
        writers = [op.type for op in prog.global_block().ops
                   if w2_grad in op.output_arg_names()]
        assert writers == [], writers
        self._verify_clean(prog, ["x", "w"], [avg.name], startup)

    @pytest.mark.slow
    def test_transformer_and_bert_clean(self):
        from paddle_tpu.models import bert as B
        from paddle_tpu.models import transformer as T

        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            avg_cost, _, feeds = T.transformer(
                src_vocab_size=512, trg_vocab_size=512, max_length=64,
                n_layer=2, n_head=4, d_key=32, d_value=32, d_model=128,
                d_inner_hid=256, dropout_rate=0.1, src_seq_len=64,
                trg_seq_len=64, use_flash=True)
            pt.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
        self._verify_clean(prog, list(feeds), [avg_cost.name], startup)

        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            avg_loss, _ = B.build_pretrain_net(
                vocab_size=512, seq_len=64, n_layer=2, n_head=4,
                d_model=128, d_ff=256, dropout_rate=0.1, use_flash=True)
        self._verify_clean(
            prog,
            ["src_ids", "pos_ids", "sent_ids", "input_mask",
             "mask_labels", "mask_weights"],
            [avg_loss.name], startup)

    def test_kernel_plan_matrix_clean(self):
        findings, report = lint_kernel_plans()
        assert findings == [], [str(f) for f in findings]
        # every Pallas plan family in kernels/ is covered
        assert set(report) == {
            "attention", "conv_bn", "dropout_epilogue",
            "embedding", "ring_attention", "decode_attention",
            "decode_step", "paged_decode_attention", "paged_decode_step",
            "short_conv", "kda",
        }
        for fam, rows in report.items():
            assert rows, fam
        # the convolution blocks' rows take the kernels in whole 16-row
        # tiles; rows that are none fall to the XLA composition
        conv = {r["label"]: r for r in report["short_conv"]}
        assert conv["lfm2-conv-bf16"]["accepted"]
        assert 4096 % conv["lfm2-conv-bf16"]["rows"] == 0
        assert not conv["conv-ragged-rows"]["accepted"]
        # the plain form over Kimi-Linear's [q | k | v] takes the kernels
        assert conv["kda-conv-silu-bf16"]["accepted"]
        assert conv["kda-conv-silu-bf16"]["rows"] == 64
        # KDA's 128-lane heads take the kernels at both chunk lengths,
        # heads of 64 the XLA composition
        kda = {r["label"]: r["accepted"] for r in report["kda"]}
        assert kda["kimi-kda-c64-bf16"] and kda["kimi-kda-c128-bf16"]
        assert not kda["kda-heads-of-64"]
        # paged matrix contract: the capacity pair accepts, the
        # misaligned-pool and oversized-table rows reject (block_t is
        # pool geometry — never snapped)
        paged = {r["label"]: r["accepted"]
                 for r in report["paged_decode_attention"]}
        assert paged["paged-base-b1"] and paged["paged-base-b64"]
        assert not paged["paged-bt12-reject"]
        assert not paged["paged-table-overflow-reject"]
        pstep = {r["label"]: r for r in report["paged_decode_step"]}
        assert pstep["paged-megastep-dh128-b64"]["accepted"]
        assert pstep["paged-megastep-dh128-fused-ffn"]["fuse_ffn"]
        assert not pstep["paged-megastep-bt12-reject"]["accepted"]
        assert not pstep[
            "paged-megastep-table-overflow-reject"]["accepted"]
        # the matrices agree with the chip: every geometry Mosaic refused
        # (d_head-64 megastep walks, sub-128-lane embedding rows) is
        # REJECTED by its gate and carries the compiler's words; the
        # 128-lane geometries those kernels compile at accept
        refused = [
            (fam, cfg) for fam, matrix in (
                ("decode_step", kernel_lint._MEGASTEP_MATRIX),
                ("paged_decode_step", kernel_lint._PAGED_MEGASTEP_MATRIX),
                ("embedding", kernel_lint._EMBEDDING_MATRIX))
            for cfg in matrix if cfg.get("mosaic_refusal")]
        assert {c["label"] for _, c in refused} == {
            "megastep-base", "megastep-fused-ffn", "paged-megastep-base",
            "paged-megastep-fused-ffn", "deepfm-emb", "deepfm-w1",
            "deepfm-adam-apply"}
        for fam, cfg in refused:
            row = {r["label"]: r for r in report[fam]}[cfg["label"]]
            assert not cfg["must_accept"] and not row["accepted"], cfg
            assert "aligned to tiling (128)" in cfg["mosaic_refusal"]
        step = {r["label"]: r for r in report["decode_step"]}
        assert step["megastep-dh128-split"]["accepted"]
        assert not step["megastep-dh128-split"]["fuse_ffn"]
        assert step["megastep-dh128-fused-ffn"]["fuse_ffn"]
        emb = {r["label"]: r for r in report["embedding"]}
        assert emb["wide-emb-d128"]["accepted"]
        assert emb["wide-emb-d128-b4096"]["block_rows"] % 8 == 0
        # transformer-base decode keeps a compiled kernel: flash-decode
        # takes d_head 64 (pipelined blocks, not hand DMA)
        dec = {r["label"]: r["accepted"]
               for r in report["decode_attention"]}
        assert dec["decode-base-b1"] and dec["decode-base-b64"]
        # the perf-critical plans ACCEPT (no silent fallback regression)
        acc = {r["label"]: r.get("accepted") for r in report["attention"]}
        assert acc["transformer-base-f32"] and acc["bert-base-bf16"]
        assert acc["transformer-base-bthd"]
        # BERT-base's fused_qkv_attention sites at both cell lengths and
        # transformer-base's: bthd under amp
        assert acc["bert-base-bf16-bthd"] and acc["bert-seq512-bf16-bthd"]
        assert acc["transformer-base-bf16-bthd"]

    def test_attention_bthd_f32_cap_is_dtype_aware(self):
        """Regression for the linter's first real catch, a byte bound that
        must scale with the dtype.  The bthd plan's bound is now on the
        whole-sequence block of a grid step's head groups (the kv-tile cap
        went with the whole-head tile): f32 takes fewer groups a step than
        bf16 where the bound binds, and neither passes it."""
        from paddle_tpu.kernels import attention as att

        t, block = 1536, 128
        g32 = att._bthd_step_groups(16, 64, block, t, 4)
        g16 = att._bthd_step_groups(16, 64, block, t, 2)
        assert g32 * 128 * t * 4 <= att._FULL_BLOCK_BYTES
        assert g16 * 128 * t * 2 <= att._FULL_BLOCK_BYTES
        assert g16 > g32  # wider dtype -> fewer groups a step


# ---------------------------------------------------------------------------
# executor wiring: FLAGS_verify_program
# ---------------------------------------------------------------------------


class TestExecutorHook:
    def _count_verifies(self, monkeypatch):
        import paddle_tpu.analysis as an

        calls = []
        real = an.verify_or_raise

        def counting(*a, **k):
            calls.append(1)
            return real(*a, **k)

        monkeypatch.setattr(an, "verify_or_raise", counting)
        return calls

    def test_verify_runs_once_per_signature(self, monkeypatch):
        calls = self._count_verifies(monkeypatch)
        FLAGS.verify_program = True
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            _, _, loss = _small_train_net()
            pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        scope, exe = pt.Scope(), pt.Executor(pt.CPUPlace())
        exe.run(startup, scope=scope)
        n0 = len(calls)
        feed = {"x": np.zeros((4, 4), "float32"),
                "y": np.zeros((4, 1), "float32")}
        exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)
        assert len(calls) == n0 + 1
        # warm path: cache hit AND verify memo both skip
        exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)
        assert len(calls) == n0 + 1

    def test_error_finding_blocks_compile(self):
        FLAGS.verify_program = True
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            out = layers.relu(x)
        prog.global_block().append_op("relu", inputs={"X": ["ghost"]},
                                      outputs={"Out": [out.name]})
        exe = pt.Executor(pt.CPUPlace())
        with pytest.raises(ProgramVerifyError) as ei:
            exe.run(prog, feed={"x": np.zeros((2, 4), "float32")},
                    fetch_list=[out], scope=pt.Scope())
        assert "ghost" in str(ei.value)

    def test_flag_off_skips_entirely(self, monkeypatch):
        """The perf guard: with FLAGS_verify_program off the hook makes
        ZERO verifier calls — compile path and hot path both."""
        calls = self._count_verifies(monkeypatch)
        FLAGS.verify_program = False
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            _, _, loss = _small_train_net()
            pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        scope, exe = pt.Scope(), pt.Executor(pt.CPUPlace())
        exe.run(startup, scope=scope)
        feed = {"x": np.zeros((4, 4), "float32"),
                "y": np.zeros((4, 1), "float32")}
        for _ in range(3):
            exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)
        assert calls == []

    def test_concurrent_compiles_verify_safely(self):
        """Serving-style concurrency: N threads compile the same program
        at different feed shapes while the verifier (which temporarily
        mutates then restores Variable shapes) runs — the verify lock
        must prevent spurious mismatches and IR corruption."""
        import threading

        FLAGS.verify_program = True
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            out = layers.fc(layers.fc(x, size=8, act="relu"), size=2)
        scope, exe = pt.Scope(), pt.Executor(pt.CPUPlace())
        exe.run(startup, scope=scope)
        shapes_before = {
            n: v.shape for n, v in prog.global_block().vars.items()
        }
        errors = []

        def worker(bs):
            try:
                for _ in range(3):
                    exe.run(prog, feed={"x": np.zeros((bs, 4), "float32")},
                            fetch_list=[out], scope=scope)
            except Exception as e:  # pragma: no cover - the regression
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(bs,))
                   for bs in (1, 2, 3, 4, 5, 6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == [], errors
        shapes_after = {
            n: v.shape for n, v in prog.global_block().vars.items()
        }
        assert shapes_after == shapes_before  # no transient-shape leak

    def test_verify_cost_is_compile_time_only(self):
        """Benchmark note for the perf guard: one verify of a transformer
        block-scale program stays far below XLA-compile scale, and the
        hook pays it once per signature (memoized)."""
        import time

        from paddle_tpu.models import bert as B

        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            avg_loss, _ = B.build_pretrain_net(
                vocab_size=512, seq_len=64, n_layer=2, n_head=4,
                d_model=128, d_ff=256, dropout_rate=0.1, use_flash=True)
        t0 = time.perf_counter()
        findings = verify_program(prog, feed_names=[
            "src_ids", "pos_ids", "sent_ids", "input_mask",
            "mask_labels", "mask_weights"], fetch_names=[avg_loss.name])
        dt = time.perf_counter() - t0
        assert findings == []
        # generous bound: the walk is O(ops); XLA compiles of this program
        # are seconds-scale, the verify is centi-seconds-scale
        assert dt < 5.0, f"verify took {dt:.2f}s"

    def test_serving_warmup_disables_verify(self, tmp_path):
        """'off in hot serving paths after warmup': the SERVER drops the
        flag only once ALL models' ladders are warm (a per-model flip
        would leave later models' warmup compiles unverified)."""
        from paddle_tpu.serving.model import ModelConfig
        from paddle_tpu.serving.server import InferenceServer

        for name in ("m1", "m2"):
            prog, startup = pt.Program(), pt.Program()
            with pt.program_guard(prog, startup):
                x = layers.data(name="x", shape=[6], dtype="float32")
                out = layers.fc(x, size=2)
            scope, exe = pt.Scope(), pt.Executor(pt.CPUPlace())
            with pt.scope_guard(scope):
                exe.run(startup, scope=scope)
                pt.io.save_inference_model(
                    str(tmp_path / name), ["x"], [out], exe,
                    main_program=prog, scope=scope)
        FLAGS.verify_program = True
        srv = InferenceServer([
            ModelConfig("m1", str(tmp_path / "m1"), buckets=(1, 2)),
            ModelConfig("m2", str(tmp_path / "m2"), buckets=(1, 2)),
        ])
        # per-model warmup must NOT flip the gate mid-ladder...
        assert srv.model("m1").warmup() > 0
        assert FLAGS.verify_program is True
        # ...the server-level warmup (all models) does
        assert srv.warmup() > 0
        assert FLAGS.verify_program is False
        from paddle_tpu.serving import server as sv

        assert sv._VERIFY_DROPPED[0] is True
        # a SECOND server in the same process restores the gate for its
        # own planned compiles, then re-drops it (process-global policy)
        srv2 = InferenceServer([
            ModelConfig("m2b", str(tmp_path / "m2"), buckets=(1,))])
        assert srv2.warmup() > 0
        assert FLAGS.verify_program is False


# ---------------------------------------------------------------------------
# CLI + repo lint rules
# ---------------------------------------------------------------------------


class TestTools:
    def test_graph_lint_cli_clean_subset(self, tmp_path):
        out = tmp_path / "graph_lint.json"
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "graph_lint.py"),
             "--models", "mnist,serving", "--skip-kernels",
             "--out", str(out)],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode == 0, r.stdout + r.stderr
        import json

        rep = json.loads(out.read_text())
        assert rep["total_findings"] == 0
        names = {p["name"] for p in rep["programs"]}
        assert "mnist" in names
        assert any(n.startswith("serving/aot-inference[b") for n in names)

    def test_lint_rules_clean_and_red(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import lint_rules
        finally:
            sys.path.pop(0)
        flags = lint_rules.declared_flags()
        assert "verify_program" in flags and "monitor" in flags
        bad = tmp_path / "bad.py"
        bad.write_text("from paddle_tpu.flags import FLAGS\n"
                       "v = FLAGS.undeclared_thing\n")
        v = lint_rules.check_file(str(bad), flags)
        assert v and "flags-declared" in v[0][2]
        kdir = tmp_path / "paddle_tpu" / "kernels"
        kdir.mkdir(parents=True)
        kbad = kdir / "k.py"
        kbad.write_text("import time\n\n"
                        "def body(ref):\n    return time.time()\n")
        v = lint_rules.check_file(str(kbad), flags)
        assert v and "no-kernel-time" in v[0][2]
        # the repo itself is clean
        viol = []
        for f in lint_rules.iter_py_files(["paddle_tpu", "tools",
                                           "bench.py"]):
            viol.extend(lint_rules.check_file(f, flags))
        assert viol == [], viol

    def test_finding_repr_roundtrip(self):
        f = Finding("dead-op", "warning", "msg", block_idx=0, op_index=3,
                    op_type="relu", var="v")
        d = f.to_dict()
        assert d["check"] == "dead-op" and d["op_type"] == "relu"
        assert "dead-op" in str(f) and "warning" in str(f)

    def test_verify_or_raise_passes_warnings(self):
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            _, _, loss = _small_train_net()
            pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        param = prog.all_parameters()[0].name
        # donated-fetch is warning severity: reported, not raised
        fs = verify_or_raise(prog, feed_names=["x", "y"],
                             fetch_names=[param])
        assert any(f.check == "donated-fetch" for f in fs)
