"""Fused decode megastep (PERF round 15): one decoder layer per launch.

Acceptance criteria covered here:
  * the megastep kernel passes interpret-mode parity against the exact
    composed-path arithmetic (fp32/bf16, causal lengths mid-block, both
    fused-FFN and split-FFN plan modes);
  * off-contract shapes fall back BIT-identically to the XLA
    composition (the plan gate's reject contract);
  * greedy decode through the fused program pair is TOKEN-IDENTICAL to
    the flag-off composed pair across >= 64 tokens with a FLAT executor
    compile cache, at batch 1 and 64;
  * flag-off graphs are op-for-op free of the fused op and keep the
    legacy feed list; parameter names interop across the flag
    (checkpoint compatibility);
  * kernel_lint's megastep matrix pins the perf-critical plans and its
    red gate NAMES fabricated bad plans;
  * the fused op is key-free (greedy stays bit-deterministic), the
    programs verify clean, and the fusion-corrected launch count drops
    >= 5x on the 6-layer smoke model.
"""

import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import executor as ex
from paddle_tpu.flags import FLAGS
from paddle_tpu.generation import GenerationSession
from paddle_tpu.models import transformer as T

TINY = dict(src_vocab_size=16, trg_vocab_size=16, max_length=70,
            n_layer=2, n_head=2, d_key=8, d_value=8, d_model=16,
            d_inner_hid=32)


def _src(rng, b, seq, vocab=16):
    return rng.randint(2, vocab, (b, seq, 1)).astype(np.int64)


def _kernel_args(rng, dtype, dm, h, dh, di, max_t, cross_t, b):
    """Random weights/caches in fused_decode_step positional order (the
    _FUSED_STEP_SLOTS contract minus the int args)."""
    import jax.numpy as jnp

    hd = h * dh

    def f(*s):
        return jnp.asarray(rng.randn(*s).astype(np.float32) * 0.1, dtype)

    args = [f(b, 1, dm), f(dm, 3 * hd), f(hd, dm),  # x, wqkv, wout
            f(dm) + 1, f(dm),                       # ln1
            f(dm, hd), f(hd, dm),                   # wcq, wcout
            f(dm) + 1, f(dm),                       # ln2
            f(dm, di), f(di), f(di, dm), f(dm),     # ffn w/b
            f(dm) + 1, f(dm)]                       # ln3
    caches = [f(1, b, max_t, h, dh), f(1, b, max_t, h, dh),
              f(1, b, cross_t, h, dh), f(1, b, cross_t, h, dh)]
    return args, caches


def _run_both(dtype, dm, h, dh, di, max_t, cross_t, lens, clens, pos,
              act, seed=0):
    import jax.numpy as jnp

    from paddle_tpu.kernels import decode_step as kds

    rng = np.random.RandomState(seed)
    b = len(lens)
    args, caches = _kernel_args(rng, dtype, dm, h, dh, di, max_t,
                                cross_t, b)
    ints = [jnp.asarray(a, jnp.int32) for a in (pos, lens, clens)]
    act = jnp.asarray(act, jnp.int32)
    kw = dict(layer=0, n_head=h, scale=dh ** -0.5)
    ref = kds.reference_decode_step(*args, *caches, *ints, act, **kw)
    fused = kds.fused_decode_step(*args, *caches, *ints, act,
                                  interpret=True, **kw)
    return ref, fused


# ---------------------------------------------------------------------------
# kernel: interpret-mode parity + plan gate
# ---------------------------------------------------------------------------


class TestMegastepKernel:
    @pytest.mark.parametrize(
        "dtype,dm,h,dh,di,label",
        [("float32", 128, 8, 64, 256, "fused-ffn"),
         ("float32", 512, 8, 64, 2048, "split-ffn"),
         ("bfloat16", 128, 16, 64, 256, "bf16-h16")])
    def test_interpret_parity_ragged_lengths(self, dtype, dm, h, dh, di,
                                             label):
        """Kernel vs the exact composed arithmetic, causal lengths mid-
        block (partial DMA blocks on both walks) and a mixed active
        mask."""
        from paddle_tpu.kernels import decode_step as kds

        plan = kds._megastep_plan(dm, h, dh, di, 128, 128, dtype)
        assert plan.ok, plan
        assert plan.fuse_ffn == (label != "split-ffn"), plan
        ref, fused = _run_both(
            dtype, dm, h, dh, di, max_t=128, cross_t=128,
            lens=[1, 5, 37, 128], clens=[3, 128, 60, 1],
            pos=[0, 4, 36, 127], act=[1, 1, 0, 1])
        tol = 3e-2 if dtype == "bfloat16" else 2e-5
        for name, a, b in zip(("out", "ck", "cv"), ref, fused):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            err = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
            assert err < tol, (label, name, err)

    def test_inactive_lane_leaves_cache_untouched(self):
        """active=0 lanes must not write their cache row (the continuous
        batcher's late-join contract rides the in-kernel @pl.when)."""
        ref, fused = _run_both(
            "float32", 128, 8, 64, 256, max_t=128, cross_t=128,
            lens=[4, 9], clens=[7, 7], pos=[3, 8], act=[0, 1], seed=3)
        _, ck_ref, _ = ref
        _, ck_f, _ = fused
        np.testing.assert_allclose(np.asarray(ck_f)[0, 0],
                                   np.asarray(ck_ref)[0, 0], atol=1e-6)

    def test_off_contract_falls_back_bit_identical(self):
        """dh=48 rejects; the fallback IS reference_decode_step, so the
        outputs are bit-equal, not merely close."""
        from paddle_tpu.kernels import decode_step as kds

        assert not kds._megastep_plan(
            128, 8, 48, 256, 128, 128, "float32").ok
        ref, fused = _run_both(
            "float32", 128, 8, 48, 256, max_t=128, cross_t=128,
            lens=[2, 66], clens=[11, 128], pos=[1, 65], act=[1, 1],
            seed=5)
        for a, b in zip(ref, fused):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_plan_gate_contract(self):
        from paddle_tpu.analysis.kernel_lint import _pretend_tpu
        from paddle_tpu.kernels import decode_step as kds

        def plan(dm=256, h=8, dh=128, di=4096, max_t=128, cross_t=256,
                 dtype="float32"):
            with _pretend_tpu():
                return kds._megastep_plan(dm, h, dh, di, max_t, cross_t,
                                          dtype)

        base = plan()
        assert base.ok and not base.fuse_ffn      # FFN ~8 MB -> split
        small = plan(dm=128, di=256, cross_t=128)
        assert small.ok and small.fuse_ffn
        # compiled: the hand-DMA'd cache walk needs a 128-lane minor dim
        # (Mosaic refused d_head 64 on the chip) — transformer-base
        # takes the XLA composition; interpret mode keeps % 64
        assert not plan(dm=512, dh=64, di=2048).ok
        assert kds._megastep_plan(512, 8, 64, 2048, 128, 256, "float32",
                                  interpret=True).ok
        assert not plan(dh=48).ok                  # dh % 64
        assert not plan(dm=100).ok                 # dm % 128
        assert not plan(di=100).ok                 # di % 128
        assert not plan(h=8, dtype="bfloat16").ok  # h % 16 sublane
        assert not plan(max_t=100).ok              # max_t % block_t
        # off-TPU with interpret unset: the production path must fall
        # back (plan carries interpret=True)
        assert kds._megastep_plan(512, 8, 64, 2048, 128, 256,
                                  "float32").interpret


# ---------------------------------------------------------------------------
# program pair: fused vs composed token identity + compile-flat
# ---------------------------------------------------------------------------


class TestFusedDecodePrograms:
    @pytest.mark.parametrize("batch", [1, 64])
    def test_token_identity_fused_vs_unfused_compile_flat(self, batch):
        """THE acceptance criterion: >= 64 greedy tokens, fused vs
        flag-off composed path token-identical, compile cache flat for
        BOTH program pairs — at batch 1 and 64."""
        dims = dict(TINY, batch_size=batch, src_seq_len=6,
                    max_out_len=64, bos_id=0, eos_id=-1)  # no early eos
        rng = np.random.RandomState(7 + batch)
        src = _src(rng, batch, 6)
        scope = ex.Scope()

        assert FLAGS.fused_decode_step  # default-on contract
        fused = GenerationSession(
            T.build_generation_programs(kv_cache=True, **dims),
            scope=scope)
        assert fused.p.self_feed_token
        assert fused.p.decode_feeds == ["gen_active"]
        fused.init_params()
        toks_f, steps = fused.generate(src)
        assert steps == 64 and toks_f.shape == (batch, 64)
        n_compiled = fused.compile_count
        fused.generate(src)
        assert fused.compile_count == n_compiled

        try:
            FLAGS.set("fused_decode_step", False)
            composed = GenerationSession(
                T.build_generation_programs(kv_cache=True, **dims),
                scope=scope)
            assert not composed.p.self_feed_token
            assert composed.p.decode_feeds == ["gen_token", "gen_active"]
            toks_c, _ = composed.generate(src)
            n_compiled = composed.compile_count
            composed.generate(src)
            assert composed.compile_count == n_compiled
        finally:
            FLAGS.reset("fused_decode_step")
        np.testing.assert_array_equal(toks_f, toks_c)

    def test_eos_latch_matches_host_masking(self):
        """With a reachable eos, the in-graph finished latch must emit
        the same eos-padded stream as the host loop's masking on the
        composed path (sequences finish at different steps)."""
        dims = dict(TINY, batch_size=4, src_seq_len=6, max_out_len=16,
                    bos_id=0)
        rng = np.random.RandomState(11)
        src = _src(rng, 4, 6)
        scope = ex.Scope()
        probe = GenerationSession(
            T.build_generation_programs(kv_cache=True, eos_id=-1, **dims),
            scope=scope)
        probe.init_params()
        # eos = a token the randomly-initialized model actually emits
        eos = int(probe.generate(src, max_tokens=2)[0][0, -1])

        fused = GenerationSession(
            T.build_generation_programs(kv_cache=True, eos_id=eos,
                                        **dims), scope=scope)
        toks_f, steps_f = fused.generate(src)
        try:
            FLAGS.set("fused_decode_step", False)
            composed = GenerationSession(
                T.build_generation_programs(kv_cache=True, eos_id=eos,
                                            **dims), scope=scope)
            toks_c, steps_c = composed.generate(src)
        finally:
            FLAGS.reset("fused_decode_step")
        assert steps_f == steps_c
        np.testing.assert_array_equal(toks_f, toks_c)

    def test_flag_off_graph_identity_and_param_interop(self):
        """Flag-off graphs are op-for-op free of the fused op with the
        legacy feed list and NO self-feed state; parameter names are
        IDENTICAL across the flag (checkpoints interop)."""
        dims = dict(TINY, batch_size=2, src_seq_len=6, max_out_len=5)

        p_on = T.build_generation_programs(kv_cache=True, **dims)
        try:
            FLAGS.set("fused_decode_step", False)
            p_off = T.build_generation_programs(kv_cache=True, **dims)
            p_off2 = T.build_generation_programs(kv_cache=True, **dims)
        finally:
            FLAGS.reset("fused_decode_step")

        ops_on = [op.type for op in p_on.decode.global_block().ops]
        ops_off = [op.type for op in p_off.decode.global_block().ops]
        ops_off2 = [op.type for op in p_off2.decode.global_block().ops]
        assert ops_off == ops_off2          # flag-off build is stable
        assert "fused_decode_step" not in ops_off
        assert ops_on.count("fused_decode_step") == dims["n_layer"]
        assert len(ops_on) < len(ops_off)   # the fusion actually shrinks
        assert p_off.decode_feeds == ["gen_token", "gen_active"]
        off_vars = set(p_off.decode.global_block().vars)
        assert p_on.last_tok_name not in off_vars
        assert p_on.finished_name not in off_vars

        def param_names(p):
            return {v.name for v in
                    p.decode.global_block().all_parameters()}

        assert param_names(p_on) == param_names(p_off)

    def test_fused_op_key_free_and_verifier_clean(self):
        """The fused greedy program draws no RNG key (bit-deterministic,
        compile key-free) and passes the static verifier with the
        self-feed feed list; the sampled strategy keeps the host token
        feed AND its RNG threading."""
        from paddle_tpu.analysis import verify_program

        dims = dict(TINY, batch_size=2, src_seq_len=6, max_out_len=5)
        p = T.build_generation_programs(kv_cache=True, **dims)
        assert [op.type for op in p.decode.global_block().ops].count(
            "fused_decode_step") == dims["n_layer"]
        assert not ex.program_uses_random(p.decode.global_block())
        findings = verify_program(p.decode, feed_names=p.decode_feeds,
                                  fetch_names=p.decode_fetch,
                                  check_dead=True)
        assert not findings, [str(f) for f in findings]

        ps = T.build_generation_programs(kv_cache=True, strategy="sample",
                                         top_k=4, **dims)
        assert not ps.self_feed_token
        assert ps.decode_feeds == ["gen_token", "gen_active"]
        assert ex.program_uses_random(ps.decode.global_block())

    def test_continuous_batcher_rides_self_feed(self):
        """Late joins through the serving tier: the self-feed decode
        program must coalesce concurrent requests without retracing
        (sampler feeds only gen_active)."""
        from paddle_tpu.serving.generation import (ContinuousBatcher,
                                                   GenerationConfig,
                                                   GenerationServingModel)

        cfg = GenerationConfig(
            "m_selffeed", slots=4,
            src_vocab_size=32, trg_vocab_size=32, max_length=32,
            n_layer=1, n_head=2, d_key=8, d_value=8, d_model=16,
            d_inner_hid=32, src_seq_len=8, max_out_len=12,
            bos_id=0, eos_id=1)
        model = GenerationServingModel(cfg)
        assert model.session.p.self_feed_token
        model.init_params()
        model.warmup()
        n_compiled = model.compile_count
        batcher = ContinuousBatcher(model)
        batcher.start()
        try:
            results = [None] * 3

            def worker(i):
                results[i] = batcher.submit([2 + i, 5, 9], max_tokens=6,
                                            timeout=60.0)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for toks, meta in results:
                assert 1 <= len(toks) <= 6
                assert meta["finished"] in ("eos", "max_tokens")
            assert model.compile_count == n_compiled  # no retrace
        finally:
            batcher.stop()


# ---------------------------------------------------------------------------
# static analysis: lint matrix, red gate, cost model
# ---------------------------------------------------------------------------


class TestMegastepStaticAnalysis:
    def test_megastep_matrix_must_accepts(self):
        """The perf-critical megastep plans stay accepted with the
        expected fusion mode (regression pin on the plan gate)."""
        from paddle_tpu.analysis.kernel_lint import (_MEGASTEP_MATRIX,
                                                     lint_kernel_plans)

        findings, report = lint_kernel_plans()
        rows = {r["label"]: r for r in report["decode_step"]}
        for cfg in _MEGASTEP_MATRIX:
            expect = cfg.get("must_accept", True)
            assert rows[cfg["label"]]["accepted"] == expect, cfg
            if "expect_fuse_ffn" in cfg:
                assert rows[cfg["label"]]["fuse_ffn"] == \
                    cfg["expect_fuse_ffn"], cfg
        assert not [f for f in findings
                    if getattr(f, "op_type", "") == "decode_step"]

    def test_megastep_lint_red_gate(self):
        """check_megastep_plan must NAME a silently-rejecting gate, a
        block-contract violation, a fusion-mode flip, and a VMEM-budget
        overrun on fabricated plans."""
        from paddle_tpu.analysis.kernel_lint import check_megastep_plan
        from paddle_tpu.kernels.decode_step import MegastepPlan

        cfg = dict(label="fab", dm=512, h=8, dh=64, di=2048, max_t=128,
                   cross_t=256, dtype="float32")
        ok = MegastepPlan(True, False, 128, 256, False)

        findings = []
        check_megastep_plan(cfg, ok._replace(ok=False), findings)
        assert any(f.check == "kernel-plan-reject" for f in findings)
        findings = []
        check_megastep_plan(cfg, ok._replace(block_t=96), findings)
        assert any(f.check == "kernel-grid-divisibility"
                   for f in findings)
        findings = []
        check_megastep_plan(dict(cfg, expect_fuse_ffn=False),
                            ok._replace(fuse_ffn=True), findings)
        assert any(f.check == "kernel-fusion-mode" for f in findings)
        # fused FFN weights of 2 x 512 x 8192 f32 = 32 MiB alone pass the
        # vmem_limit_bytes the launch requests
        findings = []
        check_megastep_plan(dict(cfg, di=8192), ok._replace(fuse_ffn=True),
                            findings)
        assert any(f.check == "kernel-vmem-budget" for f in findings)
        findings = []
        check_megastep_plan(dict(cfg, dh=48, must_accept=False), ok,
                            findings)
        assert any(f.check == "kernel-misaligned-block"
                   for f in findings)

    def test_launch_count_drops_5x_on_smoke_model(self):
        """The acceptance number: the fusion-corrected launch count of
        the 6-layer smoke decode program drops >= 5x under the flag
        (and lands at <= 12 charged launches per layer stack + head)."""
        from paddle_tpu.analysis.costmodel import cost_program

        dims = dict(src_vocab_size=64, trg_vocab_size=64, max_length=24,
                    n_layer=6, n_head=4, d_key=32, d_value=32,
                    d_model=128, d_inner_hid=256, batch_size=1,
                    src_seq_len=8, max_out_len=8)
        p_on = T.build_generation_programs(kv_cache=True, **dims)
        try:
            FLAGS.set("fused_decode_step", False)
            p_off = T.build_generation_programs(kv_cache=True, **dims)
        finally:
            FLAGS.reset("fused_decode_step")
        on = cost_program(p_on.decode, name="fused", batch_size=1)
        off = cost_program(p_off.decode, name="composed", batch_size=1)
        assert on.n_launches_fused * 5 <= off.n_launches_fused, \
            (on.n_launches_fused, off.n_launches_fused)
        # 6 fused layer launches + embedding/head/sample bookkeeping
        assert on.n_launches_fused <= 12, on.n_launches_fused
        # the corrected count never exceeds the upper bound
        assert on.n_launches_fused <= on.n_launches
        assert off.n_launches_fused <= off.n_launches
