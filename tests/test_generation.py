"""Autoregressive generation tier (PR 11): KV-cache contract, flash-decode
kernel, per-token program drivers, and continuous token-level batching.

Acceptance criteria covered here:
  * greedy decode through the KV-cache path is TOKEN-IDENTICAL to the
    flag-off full-prefix recompute path, and the executor compile cache
    stays FLAT after prefill + the first decode step across >= 64
    generated tokens at two batch sizes;
  * the flash-decode kernel passes interpret-mode parity (fwd-only
    contract) and falls back to XLA off-contract;
  * the beam-search While program is output-identical across
    FLAGS_kv_cache, and the per-token beam driver matches both;
  * a late-joining serving sequence neither retraces nor stalls
    in-flight decodes.
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import executor as ex
from paddle_tpu.core import framework as fw
from paddle_tpu.flags import FLAGS
from paddle_tpu.generation import GenerationSession, KVCache
from paddle_tpu.models import transformer as T

TINY = dict(src_vocab_size=16, trg_vocab_size=16, max_length=12,
            n_layer=2, n_head=2, d_key=8, d_value=8, d_model=16,
            d_inner_hid=32)


def _src(rng, b, seq, vocab=16):
    return rng.randint(2, vocab, (b, seq, 1)).astype(np.int64)


# ---------------------------------------------------------------------------
# flash-decode kernel
# ---------------------------------------------------------------------------


class TestFlashDecodeKernel:
    def test_interpret_parity_ragged_lengths(self):
        import jax.numpy as jnp

        from paddle_tpu.kernels import decode_attention as kda

        rng = np.random.RandomState(0)
        for b, h, dh, t, blk in [(2, 8, 64, 64, 16), (3, 8, 64, 128, 32),
                                 (1, 16, 64, 256, 256)]:
            q = jnp.asarray(rng.randn(b, h, dh).astype(np.float32))
            k = jnp.asarray(rng.randn(b, t, h, dh).astype(np.float32))
            v = jnp.asarray(rng.randn(b, t, h, dh).astype(np.float32))
            lens = jnp.asarray(
                rng.randint(1, t + 1, (b,)).astype(np.int32))
            ref = kda.reference_decode(q, k, v, lens, scale=dh**-0.5)
            out = kda.flash_decode(q, k, v, lens, scale=dh**-0.5,
                                   block_t=blk, interpret=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5)

    def test_length_masks_garbage_tail(self):
        """Rows past each sequence's length must not influence the
        output — overwrite the tail with huge values and compare."""
        import jax.numpy as jnp

        from paddle_tpu.kernels import decode_attention as kda

        rng = np.random.RandomState(1)
        b, h, dh, t = 2, 8, 64, 128
        q = jnp.asarray(rng.randn(b, h, dh).astype(np.float32))
        k = rng.randn(b, t, h, dh).astype(np.float32)
        v = rng.randn(b, t, h, dh).astype(np.float32)
        lens = np.asarray([5, 77], np.int32)
        k2, v2 = k.copy(), v.copy()
        for i, L in enumerate(lens):
            k2[i, L:] = 1e6
            v2[i, L:] = -1e6
        a = kda.flash_decode(q, jnp.asarray(k), jnp.asarray(v),
                             jnp.asarray(lens), interpret=True)
        bb = kda.flash_decode(q, jnp.asarray(k2), jnp.asarray(v2),
                              jnp.asarray(lens), interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=1e-6)

    def test_plan_gate_rejects_off_contract(self):
        import jax

        from paddle_tpu.analysis.kernel_lint import _pretend_tpu
        from paddle_tpu.kernels import decode_attention as kda

        def plan(b, h, dh, max_t):
            q = jax.ShapeDtypeStruct((b, h, dh), np.float32)
            k = jax.ShapeDtypeStruct((b, max_t, h, dh), np.float32)
            with _pretend_tpu():  # the compiled-mode contract
                return kda._decode_plan(q, k, 256, False)[0]

        assert plan(1, 8, 64, 128)          # canonical: accepted
        assert not plan(1, 8, 48, 128)      # dh % 64
        assert not plan(1, 3, 64, 128)      # h % sublane
        assert not plan(1, 8, 64, 100)      # max_t not block-divisible

    def test_off_contract_falls_back_identically(self):
        import jax.numpy as jnp

        from paddle_tpu.kernels import decode_attention as kda

        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(2, 3, 48).astype(np.float32))
        k = jnp.asarray(rng.randn(2, 50, 3, 48).astype(np.float32))
        lens = jnp.asarray([10, 50], jnp.int32)
        out = kda.flash_decode(q, k, k, lens, interpret=True)
        ref = kda.reference_decode(q, k, k, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# generation ops (also the op-contract gate's execution coverage)
# ---------------------------------------------------------------------------


class TestGenerationOps:
    def test_kv_cache_update_and_attend(self):
        L, b, max_t, h, dh = 2, 3, 128, 8, 64
        cache = KVCache("t_cache", L, b, max_t, h, dh)
        scope = ex.Scope()
        cache.allocate(scope)
        k_var = layers.data(name="k", shape=[1, h, dh], dtype="float32")
        v_var = layers.data(name="v", shape=[1, h, dh], dtype="float32")
        q_var = layers.data(name="q", shape=[1, h, dh], dtype="float32")
        pos = layers.data(name="pos", shape=[1], dtype="int32")
        lens = layers.data(name="lens", shape=[1], dtype="int32")
        pos_r = layers.reshape(pos, [b])
        lens_r = layers.reshape(lens, [b])
        cache.write(k_var, v_var, pos_r, layer=1)
        out = cache.attend(q_var, lens_r, layer=1, scale=dh**-0.5)
        exe = pt.Executor(pt.CPUPlace())
        rng = np.random.RandomState(0)
        kv = rng.randn(b, 1, h, dh).astype(np.float32)
        vv = rng.randn(b, 1, h, dh).astype(np.float32)
        qv = rng.randn(b, 1, h, dh).astype(np.float32)
        posv = np.asarray([[0], [3], [7]], np.int32)
        lensv = posv + 1
        (o,) = exe.run(feed={"k": kv, "v": vv, "q": qv, "pos": posv,
                             "lens": lensv},
                       fetch_list=[out], scope=scope)
        ck = np.asarray(scope.find_var(cache.k_name))
        # rows landed at the per-sequence positions of layer 1 only
        assert np.abs(ck[0]).sum() == 0.0
        for i in range(b):
            np.testing.assert_allclose(ck[1, i, posv[i, 0]], kv[i, 0])
        # single-row attention over a 1-row window == softmax over 1 = v
        np.testing.assert_allclose(np.asarray(o)[0, 0], vv[0, 0],
                                   atol=1e-5)

    def test_kv_cache_update_active_mask(self):
        L, b, max_t, h, dh = 1, 4, 128, 8, 64
        cache = KVCache("t_mask", L, b, max_t, h, dh)
        scope = ex.Scope()
        cache.allocate(scope)
        k_var = layers.data(name="k", shape=[1, h, dh], dtype="float32")
        pos = layers.data(name="pos", shape=[1], dtype="int32")
        act = layers.data(name="act", shape=[1], dtype="int32")
        cache.write(k_var, k_var, layers.reshape(pos, [b]), layer=0,
                    active=layers.reshape(act, [b]))
        exe = pt.Executor(pt.CPUPlace())
        kv = np.ones((b, 1, h, dh), np.float32)
        exe.run(feed={"k": kv, "pos": np.zeros((b, 1), np.int32),
                      "act": np.asarray([[1], [0], [1], [0]], np.int32)},
                fetch_list=[], scope=scope)
        ck = np.asarray(scope.find_var(cache.k_name))
        assert ck[0, 0].sum() > 0 and ck[0, 2].sum() > 0
        assert ck[0, 1].sum() == 0 and ck[0, 3].sum() == 0

    def test_kv_cache_reorder(self):
        L, b, max_t, h, dh = 2, 4, 128, 8, 64
        cache = KVCache("t_reord", L, b, max_t, h, dh)
        scope = ex.Scope()
        cache.allocate(scope)
        import jax.numpy as jnp

        marked = np.zeros(cache.shape, np.float32)
        for i in range(b):
            marked[:, i] = i + 1
        scope.set_var(cache.k_name, jnp.asarray(marked))
        scope.set_var(cache.v_name, jnp.asarray(marked))
        par = layers.data(name="par", shape=[1], dtype="int64")
        cache.reorder(layers.reshape(par, [b]))
        exe = pt.Executor(pt.CPUPlace())
        exe.run(feed={"par": np.asarray([[3], [3], [0], [1]], np.int64)},
                fetch_list=[], scope=scope)
        ck = np.asarray(scope.find_var(cache.k_name))
        assert [ck[0, i, 0, 0, 0] for i in range(b)] == [4, 4, 1, 2]

    def test_sample_token_greedy_is_argmax(self):
        logits = layers.data(name="lg", shape=[7], dtype="float32")
        out = layers.sample_token(logits, strategy="greedy")
        exe = pt.Executor(pt.CPUPlace())
        lv = np.random.RandomState(0).randn(5, 7).astype(np.float32)
        (o,) = exe.run(feed={"lg": lv}, fetch_list=[out])
        np.testing.assert_array_equal(
            np.asarray(o).reshape(-1), lv.argmax(axis=1))

    def test_sample_token_topk_in_range_and_rng_threaded(self):
        logits = layers.data(name="lg", shape=[9], dtype="float32")
        out = layers.sample_token(logits, strategy="sample",
                                  temperature=0.7, top_k=3)
        prog = fw.default_main_program()
        # attr-gated RNG: the sampling program threads the step key ...
        assert ex.program_uses_random(prog.global_block())
        exe = pt.Executor(pt.CPUPlace())
        lv = np.random.RandomState(1).randn(6, 9).astype(np.float32)
        top3 = np.argsort(-lv, axis=1)[:, :3]
        draws = set()
        for _ in range(4):
            (o,) = exe.run(feed={"lg": lv}, fetch_list=[out])
            o = np.asarray(o).reshape(-1)
            for i in range(6):
                assert o[i] in top3[i]
            draws.add(tuple(o.tolist()))
        # ... and successive runs fold a fresh counter (not frozen draws)
        assert len(draws) > 1

    def test_greedy_program_is_key_free(self):
        logits = layers.data(name="lg", shape=[7], dtype="float32")
        layers.sample_token(logits, strategy="greedy")
        assert not ex.program_uses_random(
            fw.default_main_program().global_block())


# ---------------------------------------------------------------------------
# drivers: parity + compile-flat acceptance
# ---------------------------------------------------------------------------


class TestGreedyGeneration:
    @pytest.mark.parametrize("batch", [1, 4])
    def test_cached_token_identical_to_recompute_and_compile_flat(
            self, batch):
        """THE acceptance criterion: >= 64 greedy tokens, cached vs
        recompute token-identical, executor compile cache flat after
        prefill + first decode step — at two batch sizes."""
        dims = dict(TINY, max_length=66, batch_size=batch, src_seq_len=6,
                    max_out_len=64, bos_id=0, eos_id=-1)  # no early eos
        rng = np.random.RandomState(3 + batch)
        src = _src(rng, batch, 6)
        scope = ex.Scope()

        cached = GenerationSession(
            T.build_generation_programs(kv_cache=True, **dims),
            scope=scope)
        cached.init_params()
        toks_c, steps = cached.generate(src)
        assert steps == 64 and toks_c.shape == (batch, 64)
        n_compiled = cached.compile_count
        # 64 more tokens + a fresh generate: the cache may NOT grow
        cached.generate(src)
        assert cached.compile_count == n_compiled

        recompute = GenerationSession(
            T.build_generation_programs(kv_cache=False, **dims),
            scope=scope)
        toks_r, _ = recompute.generate(src)
        np.testing.assert_array_equal(toks_c, toks_r)
        n_compiled = recompute.compile_count
        recompute.generate(src)
        assert recompute.compile_count == n_compiled

    def test_eos_terminates_and_pads(self):
        """A trained-free check of the eos contract: with eos_id set to
        the argmax the model emits immediately, generation stops at step
        1 and the emitted stream is eos-padded."""
        dims = dict(TINY, batch_size=2, src_seq_len=6, max_out_len=8,
                    bos_id=0)
        rng = np.random.RandomState(5)
        src = _src(rng, 2, 6)
        probe = GenerationSession(
            T.build_generation_programs(eos_id=-1, **dims))
        probe.init_params()
        first = int(probe.generate(src, max_tokens=1)[0][0, 0])
        sess = GenerationSession(
            T.build_generation_programs(eos_id=first, **dims),
            scope=probe.scope)
        toks, steps = sess.generate(src)
        assert steps <= 8
        assert (toks[:, 0] == first).any()

    def test_trained_copy_task_greedy_decode(self):
        """End-to-end quality: train the tiny transformer on the copy
        task, then greedy-generate through the cache and check the
        output reproduces the source prefix."""
        vocab, seq, bs = 16, 6, 32
        dims = dict(src_vocab_size=vocab, trg_vocab_size=vocab,
                    max_length=seq + 2, n_layer=1, n_head=2, d_key=16,
                    d_value=16, d_model=32, d_inner_hid=64)
        rng = np.random.RandomState(0)
        train_prog, train_startup = pt.Program(), pt.Program()
        with fw.guard_unique_name():
            with pt.program_guard(train_prog, train_startup):
                avg_cost, _, _ = T.transformer(
                    batch_size=bs, src_seq_len=seq, trg_seq_len=seq,
                    dropout_rate=0.0, **dims)
                pt.optimizer.AdamOptimizer(
                    learning_rate=3e-3).minimize(avg_cost)
        scope = ex.Scope()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(train_startup, scope=scope)
        losses = []
        for _ in range(120):
            src = rng.randint(2, vocab, (bs, seq, 1)).astype(np.int64)
            pos = np.tile(np.arange(seq, dtype=np.int64)[None, :, None],
                          (bs, 1, 1))
            trg_in = np.concatenate(
                [np.zeros((bs, 1, 1), np.int64), src[:, :-1]], axis=1)
            (lv,) = exe.run(
                train_prog,
                feed={"src_word": src, "src_pos": pos, "trg_word": trg_in,
                      "trg_pos": pos, "lbl_word": src,
                      "lbl_weight": np.ones((bs, seq, 1), np.float32)},
                fetch_list=[avg_cost], scope=scope)
            losses.append(float(np.asarray(lv)))
        assert losses[-1] < losses[0] * 0.5

        gen_b = 4
        sess = GenerationSession(
            T.build_generation_programs(
                batch_size=gen_b, src_seq_len=seq, max_out_len=seq,
                bos_id=0, eos_id=1, **dims),
            scope=scope)
        src = rng.randint(2, vocab, (gen_b, seq, 1)).astype(np.int64)
        toks, _ = sess.generate(src)
        acc = float((toks[:, :seq] == src[:, :, 0]).mean())
        assert acc > 0.55, (acc, toks, src[:, :, 0])


class TestBeamDecoding:
    def _trained_free_setup(self, beam=3, b=2, seq=6):
        rng = np.random.RandomState(0)
        src = _src(rng, b, seq)
        pos = np.tile(np.arange(seq, dtype=np.int64)[None, :, None],
                      (b, 1, 1))
        train_prog, train_startup = pt.Program(), pt.Program()
        with fw.guard_unique_name():
            with pt.program_guard(train_prog, train_startup):
                T.transformer(batch_size=b, src_seq_len=seq,
                              trg_seq_len=seq, dropout_rate=0.0, **TINY)
        scope = ex.Scope()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(train_startup, scope=scope)
        return src, pos, scope, exe

    def _run_while_decoder(self, exe, scope, src, pos, beam, b, seq):
        dec_prog, dec_startup = pt.Program(), pt.Program()
        with fw.guard_unique_name():
            with pt.program_guard(dec_prog, dec_startup):
                sent, scores, _ = T.build_decoder(
                    batch_size=b, src_seq_len=seq, max_out_len=5,
                    beam_size=beam, bos_id=0, eos_id=1, **TINY)
        s, sc = exe.run(dec_prog,
                        feed={"src_word": src, "src_pos": pos},
                        fetch_list=[sent, scores], scope=scope)
        return np.asarray(s), np.asarray(sc)

    def test_while_program_flag_parity_and_driver_match(self):
        """build_decoder cached-While == recompute-While == per-token
        beam driver, on one shared scope."""
        beam, b, seq = 3, 2, 6
        src, pos, scope, exe = self._trained_free_setup(beam, b, seq)
        try:
            FLAGS.kv_cache = True
            s_on, sc_on = self._run_while_decoder(exe, scope, src, pos,
                                                  beam, b, seq)
            FLAGS.kv_cache = False
            s_off, sc_off = self._run_while_decoder(exe, scope, src, pos,
                                                    beam, b, seq)
        finally:
            FLAGS.reset("kv_cache")
        np.testing.assert_array_equal(s_on, s_off)
        np.testing.assert_allclose(sc_on, sc_off, rtol=1e-4)

        sess = GenerationSession(
            T.build_generation_programs(
                batch_size=b, src_seq_len=seq, max_out_len=5,
                beam_size=beam, bos_id=0, eos_id=1, **TINY),
            scope=scope)
        sent, scores = sess.generate_beam(src)
        np.testing.assert_array_equal(sent, s_on)
        np.testing.assert_allclose(scores, sc_on, rtol=1e-4)
        # beam scores sorted best-first
        assert np.all(np.diff(scores, axis=1) <= 1e-5)
        # driver compile cache flat across another full generation
        n = sess.compile_count
        sess.generate_beam(src)
        assert sess.compile_count == n

    def test_beam_pair_requires_cache(self):
        with pytest.raises(ValueError, match="KV-cache"):
            T.build_generation_programs(
                batch_size=2, src_seq_len=6, max_out_len=5, beam_size=2,
                kv_cache=False, **TINY)


# ---------------------------------------------------------------------------
# static analysis coverage
# ---------------------------------------------------------------------------


class TestGenerationStaticAnalysis:
    def test_programs_verify_clean(self):
        from paddle_tpu.analysis import verify_program

        for strat in ("greedy", "sample"):
            p = T.build_generation_programs(
                batch_size=2, src_seq_len=6, max_out_len=5,
                strategy=strat, top_k=4, **TINY)
            for prog, feeds, fetch in (
                    (p.prefill, ["src_word", "src_pos", "gen_active"],
                     p.prefill_fetch),
                    # greedy self-feeds the token under
                    # FLAGS_fused_decode_step; decode_feeds names the
                    # route's actual feed list
                    (p.decode, p.decode_feeds, p.decode_fetch)):
                findings = verify_program(prog, feed_names=feeds,
                                          fetch_names=fetch,
                                          check_dead=True)
                assert not findings, [str(f) for f in findings]

    def test_decode_kernel_lint_red_gate(self):
        """check_decode_plan must NAME a gate that silently rejects a
        must-accept shape and a plan violating the block contract."""
        from paddle_tpu.analysis.kernel_lint import check_decode_plan

        cfg = dict(label="fab", b=1, h=8, dh=64, max_t=128,
                   dtype="float32")
        findings = []
        check_decode_plan(cfg, False, 128, False, findings)
        assert any(f.check == "kernel-plan-reject" for f in findings)
        findings = []
        check_decode_plan(cfg, True, 96, False, findings)  # 128 % 96
        assert any(f.check == "kernel-grid-divisibility"
                   for f in findings)
        findings = []
        check_decode_plan(dict(cfg, h=3, must_accept=False), True, 128,
                          False, findings)
        assert any(f.check == "kernel-misaligned-block"
                   for f in findings)

    def test_decode_matrix_must_accepts(self):
        """The perf-critical decode plans stay accepted (regression pin
        on the plan gate)."""
        from paddle_tpu.analysis.kernel_lint import (_DECODE_MATRIX,
                                                     lint_kernel_plans)

        findings, report = lint_kernel_plans()
        decode = {r["label"]: r for r in report["decode_attention"]}
        for cfg in _DECODE_MATRIX:
            expect = cfg.get("must_accept", True)
            assert decode[cfg["label"]]["accepted"] == expect, cfg
        assert not [f for f in findings
                    if "decode" in getattr(f, "op_type", "")]


# ---------------------------------------------------------------------------
# serving: continuous token-level batching
# ---------------------------------------------------------------------------


def _tiny_serving_model(name, slots=4, max_out=24):
    from paddle_tpu.serving.generation import (GenerationConfig,
                                               GenerationServingModel)

    cfg = GenerationConfig(
        name, slots=slots,
        src_vocab_size=32, trg_vocab_size=32, max_length=32,
        n_layer=1, n_head=2, d_key=8, d_value=8, d_model=16,
        d_inner_hid=32, src_seq_len=8, max_out_len=max_out,
        bos_id=0, eos_id=1)
    model = GenerationServingModel(cfg)
    for prog in (model.session.p.prefill, model.session.p.decode,
                 model.session.p.startup):
        prog.random_seed = 13
    model.init_params()
    return model


class TestContinuousBatching:
    def test_concurrent_requests_coalesce_without_retrace(self):
        from paddle_tpu.serving.generation import ContinuousBatcher

        model = _tiny_serving_model("genloc")
        model.warmup()
        batcher = ContinuousBatcher(model)
        batcher.start()
        try:
            n_compiled = model.compile_count
            results = [None] * 6
            def worker(i):
                results[i] = batcher.submit([2 + i, 5], max_tokens=8,
                                            timeout=60.0)
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for toks, meta in results:
                assert 1 <= len(toks) <= 8
                assert meta["ttft_ms"] >= 0
                assert meta["finished"] in ("eos", "max_tokens")
            # 6 requests over 4 slots: someone waited for a retirement
            slots = {meta["slot"] for _, meta in results}
            assert slots <= set(range(model.slots))
            # the whole burst compiled NOTHING (warm program pair)
            assert model.compile_count == n_compiled
        finally:
            batcher.stop()

    def test_late_join_does_not_stall_or_retrace(self):
        from paddle_tpu.serving.generation import ContinuousBatcher

        model = _tiny_serving_model("genlate", max_out=24)
        model.warmup()
        batcher = ContinuousBatcher(model)
        batcher.start()
        try:
            n_compiled = model.compile_count
            done = {}

            def long_req():
                done["long"] = (batcher.submit([3, 5, 7], max_tokens=24),
                                time.perf_counter())

            t = threading.Thread(target=long_req)
            t.start()
            time.sleep(0.005)
            short = batcher.submit([9, 2], max_tokens=2, timeout=60.0)
            t_short = time.perf_counter()
            t.join(timeout=60)
            (long_toks, long_meta), t_long = done["long"]
            assert len(short[0]) <= 2
            if long_meta["finished"] == "max_tokens":
                # the short request must not have waited for the long one
                assert t_short <= t_long
            assert model.compile_count == n_compiled
        finally:
            batcher.stop()

    def test_validation_errors(self):
        from paddle_tpu.serving.generation import ContinuousBatcher

        model = _tiny_serving_model("genval")
        model.warmup()
        batcher = ContinuousBatcher(model)
        batcher.start()
        try:
            with pytest.raises(ValueError, match="empty"):
                batcher.submit([])
            with pytest.raises(ValueError, match="pad id"):
                batcher.submit([999])
            with pytest.raises(ValueError, match="pad id"):
                batcher.submit([3, 0, 5])  # mid-prompt pad id rejected
            with pytest.raises(ValueError, match="max_prompt_len"):
                batcher.submit(list(range(2, 13)))
            with pytest.raises(ValueError, match="positive"):
                batcher.submit([3], max_tokens=0)
        finally:
            batcher.stop()

    def test_requires_kv_cache_flag(self):
        from paddle_tpu.serving.generation import (GenerationConfig,
                                                   GenerationServingModel)

        FLAGS.kv_cache = False
        try:
            with pytest.raises(ValueError, match="kv_cache"):
                GenerationServingModel(GenerationConfig(
                    "nocache", src_vocab_size=8, trg_vocab_size=8,
                    max_length=16, n_layer=1, n_head=2, d_key=8,
                    d_value=8, d_model=16, d_inner_hid=32,
                    src_seq_len=4, max_out_len=4))
        finally:
            FLAGS.reset("kv_cache")

    def test_server_generate_endpoint(self):
        """HTTP :generate round-trip on an in-process InferenceServer
        (readiness, models_info, and the endpoint contract)."""
        import json
        import urllib.request

        from paddle_tpu.serving import InferenceServer

        srv = InferenceServer([], port=0)
        model = _tiny_serving_model("genhttp")
        srv.add_generation_model(model)
        port = srv.start()
        try:
            body = json.dumps({"prompt": [3, 5], "max_tokens": 4}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/models/genhttp:generate",
                data=body, headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                payload = json.loads(r.read())
            assert 1 <= len(payload["tokens"]) <= 4
            assert payload["meta"]["ttft_ms"] >= 0
            infos = {m["name"]: m for m in srv.models_info()}
            assert infos["genhttp"]["type"] == "generation"
            assert infos["genhttp"]["ready"]
            assert srv.readiness()["ready"]
        finally:
            srv.stop()
