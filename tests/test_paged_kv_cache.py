"""Paged KV cache with shared-prefix block reuse (PR 20).

Acceptance criteria covered here:
  * BlockAllocator's ledger invariants: exclusive alloc at ref 1,
    share/free refcount lifecycle, exhaustion raises MemoryError with
    the ledger intact, double-free and share-of-unallocated are errors,
    the reserve withholds the trap block;
  * the paged ops are exact: reference_decode_paged over the static
    identity table is BIT-identical to the ring reference;
    flash_decode_paged passes interpret-mode parity against it on a
    scattered (non-identity) table with ragged lengths; the plan gate
    rejects misaligned block_t and oversized tables with a bit-identical
    XLA fallback;
  * greedy decode through the paged program pair is TOKEN-IDENTICAL to
    the flag-off ring pair across >= 64 tokens with a FLAT executor
    compile cache, at batch 1 and 64 (the PR-11 protocol);
  * flag-off builds are byte-stable (op-for-op free of the paged ops)
    and parameter names interop across the flag;
  * cow_if_shared isolates divergent appends: after fork_slot maps a
    prefix into a second slot, the writer's append copies first and the
    sharer's rows survive (tokens match a no-fork baseline exactly);
  * the serving exploit: N same-prompt requests prefill ONCE
    (prefix_hits_total == N-1), admission is by block budget — a
    request without blocks stays pending despite a free slot — and
    every block returns to the free list on retirement;
  * telemetry is zero-cost with FLAGS_monitor off (no metrics created);
  * the memory planner charges the pools to the kv_cache class.
"""

import numpy as np
import pytest

from paddle_tpu.core import executor as ex
from paddle_tpu.flags import FLAGS
from paddle_tpu.generation import GenerationSession
from paddle_tpu.generation.kv_cache import BlockAllocator, PagedKVCache
from paddle_tpu.models import transformer as T

TINY = dict(src_vocab_size=16, trg_vocab_size=16, max_length=12,
            n_layer=2, n_head=2, d_key=8, d_value=8, d_model=16,
            d_inner_hid=32)


def _src(rng, b, seq, vocab=16):
    return rng.randint(2, vocab, (b, seq, 1)).astype(np.int64)


# ---------------------------------------------------------------------------
# allocator ledger
# ---------------------------------------------------------------------------


class TestBlockAllocator:
    def test_alloc_free_roundtrip(self):
        a = BlockAllocator(8)
        got = a.alloc(3)
        assert got == [0, 1, 2]          # lowest-first, stable
        assert a.used_count == 3 and a.free_count == 5
        assert all(a.refcount(b) == 1 for b in got)
        a.free(got)
        assert a.used_count == 0 and a.free_count == 8
        assert a.refcount(0) == 0

    def test_share_refcount_lifecycle(self):
        a = BlockAllocator(4)
        (b,) = a.alloc(1)
        a.share([b])
        a.share([b])
        assert a.refcount(b) == 3
        a.free([b])
        a.free([b])
        assert a.refcount(b) == 1 and a.used_count == 1
        a.free([b])
        assert a.free_count == 4

    def test_exhaustion_raises_and_keeps_ledger(self):
        a = BlockAllocator(4)
        a.alloc(3)
        with pytest.raises(MemoryError):
            a.alloc(2)
        # the failed alloc must not have consumed the last block
        assert a.free_count == 1
        assert a.alloc(1) == [3]

    def test_double_free_and_share_unallocated_raise(self):
        a = BlockAllocator(4)
        (b,) = a.alloc(1)
        a.free([b])
        with pytest.raises(ValueError):
            a.free([b])
        with pytest.raises(ValueError):
            a.share([2])

    def test_reserve_withholds_trap_block(self):
        a = BlockAllocator(8, reserve=1)
        assert a.free_count == 7
        assert 0 not in a.alloc(7)       # block 0 never handed out
        with pytest.raises(MemoryError):
            a.alloc(1)


# ---------------------------------------------------------------------------
# paged ops: exactness, kernel parity, plan gate
# ---------------------------------------------------------------------------


class TestPagedOps:
    def _ring_and_pool(self, rng, b, h, dh, max_t, block_t, dtype="float32"):
        """A ring-layout cache and its identity-table paged pool holding
        the SAME rows."""
        import jax.numpy as jnp

        mb = max_t // block_t
        ring = rng.randn(b, max_t, h, dh).astype(dtype)
        pool = ring.reshape(b * mb, block_t, h, dh)
        table = np.arange(b * mb, dtype=np.int32).reshape(b, mb)
        return jnp.asarray(ring), jnp.asarray(pool), jnp.asarray(table)

    def test_reference_paged_identity_table_bit_equal_to_ring(self):
        import jax.numpy as jnp

        from paddle_tpu.kernels import decode_attention as kda

        rng = np.random.RandomState(0)
        b, h, dh, max_t, bt = 4, 2, 16, 64, 16
        k, kp, tab = self._ring_and_pool(rng, b, h, dh, max_t, bt)
        v, vp, _ = self._ring_and_pool(rng, b, h, dh, max_t, bt)
        q = jnp.asarray(rng.randn(b, h, dh).astype("float32"))
        lens = jnp.asarray([1, 17, 40, 64], jnp.int32)
        ring = kda.reference_decode(q, k, v, lens, scale=0.25)
        paged = kda.reference_decode_paged(q, kp, vp, tab, lens, scale=0.25)
        np.testing.assert_array_equal(np.asarray(ring), np.asarray(paged))

    def test_flash_paged_interpret_parity_scattered_table(self):
        """The Pallas block walk vs the reference gather on a SHUFFLED
        table (the serving allocator never hands out identity) with
        ragged mid-block lengths."""
        import jax.numpy as jnp

        from paddle_tpu.kernels import decode_attention as kda

        rng = np.random.RandomState(1)
        b, h, dh, bt, mb = 4, 8, 64, 16, 4
        pool_n = 32                       # bigger than b*mb: holes
        kp = jnp.asarray(rng.randn(pool_n, bt, h, dh).astype("float32"))
        vp = jnp.asarray(rng.randn(pool_n, bt, h, dh).astype("float32"))
        table = jnp.asarray(
            rng.permutation(pool_n)[:b * mb].reshape(b, mb).astype("int32"))
        q = jnp.asarray(rng.randn(b, h, dh).astype("float32"))
        lens = jnp.asarray([3, 16, 33, 64], jnp.int32)

        ok, _, _ = kda._paged_plan(q, kp, table, True)
        assert ok
        ref = kda.reference_decode_paged(q, kp, vp, table, lens, scale=0.125)
        out = kda.flash_decode_paged(q, kp, vp, table, lens, scale=0.125,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)

    def test_paged_scatter_rows_targets_table_blocks(self):
        """Rows land at table-directed pool blocks; inactive lanes leave
        the pool untouched."""
        import jax.numpy as jnp

        from paddle_tpu.kernels import decode_attention as kda

        rng = np.random.RandomState(2)
        L, pool_n, bt, h, dh = 1, 8, 8, 2, 16
        cache = jnp.zeros((L, pool_n, bt, h, dh), jnp.float32)
        new = jnp.asarray(rng.randn(2, 1, h, dh).astype("float32"))
        table = jnp.asarray([[5, 1], [2, 7]], jnp.int32)
        pos = jnp.asarray([9, 3], jnp.int32)    # lane0 row 9 -> blk idx 1
        act = jnp.asarray([1, 0], jnp.int32)
        out = np.asarray(kda.paged_scatter_rows(cache, new, table, pos,
                                                act, 0))
        np.testing.assert_array_equal(out[0, 1, 1], np.asarray(new)[0, 0])
        assert out[0, 2].sum() == 0 and out[0, 7].sum() == 0  # lane1 inactive
        mask = np.ones(pool_n, bool)
        mask[1] = False
        assert np.all(out[0, mask] == 0)

    def test_paged_plan_gate_contract(self):
        import jax

        from paddle_tpu.analysis.kernel_lint import _pretend_tpu
        from paddle_tpu.kernels import decode_attention as kda
        from paddle_tpu.kernels import decode_step as kds

        def spec(shape, dtype="float32"):
            return jax.ShapeDtypeStruct(shape, dtype)

        def plan(b=4, h=8, dh=64, bt=16, mb=8):
            with _pretend_tpu():
                return kda._paged_plan(
                    spec((b, h, dh)), spec((b * mb, bt, h, dh)),
                    spec((b, mb), "int32"), None)

        assert plan()[0]
        assert not plan(bt=12)[0]          # block_t % 8
        assert not plan(dh=48)[0]          # lane alignment
        assert not plan(b=64, mb=128)[0]   # b*mb > _PAGED_TABLE_CAP
        # off-TPU without explicit interpret: fallback (interpret=True)
        ok, _, interp = kda._paged_plan(
            spec((4, 8, 64)), spec((32, 16, 8, 64)),
            spec((4, 8), "int32"), None)
        assert ok and interp
        with _pretend_tpu():
            mega = kds._paged_megastep_plan(
                128, 8, 128, 256, 16, 16, 4, 8, 8, "float32")
            assert mega.ok and mega.fuse_ffn
            # d_head 64: Mosaic refuses the hand-DMA'd walk (see
            # _megastep_plan) — compiled mode rejects
            assert not kds._paged_megastep_plan(
                128, 8, 64, 256, 16, 16, 4, 8, 8, "float32").ok
            assert not kds._paged_megastep_plan(
                128, 8, 128, 256, 12, 16, 4, 8, 8, "float32").ok
            assert not kds._paged_megastep_plan(
                128, 8, 128, 256, 16, 16, 64, 128, 8, "float32").ok

    def test_fused_paged_megastep_falls_back_bit_identical(self):
        """Off-contract (block_t=12 pools) the fused paged entry IS the
        composed reference — bit-equal outputs and caches."""
        import jax.numpy as jnp

        from paddle_tpu.kernels import decode_step as kds

        rng = np.random.RandomState(3)
        dm, h, dh, di, bt, b, mb = 128, 8, 8, 256, 12, 2, 2
        hd = h * dh

        def f(*s):
            return jnp.asarray(rng.randn(*s).astype("float32") * 0.1)

        weights = [f(b, 1, dm), f(dm, 3 * hd), f(hd, dm), f(dm) + 1,
                   f(dm), f(dm, hd), f(hd, dm), f(dm) + 1, f(dm),
                   f(dm, di), f(di), f(di, dm), f(dm), f(dm) + 1, f(dm)]
        pools = [f(1, b * mb, bt, h, dh) for _ in range(4)]
        tab = jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
        ints = [jnp.asarray(a, jnp.int32) for a in
                ([1, 5], [2, 6], [bt, 3], [1, 1])]
        kw = dict(layer=0, n_head=h, scale=dh ** -0.5)
        ref = kds.reference_decode_step_paged(
            *weights, *pools, ints[0], ints[1], ints[2], tab, tab,
            ints[3], **kw)
        fused = kds.fused_decode_step_paged(
            *weights, *pools, ints[0], ints[1], ints[2], tab, tab,
            ints[3], **kw)
        for a, b_ in zip(ref, fused):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


# ---------------------------------------------------------------------------
# host choreography: COW + fork on a bare scope
# ---------------------------------------------------------------------------


class TestCowAndFork:
    def _cache(self):
        c = PagedKVCache("t", num_layers=1, batch=2, max_t=32,
                         n_head=2, d_head=8, block_t=8, num_blocks=8)
        scope = ex.Scope()
        c.reset_dynamic(scope)
        return c, scope

    def test_fork_shares_and_cow_preserves_sharer(self):
        import jax.numpy as jnp

        c, scope = self._cache()
        blocks = c.allocator.alloc(2)
        c.set_table_row(scope, 0, blocks)
        scope.set_var(c.len_name, jnp.asarray([12, 0], jnp.int32))
        # stamp recognizable rows into slot 0's pool blocks
        pool = np.asarray(scope.find_var(c.k_name)).copy()
        pool[0, blocks[0]] = 1.0
        pool[0, blocks[1]] = 2.0
        scope.set_var(c.k_name, jnp.asarray(pool))

        c.fork_slot(scope, 1, 0, 12)
        assert c.allocator.refcount(blocks[0]) == 2
        assert c.slot_blocks(scope, 1, 12) == blocks

        # slot 0 appends at row 12 (block idx 1, shared) -> COW copies
        assert c.cow_if_shared(scope, 0, 12)
        new = c.slot_blocks(scope, 0, 16)[1]
        assert new not in blocks
        assert c.allocator.refcount(blocks[1]) == 1   # sharer keeps it
        assert c.allocator.refcount(new) == 1
        # sharer's table and rows are untouched; the copy carried them
        assert c.slot_blocks(scope, 1, 12) == blocks
        pool = np.asarray(scope.find_var(c.k_name))
        np.testing.assert_array_equal(pool[0, new], pool[0, blocks[1]])
        # unshared append: no copy
        assert not c.cow_if_shared(scope, 0, 13)

    def test_fork_releases_previous_mapping(self):
        import jax.numpy as jnp

        c, scope = self._cache()
        a = c.allocator.alloc(1)
        b = c.allocator.alloc(1)
        c.set_table_row(scope, 0, a)
        c.set_table_row(scope, 1, b)
        scope.set_var(c.len_name, jnp.asarray([6, 6], jnp.int32))
        c.fork_slot(scope, 1, 0, 6)
        assert c.allocator.refcount(b[0]) == 0        # old mapping freed
        assert c.allocator.refcount(a[0]) == 2

    def test_static_allocate_is_identity(self):
        c = PagedKVCache("t", num_layers=1, batch=2, max_t=32,
                         n_head=2, d_head=8, block_t=8)
        scope = ex.Scope()
        c.allocate(scope)
        np.testing.assert_array_equal(
            c.host_table(scope),
            np.arange(2 * 4, dtype=np.int32).reshape(2, 4))
        assert c.allocator is None
        small = PagedKVCache("u", num_layers=1, batch=2, max_t=32,
                             n_head=2, d_head=8, block_t=8, num_blocks=4)
        with pytest.raises(ValueError):
            small.allocate(ex.Scope())

    def test_block_t_alignment_enforced(self):
        with pytest.raises(ValueError):
            PagedKVCache("t", 1, 2, 32, 2, 8, block_t=12)


# ---------------------------------------------------------------------------
# program pair: paged vs ring token identity + flag-off stability
# ---------------------------------------------------------------------------


class TestPagedGeneration:
    @pytest.mark.parametrize("batch", [1, 64])
    def test_token_identity_paged_vs_ring_compile_flat(self, batch):
        """THE acceptance criterion: >= 64 greedy tokens, paged vs
        flag-off ring path token-identical, compile cache flat for BOTH
        program pairs — at batch 1 and 64."""
        dims = dict(TINY, max_length=66, batch_size=batch, src_seq_len=6,
                    max_out_len=64, bos_id=0, eos_id=-1)  # no early eos
        rng = np.random.RandomState(7 + batch)
        src = _src(rng, batch, 6)
        scope = ex.Scope()

        ring = GenerationSession(
            T.build_generation_programs(kv_cache=True, **dims),
            scope=scope)
        ring.init_params()
        toks_r, steps = ring.generate(src)
        assert steps == 64 and toks_r.shape == (batch, 64)
        n_compiled = ring.compile_count
        ring.generate(src)
        assert ring.compile_count == n_compiled

        try:
            FLAGS.set("paged_kv_cache", True)
            paged = GenerationSession(
                T.build_generation_programs(kv_cache=True, **dims),
                scope=scope)
            assert paged.p.paged
            toks_p, steps_p = paged.generate(src)
            assert steps_p == 64
            n_compiled = paged.compile_count
            paged.generate(src)
            assert paged.compile_count == n_compiled
        finally:
            FLAGS.reset("paged_kv_cache")
        np.testing.assert_array_equal(toks_p, toks_r)

    def test_flag_off_graph_identity_and_param_interop(self):
        """Flag-off builds are byte-stable op-for-op (no paged ops, ring
        cache vars); parameter names are IDENTICAL across the flag
        (checkpoints interop)."""
        dims = dict(TINY, batch_size=2, src_seq_len=6, max_out_len=5)

        p_off = T.build_generation_programs(kv_cache=True, **dims)
        p_off2 = T.build_generation_programs(kv_cache=True, **dims)
        try:
            FLAGS.set("paged_kv_cache", True)
            p_on = T.build_generation_programs(kv_cache=True, **dims)
        finally:
            FLAGS.reset("paged_kv_cache")

        def ops(p):
            return [op.type for op in p.decode.global_block().ops]

        assert ops(p_off) == ops(p_off2)      # flag-off build is stable
        assert not any(o.startswith("paged_") for o in ops(p_off))
        assert any(o.startswith("paged_") or o == "fused_decode_step_paged"
                   for o in ops(p_on))
        off_vars = set(p_off.decode.global_block().vars)
        assert p_on.self_cache.table_name not in off_vars

        def param_names(p):
            return {v.name for v in
                    p.decode.global_block().all_parameters()}

        assert param_names(p_on) == param_names(p_off)

    def test_unfused_paged_route_token_identity(self):
        """FLAGS_fused_decode_step off decomposes the decode step into
        the discrete paged ops (paged_kv_cache_update +
        paged_decode_attention) — that walk must stay token-identical
        to the flag-off ring build."""
        dims = dict(TINY, max_length=66, batch_size=2, src_seq_len=6,
                    max_out_len=8, bos_id=0, eos_id=-1)
        rng = np.random.RandomState(11)
        src = _src(rng, 2, 6)
        scope = ex.Scope()
        try:
            FLAGS.set("fused_decode_step", False)
            ring = GenerationSession(
                T.build_generation_programs(kv_cache=True, **dims),
                scope=scope)
            ring.init_params()
            toks_r, _ = ring.generate(src)

            FLAGS.set("paged_kv_cache", True)
            paged = GenerationSession(
                T.build_generation_programs(kv_cache=True, **dims),
                scope=scope)
            ops = [op.type for op in paged.p.decode.global_block().ops]
            assert "paged_decode_attention" in ops
            assert "paged_kv_cache_update" in ops
            toks_p, _ = paged.generate(src)
        finally:
            FLAGS.reset("fused_decode_step")
            FLAGS.reset("paged_kv_cache")
        np.testing.assert_array_equal(toks_p, toks_r)

    def test_paged_beam_reorder_matches_ring_beam(self):
        """Beam programs under the flag swap kv_cache_reorder for
        paged_kv_cache_reorder (the parent gather permutes block-table
        ROWS, not pool bytes); hypotheses and scores must match the
        ring beam build exactly."""
        dims = dict(TINY, batch_size=2, src_seq_len=6, max_out_len=5,
                    beam_size=2, bos_id=0, eos_id=1)
        rng = np.random.RandomState(13)
        src = _src(rng, 2, 6)
        scope = ex.Scope()
        ring = GenerationSession(
            T.build_generation_programs(kv_cache=True, **dims),
            scope=scope)
        ring.init_params()
        sent_r, scores_r = ring.generate_beam(src)
        try:
            FLAGS.set("paged_kv_cache", True)
            paged = GenerationSession(
                T.build_generation_programs(kv_cache=True, **dims),
                scope=scope)
            ops = [op.type for op in paged.p.decode.global_block().ops]
            assert "paged_kv_cache_reorder" in ops
            sent_p, scores_p = paged.generate_beam(src)
        finally:
            FLAGS.reset("paged_kv_cache")
        np.testing.assert_array_equal(sent_p, sent_r)
        np.testing.assert_allclose(scores_p, scores_r, rtol=1e-6)


# ---------------------------------------------------------------------------
# serving: shared-prefix admission, block budget, release, telemetry
# ---------------------------------------------------------------------------


def _drive(batcher, reqs, max_iters=300):
    """Synchronous admit/step loop (no scheduler thread): returns when
    every request's event is set."""
    for r in reqs:
        batcher._pending_join.append(r)
    it = 0
    while not all(r.event.is_set() for r in reqs):
        batcher._admit()
        batcher._step()
        it += 1
        assert it < max_iters, "batcher made no progress"


class TestPagedServing:
    def _model(self, slots=4):
        from paddle_tpu.serving.generation import (
            ContinuousBatcher, build_demo_generation_model)

        model = build_demo_generation_model(slots=slots)
        model.warmup()
        return model, ContinuousBatcher(model)

    def test_shared_prefix_prefills_once_and_tokens_match_ring(self):
        from paddle_tpu import monitor
        from paddle_tpu.serving.generation import _GenRequest

        prompts = [[5, 9, 3], [5, 9, 3], [5, 9, 3], [7, 2]]

        def run(paged):
            try:
                if paged:
                    FLAGS.set("paged_kv_cache", True)
                model, b = self._model()
                pre0 = monitor.counter(
                    "serving.gen.gendemo.prefills").value
                hit0 = monitor.counter(
                    "generation.gendemo.prefix_hits_total").value
                reqs = [_GenRequest(list(p), 12) for p in prompts]
                _drive(b, reqs)
                pre = monitor.counter(
                    "serving.gen.gendemo.prefills").value - pre0
                hit = monitor.counter(
                    "generation.gendemo.prefix_hits_total").value - hit0
                return model, b, [list(r.tokens) for r in reqs], pre, hit
            finally:
                if paged:
                    FLAGS.reset("paged_kv_cache")

        try:
            FLAGS.set("monitor", True)
            _, _, toks_ring, pre_ring, _ = run(False)
            model, b, toks_paged, pre_paged, hits = run(True)
        finally:
            FLAGS.reset("monitor")

        assert toks_paged == toks_ring
        assert pre_ring == 4               # ring prefills every lane
        assert pre_paged == 2              # 3 sharers prefill ONCE + 1
        assert hits == 2                   # N-1 for the shared triple
        # retirement returned every block; the prefix registry drained
        p = model.session.p
        assert p.self_cache.allocator.used_count == 0
        assert p.cross_cache.allocator.used_count == 0
        assert not b._prefix_map

    def test_admission_is_by_block_budget_not_slots(self):
        """A request that cannot get blocks stays PENDING even with free
        slots, and admits as soon as a retirement frees them."""
        from paddle_tpu.serving.generation import _GenRequest

        try:
            FLAGS.set("paged_kv_cache", True)
            # 1 non-trap block per pool: ONE request (1 self + 1 cross
            # needed at max_tokens=12, prompt len 3) exhausts both
            # pools; a second DISTINCT prompt must wait for retirement
            FLAGS.set("kv_cache_blocks", 2)
            model, b = self._model()
            p = model.session.p
            assert p.self_cache.allocator.free_count == 1
            r1 = _GenRequest([5, 9, 3], 12)
            r2 = _GenRequest([7, 2, 4], 12)
            b._pending_join.append(r1)
            b._pending_join.append(r2)
            b._admit()
            assert b._slot_req.count(None) == model.slots - 1
            assert len(b._pending_join) == 1      # r2 held back
            assert p.self_cache.allocator.free_count == 0
            it = 0
            while not r2.event.is_set():
                b._admit()
                b._step()
                it += 1
                assert it < 200
            assert r1.event.is_set() and len(r1.tokens) == 12
            assert len(r2.tokens) == 12
            assert p.self_cache.allocator.used_count == 0
        finally:
            FLAGS.reset("kv_cache_blocks")
            FLAGS.reset("paged_kv_cache")

    def test_fork_then_diverge_cow_keeps_sharer_tokens(self):
        """The speculative-decode skeleton: fork a live sequence into a
        spare slot mid-decode; the writer's next appends must COW and
        the original's tokens must match a no-fork baseline exactly."""
        from paddle_tpu import monitor
        from paddle_tpu.serving.generation import _GenRequest

        def run(fork):
            try:
                FLAGS.set("paged_kv_cache", True)
                if fork:
                    FLAGS.set("monitor", True)
                model, b = self._model()
                req = _GenRequest([5, 9, 3], 16)
                b._pending_join.append(req)
                b._admit()
                slot = next(i for i, r in enumerate(b._slot_req)
                            if r is req)
                spare = next(i for i, r in enumerate(b._slot_req)
                             if r is None)
                cow0 = monitor.counter(
                    "generation.gendemo.cow_copies_total").value
                for _ in range(4):
                    b._step()
                if fork:
                    model.fork_slot(spare, slot)
                    p = model.session.p
                    scope = model.session.scope
                    shared = p.self_cache.slot_blocks(
                        scope, spare,
                        int(p.self_cache.lengths(scope)[spare]))
                    frozen = np.asarray(scope.find_var(
                        p.self_cache.k_name))[:, shared].copy()
                it = 0
                while not req.event.is_set():
                    b._admit()
                    b._step()
                    it += 1
                    assert it < 200
                cow = monitor.counter(
                    "generation.gendemo.cow_copies_total").value - cow0
                if fork:
                    # the sharer's pool rows survived the divergence
                    after = np.asarray(scope.find_var(
                        p.self_cache.k_name))[:, shared]
                    np.testing.assert_array_equal(after, frozen)
                    assert cow >= 1
                return list(req.tokens)
            finally:
                if fork:
                    FLAGS.reset("monitor")
                FLAGS.reset("paged_kv_cache")

        base = run(fork=False)
        forked = run(fork=True)
        assert forked == base

    def test_telemetry_zero_cost_with_monitor_off(self):
        from paddle_tpu import monitor
        from paddle_tpu.serving.generation import _GenRequest

        assert not FLAGS.monitor
        try:
            FLAGS.set("paged_kv_cache", True)
            _, b = self._model()
            before = set(monitor.default_registry().names())
            reqs = [_GenRequest([5, 9, 3], 8), _GenRequest([5, 9, 3], 8)]
            _drive(b, reqs)
        finally:
            FLAGS.reset("paged_kv_cache")
        created = set(monitor.default_registry().names()) - before
        assert not {n for n in created
                    if "blocks_" in n or "prefix_hits" in n
                    or "cow_copies" in n or "prefills" in n}, created


# ---------------------------------------------------------------------------
# memory planner: the pools are a named kv_cache row
# ---------------------------------------------------------------------------


def test_planner_charges_pools_to_kv_cache_class():
    from paddle_tpu.memory import planner as M

    dims = dict(TINY, batch_size=2, src_seq_len=6, max_out_len=5)
    try:
        FLAGS.set("paged_kv_cache", True)
        p = T.build_generation_programs(kv_cache=True, **dims)
    finally:
        FLAGS.reset("paged_kv_cache")
    plan = M.plan_program(p.decode, [], [])
    kv = plan.class_peaks.get("kv_cache", 0)
    assert kv > 0
    # the row covers both pools' K+V (+ tables/counters via hbm_bytes)
    expect = p.self_cache.hbm_bytes + p.cross_cache.hbm_bytes
    assert abs(kv - expect) <= 0.05 * expect, (kv, expect)
    assert "kv_cache" in plan.table()
