"""Memory-optimization tier (paddle_tpu/memory): the static HBM liveness
planner (hand-computed red-gates, class split, sub-blocks, accumulated /
pipeline-stage variants, XLA memory_analysis agreement), the
activation-recompute pass (loss/grad parity, bit-identical dropout
masks, rng-without-id stash rule, flag-off zero cost, verifier-clean
output, checkpoint interop), and the host-offload pass (value parity,
exact watermark subtraction)."""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, memory
from paddle_tpu.analysis import verify_program
from paddle_tpu.core import framework as fw
from paddle_tpu.flags import FLAGS


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------


def _mlp(dropout=0.3, sizes=(32, 32), feature=8, optimizer="adam"):
    prog, start = pt.Program(), pt.Program()
    with pt.program_guard(prog, start):
        x = layers.data(name="x", shape=[feature], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        h = x
        for i, sz in enumerate(sizes):
            h = layers.fc(h, size=sz, act="tanh",
                          param_attr=pt.ParamAttr(name=f"w{i}"),
                          bias_attr=pt.ParamAttr(name=f"b{i}"))
            if dropout:
                h = layers.dropout(
                    h, dropout_prob=dropout,
                    dropout_implementation="upscale_in_train")
        pred = layers.fc(h, size=1, param_attr=pt.ParamAttr(name="w_out"),
                         bias_attr=pt.ParamAttr(name="b_out"))
        loss = layers.mean(layers.square(pred - y))
        if optimizer == "adam":
            pt.optimizer.AdamOptimizer(learning_rate=0.01).minimize(loss)
        else:
            pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return prog, start, loss


def _tiny_transformer(dropout=0.1, seq=16, n_layer=2):
    from paddle_tpu.models import transformer as T

    prog, start = pt.Program(), pt.Program()
    with pt.program_guard(prog, start), fw.guard_unique_name():
        avg_cost, _, feeds = T.transformer(
            src_vocab_size=128, trg_vocab_size=128, max_length=32,
            n_layer=n_layer, n_head=4, d_key=16, d_value=16, d_model=64,
            d_inner_hid=128, dropout_rate=dropout, src_seq_len=seq,
            trg_seq_len=seq, use_flash=False)
        pt.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
    return prog, start, avg_cost.name, list(feeds)


def _transformer_feed(k, mbs, seq=16):
    from paddle_tpu.models import transformer as T

    batches = [T.make_batch(mbs, seq, seq, 4, 128, 128,
                            rng=np.random.RandomState(s))
               for s in range(k)]
    return {n: np.stack([b[n] for b in batches]) for n in batches[0]}


def _run_pair(prog_a, prog_b, start, loss_name, feed, steps=3,
              runner=None):
    """Run both programs from IDENTICAL param init; returns (losses_a,
    losses_b, params_a, params_b)."""
    pnames = [p.name for p in prog_a.all_parameters()]

    def one(prog):
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(start, scope=scope)
        if one.init is None:
            one.init = {n: np.asarray(scope.find_var(n)).copy()
                        for n in pnames}
        else:
            for n, v in one.init.items():
                scope.set_var(n, v)
        losses = []
        for _ in range(steps):
            if runner is None:
                out = exe.run(prog, feed=feed, fetch_list=[loss_name],
                              scope=scope)
            else:
                out = runner(exe, prog, scope)
            losses.append(np.asarray(out[0]))
        return losses, {n: np.asarray(scope.find_var(n)) for n in pnames}

    one.init = None
    la, pa = one(prog_a)
    lb, pb = one(prog_b)
    return la, lb, pa, pb


# ---------------------------------------------------------------------------
# planner red-gates
# ---------------------------------------------------------------------------


def _fabricate_chain():
    """square-op chain with fully known shapes: a[4,8] -> b -> c -> d,
    every var 4*8*4 = 128 bytes.  Liveness by hand: feed a dies after
    op0, b after op1, c after op2; d is the fetch.  The sweep's live set
    is 256 bytes at every op — the hand-computed peak."""
    prog = pt.Program()
    blk = prog.global_block()
    blk.create_var(name="a", shape=[4, 8], dtype="float32", is_data=True)
    for n in ("b", "c", "d"):
        blk.create_var(name=n, shape=[4, 8], dtype="float32")
    blk.append_op("square", {"X": ["a"]}, {"Out": ["b"]})
    blk.append_op("square", {"X": ["b"]}, {"Out": ["c"]})
    blk.append_op("square", {"X": ["c"]}, {"Out": ["d"]})
    return prog


class TestPlanner:
    def test_hand_computed_peak(self):
        plan = memory.plan_program(_fabricate_chain(), ["a"], ["d"])
        assert plan.peak_bytes == 256
        assert plan.warnings == []
        # lifetimes table is exact
        assert plan.lifetimes["a"].last_use == 0
        assert plan.lifetimes["b"].last_use == 1
        assert plan.lifetimes["d"].last_use == 2
        # b, c, d are forward products = activations; a is the feed
        assert plan.lifetimes["b"].klass == "activations"
        assert plan.lifetimes["a"].klass == "feeds"

    def test_unknown_shape_degrades_to_named_warning(self):
        prog = pt.Program()
        blk = prog.global_block()
        blk.create_var(name="a", shape=[4, 8], dtype="float32",
                       is_data=True)
        blk.create_var(name="b")
        blk.create_var(name="c", shape=[4, 8], dtype="float32")
        blk.append_op("square", {"X": ["a"]}, {"Out": ["b"]})
        blk.append_op("square", {"X": ["b"]}, {"Out": ["c"]})
        blk.vars["b"].shape = None  # stale/undeclared IR shape
        plan = memory.plan_program(prog, ["a"], ["c"])
        assert any(w["var"] == "b" and w["check"] == "unknown-shape"
                   for w in plan.warnings)
        # degraded to 0 bytes, never a fabricated number
        assert plan.lifetimes["b"].bytes == 0
        # a (128 B) dies after op0 and b contributes 0: both op live
        # sets hold exactly one known 128 B var
        assert plan.peak_bytes == 128

    def test_batch_substitution(self):
        prog = pt.Program()
        blk = prog.global_block()
        blk.create_var(name="a", shape=[-1, 8], dtype="float32",
                       is_data=True)
        blk.create_var(name="b", shape=[-1, 8], dtype="float32")
        blk.append_op("square", {"X": ["a"]}, {"Out": ["b"]})
        plan = memory.plan_program(prog, ["a"], ["b"], batch_size=16)
        assert plan.lifetimes["b"].bytes == 16 * 8 * 4
        assert plan.warnings == []
        plan0 = memory.plan_program(prog, ["a"], ["b"])
        assert plan0.lifetimes["b"].bytes == 0
        assert any(w["check"] == "dynamic-dim" for w in plan0.warnings)

    def test_class_split_on_trained_mlp(self):
        prog, _, loss = _mlp(dropout=0.0)
        plan = memory.plan_program(prog, ["x", "y"], [loss.name],
                                   batch_size=16)
        assert plan.class_peaks["params"] > 0
        assert plan.class_peaks["opt_state"] > 0       # adam moments
        assert plan.class_peaks["activations"] > 0
        assert plan.class_peaks["workspace"] > 0       # grads
        assert plan.peak_bytes >= plan.class_peaks["params"]
        # the fwd->bwd gap signal exists for a stashed activation
        gaps = [lf.fwd_bwd_gap for lf in plan.lifetimes.values()
                if lf.klass == "activations"]
        assert max(gaps) > 0

    def test_sub_block_peak_charged_at_parent(self):
        # fabricated op types (no registered infer) keep the declared
        # shapes authoritative — the planner is registry-independent
        prog = pt.Program()
        blk = prog.global_block()
        blk.create_var(name="a", shape=[4, 8], dtype="float32",
                       is_data=True)
        blk.create_var(name="out", shape=[4, 8], dtype="float32")
        sub = prog._create_block()
        sub.create_var(name="i1", shape=[16, 16], dtype="float32")
        sub.create_var(name="i2", shape=[16, 16], dtype="float32")
        sub.append_op("fab_body_op", {"X": ["a"]}, {"Out": ["i1"]})
        sub.append_op("fab_body_op", {"X": ["i1"]}, {"Out": ["i2"]})
        prog.current_block_idx = 0
        blk.append_op("while", {"X": ["a"]}, {"Out": ["out"]},
                      attrs={"sub_block": sub})
        plan = memory.plan_program(prog, ["a"], ["out"])
        # 128 (a) + 128 (out) + 2048 (interior body transient: i1 + i2
        # both live at the body's second op)
        assert plan.peak_bytes == 128 + 128 + 2 * 16 * 16 * 4

    def test_plan_accumulated_scales_feed_stack(self):
        prog, _, loss = _mlp(dropout=0.0)
        r1 = memory.plan_accumulated(prog, ["x", "y"], [loss.name],
                                     accumulate_steps=1, batch_size=8)
        r4 = memory.plan_accumulated(prog, ["x", "y"], [loss.name],
                                     accumulate_steps=4, batch_size=8)
        assert r4["grad_sum_bytes"] == r1["grad_sum_bytes"] > 0
        assert r4["feed_stack_bytes"] == 4 * r1["feed_stack_bytes"]
        assert r4["peak_bytes"] > r1["peak_bytes"]

    def test_plan_stages_stash_and_inflight(self):
        from paddle_tpu.parallel.pipeline import split_program

        prog, _, loss = _mlp(dropout=0.0, sizes=(16, 16))
        stages = split_program(prog, ["x", "y"], n_stages=2)
        rows = memory.plan_stages(stages, schedule="1f1b",
                                  micro_batches=8, batch_size=8)
        assert len(rows) == 2
        assert all(r["in_flight"] == 2 for r in rows)  # min(K, S)
        grows = memory.plan_stages(stages, schedule="gpipe",
                                   micro_batches=8, batch_size=8)
        assert all(r["in_flight"] == 8 for r in grows)
        # some stage stashes fwd state for its own bwd
        assert any(r["stash_bytes"] > 0 for r in rows)
        assert all(r["peak_bytes"] > 0 for r in rows)

    def test_activation_cost_split_balances(self):
        from paddle_tpu.parallel.pipeline import split_program

        prog, _, loss = _mlp(dropout=0.0, sizes=(16, 16, 16))
        stages = split_program(prog.clone(), ["x", "y"], n_stages=2,
                               cost="activations")
        assert stages.n_stages == 2
        assert all(st.fwd_idx for st in stages)

    def test_agreement_mnist(self):
        """Estimator vs compiled.memory_analysis() ground truth on the
        mnist train step (CPU): within the STATED tolerance factor."""
        from paddle_tpu.models import mnist as M

        prog, start = pt.Program(), pt.Program()
        with pt.program_guard(prog, start):
            img, label, avg_cost, acc, _ = M.build_train_net()
            pt.optimizer.SGD(learning_rate=0.01).minimize(avg_cost)
        bs = 32
        plan = memory.plan_program(prog, ["pixel", "label"],
                                   [avg_cost.name], batch_size=bs)
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(start, scope=scope)
        rng = np.random.RandomState(0)
        feed = {"pixel": rng.rand(bs, 1, 28, 28).astype("float32"),
                "label": rng.randint(0, 10, (bs, 1)).astype("int64")}
        stats = memory.xla_cross_check(plan, exe, prog, feed,
                                       [avg_cost.name], scope)
        ratio = plan.peak_bytes / stats["peak_bytes"]
        assert 1.0 / memory.PLANNER_XLA_TOLERANCE <= ratio \
            <= memory.PLANNER_XLA_TOLERANCE, (plan.peak_bytes, stats)
        # the delta rides the plan artifact
        assert plan.to_dict()["xla_ratio"] == round(ratio, 3)

    @pytest.mark.slow
    @pytest.mark.parametrize("model", ["transformer", "bert"])
    def test_agreement_base_widths(self, model):
        """The CI agreement gate at transformer-base / bert-base widths
        (short seq + small batch keep the CPU compile tractable — the
        run_ci pipeline-leg convention)."""
        prog, start = pt.Program(), pt.Program()
        bs = 2
        if model == "transformer":
            from paddle_tpu.models import transformer as T

            with pt.program_guard(prog, start), fw.guard_unique_name():
                avg, _, feeds = T.transformer(
                    src_vocab_size=2048, trg_vocab_size=2048,
                    max_length=32, n_layer=6, n_head=8, d_key=64,
                    d_value=64, d_model=512, d_inner_hid=2048,
                    dropout_rate=0.1, src_seq_len=32, trg_seq_len=32,
                    use_flash=False)
                pt.optimizer.Adam(learning_rate=1e-4).minimize(avg)
            feed = T.make_batch(bs, 32, 32, 8, 2048, 2048,
                                rng=np.random.RandomState(0))
            loss_name = avg.name
        else:
            from paddle_tpu.models import bert as B

            with pt.program_guard(prog, start), fw.guard_unique_name():
                avg, _ = B.build_pretrain_net(
                    vocab_size=4096, seq_len=32, n_layer=12, n_head=12,
                    d_model=768, d_ff=3072, dropout_rate=0.1,
                    use_flash=False)
            batch = B.make_batch(bs, 32, 4096,
                                 rng=np.random.RandomState(0))
            feed = batch
            feeds = sorted(batch)
            loss_name = avg.name
        plan = memory.plan_program(prog, sorted(feed), [loss_name],
                                   batch_size=bs)
        assert plan.warnings == []
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(start, scope=scope)
        stats = memory.xla_cross_check(plan, exe, prog, feed,
                                       [loss_name], scope)
        ratio = plan.peak_bytes / stats["peak_bytes"]
        assert 1.0 / memory.PLANNER_XLA_TOLERANCE <= ratio \
            <= memory.PLANNER_XLA_TOLERANCE, (model, plan.peak_bytes,
                                              stats)


# ---------------------------------------------------------------------------
# recompute pass
# ---------------------------------------------------------------------------


class TestRecompute:
    def test_flag_off_zero_cost(self):
        prog, _, loss = _mlp()
        fp = prog.fingerprint()
        assert FLAGS.recompute == ""
        assert memory.maybe_optimize_memory(
            prog, ["x", "y"], [loss.name]) is None
        assert prog.fingerprint() == fp  # byte-identical

    @pytest.mark.parametrize("draw", [0, 2, 20])
    @pytest.mark.parametrize("optimizer,atol", [("sgd", 1e-7),
                                                ("adam", 1e-5)])
    def test_mlp_parity_and_peak(self, optimizer, atol, draw, monkeypatch):
        # the dropout masks follow the process-wide rng-id counter, which
        # other tests of the process move: three stated draws, 0 and 20
        # the ones where Adam's tolerance below is needed
        monkeypatch.setattr(fw, "_rng_id_counter", [draw])
        prog, start, loss = _mlp(dropout=0.3, optimizer=optimizer)
        prog2 = prog.clone()
        rep = memory.apply_recompute(prog2, ["x", "y"],
                                     fetch_names=[loss.name],
                                     batch_size=16)
        assert rep["cloned_ops"] > 0
        assert rep["activation_peak_after"] < rep["activation_peak_before"]
        rng = np.random.RandomState(0)
        feed = {"x": rng.randn(16, 8).astype("float32"),
                "y": rng.randn(16, 1).astype("float32")}
        la, lb, pa, pb = _run_pair(prog, prog2, start, loss.name, feed)
        # forward MATH is untouched, but the rewritten program is a
        # separately compiled XLA module: a reduce may re-round its last
        # bit (the PR-12 class) — losses agree to 1 ulp.  SGD's update is
        # linear in the gradient and holds the params to a TIGHT
        # tolerance on every draw (3e-8 .. 4.5e-8).  Adam's m / (sqrt(v) +
        # eps) turns the last bit of a gradient near eps (a weight under
        # dropped units) into a share of a whole step: 4.1e-6 and 3.0e-7
        # on one or two of 1,024 elements on draws 0 and 20, 6e-8 on 2.
        # Its tolerance is a thousandth of a step (lr 0.01); a wrong
        # mask moves every live weight by a step
        for a, b in zip(la, lb):
            np.testing.assert_allclose(a, b, rtol=1e-6)
        for n in pa:
            np.testing.assert_allclose(pa[n], pb[n], rtol=1e-6, atol=atol)

    def test_dropout_mask_bit_identical(self):
        """A recomputed segment containing dropout regenerates the SAME
        mask: the renamed recomputed value equals the stashed original
        bitwise in one run (the static rng_id replays the step key)."""
        prog, start, loss = _mlp(dropout=0.4)
        prog2 = prog.clone()
        memory.apply_recompute(prog2, ["x", "y"], fetch_names=[loss.name],
                               batch_size=16)
        blk = prog2.global_block()
        rc = sorted(n for n in blk.vars
                    if n.startswith("dropout_") and "@RC" in n
                    and not n.endswith(".tmp_1"))
        assert rc, "no recomputed dropout output — segment missed dropout"
        orig = rc[0].split("@RC")[0]
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(start, scope=scope)
        rng = np.random.RandomState(0)
        feed = {"x": rng.randn(16, 8).astype("float32"),
                "y": rng.randn(16, 1).astype("float32")}
        a, b = exe.run(prog2, feed=feed, fetch_list=[orig, rc[0]],
                       scope=scope)
        assert np.array_equal(a, b)
        assert np.any(a == 0.0)  # dropout actually dropped something

    def test_rng_without_static_id_stays_stashed(self):
        """An RNG op with no rng_id/seed cannot replay deterministically:
        the pass must stash its output, not clone a DIFFERENT mask."""
        prog, start = pt.Program(), pt.Program()
        with pt.program_guard(prog, start):
            x = layers.data(name="x", shape=[8], dtype="float32")
            h = layers.fc(x, size=8, act="tanh",
                          param_attr=pt.ParamAttr(name="w0"),
                          bias_attr=pt.ParamAttr(name="b0"))
            u = layers.ops.uniform_random([16, 8])
            h2 = h * u
            h3 = layers.fc(h2, size=8, act="tanh",
                           param_attr=pt.ParamAttr(name="w1"),
                           bias_attr=pt.ParamAttr(name="b1"))
            loss = layers.mean(layers.square(h3))
            pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        u_name = u.name
        ck = [op.output("Out")[0] for op in prog.global_block().ops
              if op.type == "tanh"]
        memory.apply_recompute(prog, ["x"], checkpoints=ck[:1],
                               fetch_names=[loss.name], batch_size=16)
        blk = prog.global_block()
        # no clone of the uniform_random, no rename of its output
        assert not any(op.type == "uniform_random"
                       and op.attr("recompute_segment") is not None
                       for op in blk.ops)
        assert u_name + "@RC1" not in blk.vars
        # its backward reader still reads the stashed original
        readers = [op for op in blk.ops
                   if u_name in op.input_arg_names()
                   and op.type.endswith("_grad")]
        assert readers
        # and the rewritten program still runs
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(start, scope=scope)
        exe.run(prog, feed={"x": np.ones((16, 8), np.float32)},
                fetch_list=[loss.name], scope=scope)

    def test_verifier_clean_and_checkpoint_interop(self):
        prog, start, loss = _mlp(dropout=0.3)
        names_before = sorted(p.name for p in prog.all_parameters())
        prog2 = prog.clone()
        memory.apply_recompute(prog2, ["x", "y"], fetch_names=[loss.name],
                               batch_size=16)
        findings = verify_program(prog2, feed_names=["x", "y"],
                                  fetch_names=[loss.name],
                                  check_dead=True)
        assert findings == [], [str(f) for f in findings]
        # checkpoint-v2 interop: param names unchanged across the flag,
        # so a scope saved under either program loads into the other
        assert sorted(p.name for p in prog2.all_parameters()) \
            == names_before

    def test_checkpoint_v2_roundtrip_across_flag(self, tmp_path):
        prog, start, loss = _mlp(dropout=0.0, sizes=(16,))
        prog2 = prog.clone()
        memory.apply_recompute(prog2, ["x", "y"], fetch_names=[loss.name],
                               batch_size=8)
        feed = {"x": np.ones((8, 8), np.float32),
                "y": np.ones((8, 1), np.float32)}
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(start, scope=scope)
        exe.run(prog2, feed=feed, fetch_list=[loss.name], scope=scope)
        pt.io.save_persistables(exe, str(tmp_path), main_program=prog2,
                                scope=scope)
        # load the rewritten program's checkpoint under the PLAIN program
        scope2, exe2 = pt.Scope(), pt.Executor()
        exe2.run(start, scope=scope2)
        pt.io.load_persistables(exe2, str(tmp_path), main_program=prog,
                                scope=scope2)
        for p in prog.all_parameters():
            np.testing.assert_array_equal(
                np.asarray(scope.find_var(p.name)),
                np.asarray(scope2.find_var(p.name)))

    @pytest.mark.slow
    def test_tiny_transformer_reduction_and_parity(self):
        prog, start, loss_name, feeds = _tiny_transformer()
        pt.amp.enable(prog)
        prog2 = prog.clone()
        prog2._amp_bf16 = True
        rep = memory.apply_recompute(prog2, feeds,
                                     fetch_names=[loss_name],
                                     batch_size=4)
        before, after = (rep["activation_peak_before"],
                         rep["activation_peak_after"])
        assert 1.0 - after / before >= 0.40, (before, after)
        assert rep["flops_ratio"] <= 1.35
        findings = verify_program(prog2, feed_names=feeds,
                                  fetch_names=[loss_name],
                                  check_dead=True)
        assert findings == [], [str(f) for f in findings]
        # run_accumulated compose: K=2 micro-batches, dropout + amp on —
        # training state parity at tight tolerance
        feed = _transformer_feed(2, 2)

        def runner(exe, prog_, scope):
            return exe.run_accumulated(prog_, feed=feed,
                                       fetch_list=[loss_name],
                                       scope=scope)

        la, lb, pa, pb = _run_pair(prog, prog2, start, loss_name, feed,
                                   steps=2, runner=runner)
        for n in pa:
            np.testing.assert_allclose(
                pa[n].astype(np.float32), pb[n].astype(np.float32),
                rtol=2e-6, atol=1e-7)

    def test_composes_with_pipeline_stage(self):
        """Recompute within a stage: the pass applied to a split_program
        stage program emits verifier-clean IR."""
        from paddle_tpu.parallel.pipeline import split_program

        prog, start, loss = _mlp(dropout=0.0, sizes=(16, 16, 16))
        stages = split_program(prog, ["x", "y"], n_stages=2)
        st = stages.stages[0]
        feedish = (st.feeds + [n for n, _, _ in st.fwd_inputs]
                   + [n for n, _, _ in st.bwd_inputs] + st.bwd_feeds)
        fetch = ([n for n, _, _ in st.fwd_outputs]
                 + [n for n, _, _ in st.bwd_outputs])
        rep = memory.apply_recompute(st.program, feedish,
                                     fetch_names=fetch, batch_size=8)
        findings = verify_program(st.program, feed_names=feedish,
                                  fetch_names=fetch)
        assert [f for f in findings if f.severity == "error"] == []

    @pytest.mark.slow
    def test_transformer_base_reduction_bar(self):
        """ISSUE 15 acceptance: >= 40% estimated activation-peak
        reduction at <= 1.35x estimated FLOPs on transformer-base widths
        (IR-only — no compile)."""
        from paddle_tpu.models import transformer as T

        prog, start = pt.Program(), pt.Program()
        with pt.program_guard(prog, start), fw.guard_unique_name():
            avg, _, feeds = T.transformer(
                src_vocab_size=2048, trg_vocab_size=2048, max_length=64,
                n_layer=6, n_head=8, d_key=64, d_value=64, d_model=512,
                d_inner_hid=2048, dropout_rate=0.1, src_seq_len=64,
                trg_seq_len=64, use_flash=False)
            pt.optimizer.Adam(learning_rate=1e-4).minimize(avg)
        rep = memory.apply_recompute(prog, feeds, fetch_names=[avg.name],
                                     batch_size=8)
        reduction = 1.0 - (rep["activation_peak_after"]
                           / rep["activation_peak_before"])
        assert reduction >= 0.40, reduction
        assert rep["flops_ratio"] <= 1.35, rep["flops_ratio"]

    def test_rejects_control_flow_and_forward_only(self):
        prog = pt.Program()
        blk = prog.global_block()
        blk.create_var(name="a", shape=[4], dtype="float32", is_data=True)
        blk.create_var(name="b", shape=[4], dtype="float32")
        blk.append_op("square", {"X": ["a"]}, {"Out": ["b"]})
        with pytest.raises(memory.RecomputeError, match="no Backward"):
            memory.apply_recompute(prog, ["a"], fetch_names=["b"])
        sub = prog._create_block()
        prog.current_block_idx = 0
        blk.append_op("while", {"X": ["b"]}, {"Out": ["b"]},
                      attrs={"sub_block": sub})
        with pytest.raises(memory.RecomputeError, match="sub-block"):
            memory.apply_recompute(prog, ["a"], fetch_names=["b"])

    def test_unknown_checkpoint_raises(self):
        prog, _, loss = _mlp()
        with pytest.raises(memory.RecomputeError, match="nope"):
            memory.apply_recompute(prog, ["x", "y"], checkpoints=["nope"],
                                   fetch_names=[loss.name])


# ---------------------------------------------------------------------------
# offload pass
# ---------------------------------------------------------------------------


def _fabricate_gap_program():
    """A = square(feed) [4096 B, read only by the trailing Backward-role
    op] rides across a gap whose middle op is the watermark (B and C are
    16 KB each, so the gap dominates both before AND after the rewrite);
    offloading A must subtract its 4096 bytes from the peak exactly."""
    prog = pt.Program()
    blk = prog.global_block()
    blk.create_var(name="f", shape=[8, 8], dtype="float32", is_data=True)
    blk.create_var(name="A", shape=[32, 32], dtype="float32")   # 4096 B
    blk.create_var(name="B", shape=[64, 64], dtype="float32")   # 16384 B
    blk.create_var(name="C", shape=[64, 64], dtype="float32")
    blk.create_var(name="D", shape=[8, 8], dtype="float32")
    # fabricated op types: no registered infer, so the declared shapes
    # above stay authoritative (the planner is registry-independent)
    blk.append_op("fab_stash_op", {"X": ["f"]}, {"Out": ["A"]})
    blk.append_op("fab_gap_op", {"X": ["f"]}, {"Out": ["B"]})
    blk.append_op("fab_gap_op", {"X": ["B"]}, {"Out": ["C"]})
    blk.append_op("fab_gap_op", {"X": ["A"]}, {"Out": ["D"]},
                  attrs={fw.OpRole.ROLE_ATTR_NAME: fw.OpRole.Backward})
    return prog


class TestOffload:
    def test_exact_watermark_subtraction(self):
        prog = _fabricate_gap_program()
        before = memory.plan_program(prog, ["f"], ["C", "D"])
        # watermark: op2 holds A(4096) + B(16384) + C(16384); the feed
        # died after op1
        assert before.peak_bytes == 4096 + 16384 + 16384
        rep = memory.apply_offload(prog, ["f"], offload_vars=["A"],
                                   fetch_names=["C", "D"])
        assert rep["offloaded"] == ["A"]
        assert rep["offloaded_bytes"] == 4096
        # A is parked in host memory across the gap: the device
        # watermark subtracts exactly its bytes
        assert rep["peak_after"] == before.peak_bytes - 4096
        after = rep["plan_after"]
        assert after.lifetimes["A@HOST"].klass == "host"
        assert after.offloaded_bytes == 4096

    def test_value_parity_and_planner_peak(self):
        prog, start, loss = _mlp(dropout=0.0, sizes=(32, 32))
        prog2 = prog.clone()
        plan = memory.plan_program(prog2, ["x", "y"], [loss.name],
                                   batch_size=32)
        cands = memory.select_offload_vars(plan, min_bytes=1,
                                           min_gap_frac=0.1)
        assert cands
        rep = memory.apply_offload(prog2, ["x", "y"], offload_vars=cands,
                                   fetch_names=[loss.name], batch_size=32)
        assert rep["offloaded_bytes"] > 0
        assert rep["peak_after"] < rep["peak_before"]
        findings = verify_program(prog2, feed_names=["x", "y"],
                                  fetch_names=[loss.name],
                                  check_dead=True)
        assert findings == [], [str(f) for f in findings]
        rng = np.random.RandomState(0)
        feed = {"x": rng.randn(32, 8).astype("float32"),
                "y": rng.randn(32, 1).astype("float32")}
        la, lb, pa, pb = _run_pair(prog, prog2, start, loss.name, feed)
        for a, b in zip(la, lb):
            assert np.array_equal(a, b)  # identity memcpys: exact
        for n in pa:
            np.testing.assert_array_equal(pa[n], pb[n])

    def test_flag_gated_entry_point(self):
        prog, start, loss = _mlp(dropout=0.3, sizes=(32,))
        FLAGS.offload_activations = True
        FLAGS.recompute = "auto"
        try:
            rep = memory.maybe_optimize_memory(prog, ["x", "y"],
                                               [loss.name])
        finally:
            FLAGS.reset("offload_activations")
            FLAGS.reset("recompute")
        assert rep is not None
        assert rep["recompute"]["cloned_ops"] >= 0
        assert "offload" in rep
        # the combined rewrite still runs
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(start, scope=scope)
        out = exe.run(prog, feed={"x": np.ones((8, 8), np.float32),
                                  "y": np.ones((8, 1), np.float32)},
                      fetch_list=[loss.name], scope=scope)
        assert np.isfinite(np.asarray(out[0])).all()


def test_trace_report_renders_memory_section():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(__file__), "..",
                                     "tools", "trace_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    doc = {"traceEvents": [], "flight": {"header": {}, "events": [
        {"kind": "memory.plan", "name": "bench", "peak_bytes": 12e6,
         "peak_op_index": 42, "peak_op_type": "mul_grad",
         "activation_peak_bytes": 6e6, "offloaded_bytes": 1e6,
         "peak_by_class": {"params": 2e6, "opt_state": 3e6,
                           "activations": 6e6, "workspace": 1e6,
                           "feeds": 0},
         "warnings": 0},
    ]}}
    text = tr.report(doc)
    assert "Memory (planner table" in text
    assert "mul_grad" in text
    assert "activations 6.00 MB" in text


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------


class TestTelemetry:
    def test_publish_plan_zero_cost_off(self):
        import paddle_tpu.monitor as monitor
        from paddle_tpu.monitor import flight

        prog, _, loss = _mlp(dropout=0.0, sizes=(16,))
        plan = memory.plan_program(prog, ["x", "y"], [loss.name],
                                   batch_size=8)
        # force the flag OFF for the zero-cost probe (another test in
        # the session may have flipped the process-global default)
        prev = FLAGS.monitor
        FLAGS.monitor = False
        try:
            before = monitor.default_registry().get(
                "memory.activation_peak_bytes")
            val_before = before.value if before is not None else None
            n_ev = len([e for e in flight.default_recorder().events()
                        if e.get("kind") == "memory.plan"])
            memory.publish_plan(plan)  # one enabled() read, no writes
            after = monitor.default_registry().get(
                "memory.activation_peak_bytes")
            assert (after.value if after is not None else None) \
                == val_before
            assert len([e for e in flight.default_recorder().events()
                        if e.get("kind") == "memory.plan"]) == n_ev
        finally:
            FLAGS.monitor = prev

    def test_publish_plan_gauges_and_flight(self):
        import paddle_tpu.monitor as monitor
        from paddle_tpu.monitor import flight

        prog, _, loss = _mlp(dropout=0.0, sizes=(16,))
        plan = memory.plan_program(prog, ["x", "y"], [loss.name],
                                   batch_size=8)
        prev = FLAGS.monitor
        FLAGS.monitor = True
        try:
            memory.publish_plan(plan, name="test")
            g = monitor.gauge("memory.activation_peak_bytes")
            assert g.value == plan.activation_peak_bytes
            evs = [e for e in flight.default_recorder().events()
                   if e.get("kind") == "memory.plan"
                   and e.get("name") == "test"]
            assert evs
            assert evs[-1]["peak_bytes"] == plan.peak_bytes
        finally:
            FLAGS.monitor = prev
