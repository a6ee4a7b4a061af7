"""The `sdar_30b_a3b_ep8` files on the CPU: the reference against the
program at a tiny preset in float32, the FLOPs, pairs and bytes functions
against hand reckoning, the six readers on hand-made contexts (and on a
parent commit's, which records nothing), the configuration file against
the published config, the two new traffic files, and what a stated
`weights_seed`, a stated `data_seed` and a longer `feed_pool` change and
leave (PR 34)."""

import os

import pytest

import registry
import traffic_gen

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "sdar_30b_a3b_ep8_train"

#: config.json of JetLM/SDAR-30B-A3B-Chat, all but the three keys cut
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
}


def tiny_cell(amp=False, router_trained=True, pinned=True):
    cfg = registry.load_json(os.path.join(HERE, "data/sdar_tiny.json"))
    cfg["amp"] = amp
    cfg["router_trained"] = router_trained
    if not pinned:
        del cfg["weights_seed"]
    return registry.Cell(
        "sdar_tiny", 1, cfg,
        registry.load_json(os.path.join(HERE, "data/train_tiny_sdar.json")),
        {})


@pytest.mark.parametrize("router_trained, pinned",
                         [(True, False), (False, True)])
def test_reference_follows_the_program_in_float32(router_trained, pinned):
    cell = tiny_cell(router_trained=router_trained, pinned=pinned)
    train = registry.load_driver("train")
    tc = train.TrainCell(cell)
    drawn, init = [], tc._init

    def spy(key):
        drawn.append(tc.jax.random.key_data(key).tolist())
        return init(key)

    tc._init = spy
    # 2 layers' routers are trained leaves, or none is
    assert sum("router_w" in n for n in tc.trainable) == (
        2 if router_trained else 0)
    seed = 2 ** 31 + 11
    feeds = traffic_gen.train_feeds(cell.traffic, cell.cfg, seed)
    assert {k: v.shape for k, v in feeds[0].items()} == {
        k: (4, 4, 32, 1) for k in ("ids", "noise", "loss_weight")}
    assert feeds[0]["ids"].max() < cell.cfg["mask_token_id"]
    obs = tc.first_calls(seed, feeds)
    ref = tc.reference(seed, feeds)
    numbers, where = train.numbers_of(obs, ref)
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["loss0_gap"] < 1e-5, numbers
    assert numbers["grad_diff"] < 1e-3, (numbers, where)
    assert numbers["frozen_moved"] == 0.0, numbers
    assert numbers["grad_gap"] < 1e-4, (numbers, where)
    assert numbers["step_gap"] < 1e-3, (numbers, where)
    # program and reference were handed the same weights: every draw, the
    # program's resets' and the reference's, came from one key, the stated
    # seed's where the configuration states one and the run's where not
    want = tc.blocks.seed_key(cell.cfg["weights_seed"] if pinned else seed)
    assert len(drawn) >= 4
    assert all(k == tc.jax.random.key_data(want).tolist() for k in drawn)


@pytest.mark.parametrize("pinned", [True, False])
def test_a_stated_weights_seed_gives_every_run_the_same_weights(pinned):
    """With the key two `--seed`s start from equal parameters and train on
    different feeds; without it they differ in both, as on the parent."""
    cell = tiny_cell(pinned=pinned)
    train = registry.load_driver("train")
    tc = train.TrainCell(cell)
    a, b = (tc.init_params(s) for s in (5, 2 ** 31 + 6))
    random = [k for k in a if a[k].ndim > 1]  # norm scales start at one
    assert len(random) > 8
    assert {bool((a[k] == b[k]).all()) for k in random} == {pinned}
    assert train.weights_seed(cell.cfg, 5) == (3400001 if pinned else 5)
    fa, fb = (traffic_gen.train_feeds(cell.traffic, cell.cfg, s)
              for s in (5, 2 ** 31 + 6))
    assert not (fa[0]["ids"] == fb[0]["ids"]).all()
    assert not (fa[0]["noise"] == fb[0]["noise"]).all()


@pytest.mark.parametrize("stated", [77, None])
def test_run_draws_its_feeds_from_a_stated_data_seed(stated, monkeypatch):
    """`data_seed` in a traffic mix gives every run the same batches; a mix
    without the key draws them from `--seed`, as on the parent."""
    cell = tiny_cell()
    if stated is not None:
        cell.traffic["data_seed"] = stated
    train = registry.load_driver("train")
    assert train.data_seed(cell.traffic, 5) == (stated or 5)
    seen = []

    class Stop(Exception):
        pass

    def record(traffic, cfg, seed):
        seen.append(seed)
        raise Stop

    monkeypatch.setattr(train.traffic_gen, "train_feeds", record)
    with pytest.raises(Stop):
        train.run(cell, 5, 1.0, False, compile_cache=False)
    assert seen == [stated or 5]


@pytest.mark.parametrize("real", [False, True])
def test_a_longer_pool_leaves_a_seeds_first_feeds_as_they_were(real):
    """`train_feeds` draws the feeds from one generator in order: the
    checked calls' feeds, and with them `correct`, do not move with
    `feed_pool` (PR 34 measured a pool of 12 against the 4 that stands)."""
    cell = registry.load_cell(CELL) if real else tiny_cell()
    seed = 2 ** 31 + 12
    four, twelve = (traffic_gen.train_feeds(
        dict(cell.traffic, feed_pool=n), cell.cfg, seed) for n in (4, 12))
    assert (len(four), len(twelve)) == (4, 12)
    for a, b in zip(four, twelve):
        assert sorted(a) == sorted(b)
        assert all((a[k] == b[k]).all() for k in a)
    # every feed of a pool is a batch of its own
    assert len({f["ids"].tobytes() for f in twelve}) == 12


def test_calibrate_free_weights_sets_the_stated_seed_aside():
    import calibrate

    assert "weights_seed" in calibrate.load_cell(CELL, False).cfg
    free = calibrate.load_cell(CELL, True)
    assert "weights_seed" not in free.cfg
    assert registry.load_driver("train").weights_seed(free.cfg, 7) == 7


@pytest.mark.parametrize("workload", [
    "transformer_base_train", "bert_base_train", "bert_base_train_seq512",
    "joyai_flash_ep16_train", CELL])
def test_only_this_cell_states_its_seeds(workload):
    """The other cells' weights and feeds follow `--seed`, as on the
    parent; this one states both and says so among its departures."""
    cell = registry.load_cell(workload)
    train = registry.load_driver("train")
    seed = 2 ** 31 + 5
    got = (train.weights_seed(cell.cfg, seed),
           train.data_seed(cell.traffic, seed))
    if workload == CELL:
        assert got == (cell.cfg["weights_seed"], cell.traffic["data_seed"])
        assert seed not in got
        assert "weights_seed" in cell.cfg["assumed"]
        assert "same_batches_every_run" in cell.cfg["departures"]
    else:
        assert got == (seed, seed)
        assert "weights_seed" not in cell.cfg
        assert "data_seed" not in cell.traffic
    assert cell.traffic["feed_pool"] == 4


def test_half_batch_fault_zeroes_whole_rows():
    cell = tiny_cell()
    train = registry.load_driver("train")
    feed = traffic_gen.train_feeds(cell.traffic, cell.cfg, 5)[0]
    bad = train.half_batch(feed, "loss_weight")
    assert bad["loss_weight"][:, 2:].sum() == 0
    assert (bad["loss_weight"][:, :2] == 1).all()


def test_configuration_keeps_every_published_width():
    cell = registry.load_cell(CELL)
    for key, value in PUBLISHED.items():
        assert cell.cfg[key] == value, key
    assert (cell.cfg["router_experts"], cell.cfg["num_experts"]) == (128, 16)
    assert cell.cfg["vocab_size"] * 8 == 151936
    assert cell.cfg["num_hidden_layers"] == 4
    assert cell.cfg["mask_token_id"] == cell.cfg["vocab_size"] - 1
    entry = [c for c in cell.bench["configs"]
             if c["name"] == "sdar_30b_a3b_ep8"][0]
    assert sorted(entry["reduced"]) == sorted(cell.cfg["reduced"])
    assert "8 chips share each layer" in cell.cfg["deployment"]
    for key in ("block_length", "noise_schedule", "mask_token_id"):
        assert key in cell.cfg["assumed"]
    assert "fixed_noise_level" in cell.cfg["departures"]
    assert traffic_gen.tokens_per_step(cell.traffic, cell.cfg) == 4096


def test_noise_field_is_drawn_at_the_noise_level():
    cell = registry.load_cell(CELL)
    traffic = cell.traffic
    assert traffic["fields"]["noise"]["p"] == traffic["noise_level"] \
        == cell.cfg["noise_level"]
    assert traffic["block_length"] == cell.cfg["block_length"]
    assert traffic["token_high"] == cell.cfg["mask_token_id"]
    tiny = tiny_cell()
    assert tiny.traffic["fields"]["noise"]["p"] == tiny.cfg["noise_level"]
    program = registry.load_module(cell.path(cell.cfg["program"]))
    with pytest.raises(ValueError, match="noise_level"):
        program.build(cell.cfg, dict(traffic, noise_level=0.25))


def test_leaves_add_up_to_the_cut_as_reckoned():
    cell = registry.load_cell(CELL)
    ref = registry.load_module(cell.path(cell.cfg["reference"]))
    size = {}
    for name, shape, _, _ in ref.leaves(cell.cfg, cell.traffic):
        n = 1
        for s in shape:
            n *= s
        size[name] = n
    attention = sum(size[f"layer0.{w}"] for w in (
        "q_w", "k_w", "v_w", "o_w", "q_norm.scale", "k_norm.scale"))
    assert attention == 18_874_624
    layer = sum(v for k, v in size.items() if k.startswith("layer0."))
    assert layer == 94_638_336
    assert size["embed_w"] + size["head_w"] == 77_791_232
    assert sum(size.values()) == 4 * layer + 77_791_232 + 2048
    assert round(sum(size.values()) / 1e6, 2) == 456.35


def test_flops_count_what_this_chip_computes():
    cell = registry.load_cell(CELL)
    f = registry.load_module(cell.path(cell.cfg["flops"]))
    cfg, traffic = cell.cfg, cell.traffic
    assert f.visible_pairs(2048, 4) == 2048 * 4 + 2048 * 2048
    assert f.visible_pairs(2048, 4) / (4096 * 4096) == pytest.approx(
        0.2505, abs=1e-4)
    proj = 2 * 2048 * 4096 + 2 * 2048 * 512
    attn = 32 * 2 * 128 * (2048 + 4) / 2
    expert = 3 * 2048 * 768
    want = 2 * 4 * (proj + attn + 2048 * 128 + expert) + 2048 * 18992
    assert f.forward_macs_per_token(cfg, 2048) == want
    # the cell does not train its routers: their two backward products
    # (dX and dW, 2 positions a token, 4 layers) are not counted
    assert cfg["router_trained"] is False
    assert f.flops_per_token(cfg, traffic) == (
        6 * want - 2 * 2 * 2 * 4 * 2048 * 128)
    assert f.flops_per_token(dict(cfg, router_trained=True),
                             traffic) == 6 * want
    # 7.3 TFLOP a step of 4,096 counted tokens
    assert 7.2e12 < 4096 * f.flops_per_token(cfg, traffic) < 7.4e12
    pairs = 2 * 32 * 4 * (2048 * 4 + 2048 * 2048)
    assert f.attention_flops_per_step(cfg, traffic) == pairs * (
        2 * 256 + 2 * 5 * 128)


def test_gmm_bytes_count_each_held_expert_once_a_pass():
    cell = registry.load_cell(CELL)
    f = registry.load_module(cell.path(cell.cfg["flops"]))
    weights = 4 * 16 * 3 * 2048 * 768
    assert f.gmm_bytes_per_step(cell.cfg, 0) == 3 * 2 * weights
    assert f.gmm_bytes_per_step(cell.cfg, 10) - f.gmm_bytes_per_step(
        cell.cfg, 0) == 3 * 2 * 10 * (2 * 2048 + 3 * 768)
    assert f.gmm_flops_per_step(cell.cfg, 32768) == (
        3 * 2 * 3 * 2048 * 768 * 32768)
    # 512 rows an expert: the multiply-adds are the bound, not the weights
    assert (f.gmm_flops_per_step(cell.cfg, 32768) / 197e12
            > f.gmm_bytes_per_step(cell.cfg, 32768) / 819e9)


NEW_METRICS = ["bd_attn_ms.train", "bd_attn_roofline.train",
               "bd_attn_visited_share.train", "sdar_moe_gmm_ms.train",
               "sdar_moe_gmm_roofline.train", "sdar_moe_imbalance.train"]
#: the attention kernels' readings of T and B, in BERT's phase-2 cell
SEQ512_METRICS = ["seq512_attn_fwd_ms.train", "seq512_attn_bwd_ms.train"]


def _ctx(ops_s, events, phases, monkeypatch):
    import program_spans

    monkeypatch.setattr(program_spans, "traced_calls", lambda ctx: events)
    monkeypatch.setattr(program_spans, "compile_phases", lambda: phases)
    return {"cell": registry.load_cell(CELL),
            "result": {"traced": {"steps": 32, "calls": 4}},
            "device": {"kind": "TPU v5 lite"}, "trace": {"ops_s": ops_s}}


def test_benchmark_lists_the_six_for_the_new_cell_alone():
    cell = registry.load_cell(CELL)
    mine = [m for m in cell.bench["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in mine] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"]
               == "train_tokens_per_s" for m in mine)
    assert [m["name"] for m in cell.bench["per_layer"]][-8:] \
        == NEW_METRICS + SEQ512_METRICS
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW_METRICS) <= reported and "step_mfu.train" in reported
    assert "moe_gmm_ms.train" not in reported
    assert not set(SEQ512_METRICS) & reported
    other = registry.load_cell("bert_base_train_seq512")
    theirs = {m["name"] for m in other.metrics("per_layer")}
    assert not set(NEW_METRICS) & theirs
    assert set(SEQ512_METRICS) <= theirs and "attn_fwd_ms.train" not in theirs


@pytest.mark.parametrize("phases", [None, {"backend_s": 1.0}])
@pytest.mark.parametrize("metric", NEW_METRICS + SEQ512_METRICS)
def test_readers_find_nothing_on_a_program_that_records_nothing(
        metric, phases, monkeypatch):
    ctx = _ctx({"fusion.1 f32[8]": 1.0}, [{"phases": [["feed", 0, 1]]}],
               phases, monkeypatch)
    assert registry.load_reader(metric).read(ctx) is None


def test_readers_on_a_hand_made_trace(monkeypatch):
    ops = {"flash_bhtd_fwd.3 bf16[64,4096,128] mosaic": 0.32,
           "flash_bhtd_bwd_dq.3 bf16[64,4096,128] mosaic": 0.32,
           "flash_bhtd_bwd_dkv.3 bf16[64,4096,128] mosaic": 0.64,
           "moe_gmm_fwd.1 bf16[65536,1536] mosaic": 0.128,
           "moe_gmm_bwd_dx.1 bf16[65536,768] mosaic": 0.128,
           "moe_gmm_bwd_dw.1 bf16[16,2048,1536] mosaic": 0.128,
           "flash_bhtd_fwd.9 bf16[1]": 5.0}  # not a Mosaic call: not read
    events = [{"phases": [], "counters": {"moe_local_pairs": 32000.0,
                                          "moe_max_over_mean": 3.0}},
              {"phases": [], "counters": {"moe_local_pairs": 33536.0,
                                          "moe_max_over_mean": 5.0}}]
    phases = {"attn_tiles_visited": 4 * 24, "attn_tiles_total": 4 * 64}
    ctx = _ctx(ops, events, phases, monkeypatch)
    read = {m: registry.load_reader(m).read(ctx) for m in NEW_METRICS}
    assert read["bd_attn_ms.train"] == pytest.approx(40.0)
    assert read["sdar_moe_gmm_ms.train"] == pytest.approx(12.0)
    assert read["sdar_moe_imbalance.train"] == pytest.approx(4.0)
    assert read["bd_attn_visited_share.train"] == pytest.approx(37.5)
    cell = ctx["cell"]
    f = registry.load_module(cell.path(cell.cfg["flops"]))
    assert read["bd_attn_roofline.train"] == pytest.approx(
        100 * f.attention_flops_per_step(cell.cfg, cell.traffic)
        / (0.04 * 197e12))
    assert read["sdar_moe_gmm_roofline.train"] == pytest.approx(
        100 * f.gmm_flops_per_step(cell.cfg, 32768) / 197e12 / 0.012)
    assert 0 < read["bd_attn_roofline.train"] < 100
    assert 0 < read["sdar_moe_gmm_roofline.train"] < 100


def test_seq512_readers_split_the_bthd_kernels_by_direction(monkeypatch):
    ops = {"flash_bthd_fwd.2 bf16[32,512,12,64] mosaic": 0.128,
           "flash_bthd_bwd_dq.2 bf16[32,512,12,64] mosaic": 0.096,
           "flash_bthd_bwd_dkv.2 bf16[32,512,12,64] mosaic": 0.16,
           "fusion.7_fwd bf16[1]": 5.0}  # not a Mosaic call: not read
    ctx = _ctx(ops, [], None, monkeypatch)
    ctx["cell"] = registry.load_cell("bert_base_train_seq512")
    fwd, bwd = (registry.load_reader(m).read(ctx) for m in SEQ512_METRICS)
    assert fwd == pytest.approx(4.0) and bwd == pytest.approx(8.0)


def test_bert_phase_two_cell_is_bert_base_at_another_shape():
    cell = registry.load_cell("bert_base_train_seq512")
    base = registry.load_cell("bert_base_train")
    assert cell.cfg == base.cfg
    want = dict(base.traffic, batch=32, seq_len=512,
                reference_rows_per_block=8)
    assert cell.traffic == want
    assert traffic_gen.tokens_per_step(cell.traffic, cell.cfg) == 16384
    assert cell.traffic["batch"] % cell.traffic[
        "reference_rows_per_block"] == 0
