"""The `lfm2_8b_a1b_ep4` files on the CPU: the reference against the
program at a tiny preset in float32, the configuration file against the
published config, the leaves against the reckoned cut, what the stated
seeds pin, the FLOPs and bytes functions against hand reckoning, and the
eight readers on hand-made contexts (and on a parent commit's, which
records nothing)."""

import os

import pytest

import registry
import traffic_gen

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "lfm2_8b_a1b_ep4_train"

#: config.json of LiquidAI/LFM2-8B-A1B as the catalog has it, all but the
#: three keys cut
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention", "conv",
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts_per_tok": 4,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True,
}


def tiny_cell(amp=False, router_trained=True):
    cfg = registry.load_json(os.path.join(HERE, "data/lfm2_tiny.json"))
    cfg["amp"] = amp
    cfg["router_trained"] = router_trained
    return registry.Cell(
        "lfm2_tiny", 1, cfg,
        registry.load_json(os.path.join(HERE, "data/train_tiny_lfm2.json")),
        {})


@pytest.mark.parametrize("router_trained", [True, False])
def test_reference_follows_the_program_in_float32(router_trained):
    cell = tiny_cell(router_trained=router_trained)
    train = registry.load_driver("train")
    tc = train.TrainCell(cell)
    # 3 expert layers' routers are trained leaves, or none is; the bias
    # never is; one vocabulary leaf
    assert sum("router_w" in n for n in tc.trainable) == (
        3 if router_trained else 0)
    assert not [n for n in tc.trainable if "router_bias" in n]
    assert [n for n, s, _, _ in tc.leaves if s[0] == 211] == ["embed_w"]
    seed = 2 ** 31 + 11
    feeds = traffic_gen.train_feeds(
        cell.traffic, cell.cfg, train.data_seed(cell.traffic, seed))
    assert {k: v.shape for k, v in feeds[0].items()} == {
        "ids": (4, 4, 49, 1), "loss_weight": (4, 4, 48, 1)}
    obs = tc.first_calls(seed, feeds)
    ref = tc.reference(seed, feeds)
    numbers, where = train.numbers_of(obs, ref)
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["loss0_gap"] < 1e-5, numbers
    assert numbers["grad_diff"] < 1e-3, (numbers, where)
    assert numbers["frozen_moved"] == 0.0, numbers
    assert numbers["grad_gap"] < 1e-4, (numbers, where)
    assert numbers["step_gap"] < 1e-3, (numbers, where)


def test_stated_seeds_give_every_run_the_same_weights_and_feeds():
    train = registry.load_driver("train")
    for cell in (registry.load_cell(CELL), tiny_cell()):
        got = {(train.weights_seed(cell.cfg, s),
                train.data_seed(cell.traffic, s))
               for s in (5, 2 ** 31 + 6)}
        assert got == {(cell.cfg["weights_seed"],
                        cell.traffic["data_seed"])}
    cell = registry.load_cell(CELL)
    assert "weights_seed" in cell.cfg["assumed"]
    assert cell.traffic["feed_pool"] == 4
    tiny = tiny_cell()
    tc = train.TrainCell(tiny)
    a, b = (tc.init_params(s) for s in (5, 2 ** 31 + 6))
    assert all(bool((a[k] == b[k]).all()) for k in a)
    fa, fb = (traffic_gen.train_feeds(
        tiny.traffic, tiny.cfg, train.data_seed(tiny.traffic, s))
        for s in (5, 2 ** 31 + 6))
    assert all((fa[i][k] == fb[i][k]).all() for i in range(2) for k in fa[i])


def test_configuration_keeps_every_published_width():
    cell = registry.load_cell(CELL)
    for key, value in PUBLISHED.items():
        assert cell.cfg[key] == value, key
    assert (cell.cfg["router_experts"], cell.cfg["num_experts"]) == (32, 8)
    assert cell.cfg["vocab_size"] * 4 == 65536
    assert cell.cfg["num_hidden_layers"] == 6
    entry = [c for c in cell.bench["configs"]
             if c["name"] == "lfm2_8b_a1b_ep4"][0]
    assert sorted(entry["reduced"]) == sorted(cell.cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/LiquidAI/LFM2-8B-A1B"
                               "/blob/main/config.json")
    for beside, key in (("24", "num_hidden_layers"), ("32", "num_experts"),
                        ("65536", "vocab_size")):
        assert beside in cell.cfg["reduced"][key]
    assert "4 chips" in cell.cfg["deployment"]
    for key in ("tie_word_embeddings", "initializer_range",
                "conv_filter_init", "router_bias_std", "router_norm_eps",
                "weights_seed"):
        assert key in cell.cfg["assumed"]
    for key in ("balance_loss", "router_bias_update", "packed_gate_up"):
        assert key in cell.cfg["departures"]
    assert traffic_gen.tokens_per_step(cell.traffic, cell.cfg) == (
        cell.traffic["batch"] * cell.traffic["seq_len"])
    assert cell.traffic["ids_len"] == cell.traffic["seq_len"] + 1


def test_leaves_add_up_to_the_cut_as_reckoned():
    cell = registry.load_cell(CELL)
    ref = registry.load_module(cell.path(cell.cfg["reference"]))
    size = {}
    for name, shape, _, _ in ref.leaves(cell.cfg, cell.traffic):
        n = 1
        for s in shape:
            n *= s
        size[name] = n

    def layer(i, *names):
        return sum(size[f"layer{i}.{n}"] for n in names)

    conv = layer(0, "conv_in_w", "conv_w", "conv_out_w")
    attention = layer(2, "q_w", "k_w", "v_w", "o_w", "q_norm.scale",
                      "k_norm.scale")
    dense = layer(0, "gate_up_w", "down_w")
    router = layer(2, "router_w", "router_bias")
    experts = layer(2, "experts_gate_up_w", "experts_down_w")
    assert (conv, attention, dense, router, experts // 8) == (
        16_783_360, 10_485_888, 44_040_192, 65_568, 11_010_048)

    def block(i):
        return sum(v for k, v in size.items() if k.startswith(f"layer{i}."))

    assert [block(i) for i in range(6)] == [
        60_827_648, 60_827_648, 98_635_936, 104_933_408, 104_933_408,
        104_933_408]
    assert size["embed_w"] == 33_554_432 and size["final_norm.scale"] == 2048
    assert "head_w" not in size
    assert sum(size.values()) == 568_647_936


def test_flops_count_what_this_chip_computes():
    cell = registry.load_cell(CELL)
    f = registry.load_module(cell.path(cell.cfg["flops"]))
    cfg = cell.cfg
    traffic = dict(cell.traffic, batch=2, seq_len=4096)
    conv = 2048 * 6144 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 32 * 2 * 64 * 4097 / 2
    dense, expert = 3 * 2048 * 7168, 3 * 2048 * 1792
    want = (5 * conv + attn + 2 * dense + 4 * (2048 * 32 + expert)
            + 2048 * 16384)
    assert f.forward_macs_per_token(cfg, 4096) == want
    # ISSUE 35's count: 537 M forward FLOPs a token, 13.2 TFLOP a step
    assert round(2 * want / 1e6) == 537
    assert f.flops_per_token(dict(cfg, router_trained=True),
                             traffic) == 6 * want
    assert f.flops_per_token(dict(cfg, router_trained=False), traffic) == (
        6 * want - 2 * 2 * 4 * 2048 * 32)
    assert 13.1e12 < 8192 * 6 * want < 13.3e12
    pairs = 2 * 32 * 1 * 4096 * 4097 // 2
    assert f.attention_flops_per_step(cfg, traffic) == pairs * (
        2 * 128 + 2 * 5 * 64)
    # the convolution kernels move bytes: X [T, 6144] and Out [T, 2048]
    # forward; X, dOut and dX backward; bfloat16; five blocks
    rows = 2 * 8192 * 2048 * (4 + 7)
    assert f.short_conv_bytes_per_step(cfg, traffic) == 5 * (
        rows + 4 * 3 * 2048 * 3)
    weights = 4 * 8 * expert
    assert f.gmm_bytes_per_step(cfg, 0) == 3 * 2 * weights
    assert f.gmm_bytes_per_step(cfg, 10) - f.gmm_bytes_per_step(
        cfg, 0) == 3 * 2 * 10 * (2 * 2048 + 3 * 1792)
    assert f.gmm_flops_per_step(cfg, 32768) == 3 * 2 * expert * 32768
    # a thousand rows an expert: the multiply-adds are the bound
    assert (f.gmm_flops_per_step(cfg, 32768) / 197e12
            > f.gmm_bytes_per_step(cfg, 32768) / 819e9)


NEW_METRICS = ["short_conv_ms.train", "short_conv_roofline.train",
               "lfm2_attn_ms.train", "lfm2_attn_roofline.train",
               "lfm2_moe_gmm_ms.train", "lfm2_moe_gmm_roofline.train",
               "lfm2_moe_imbalance.train", "lfm2_moe_live_share.train"]


def _ctx(ops_s, events, phases, monkeypatch):
    import program_spans

    monkeypatch.setattr(program_spans, "traced_calls", lambda ctx: events)
    monkeypatch.setattr(program_spans, "compile_phases", lambda: phases)
    return {"cell": registry.load_cell(CELL),
            "result": {"traced": {"steps": 32, "calls": 4}},
            "device": {"kind": "TPU v5 lite"}, "trace": {"ops_s": ops_s}}


def test_benchmark_lists_the_eight_for_the_new_cell_alone():
    cell = registry.load_cell(CELL)
    mine = [m for m in cell.bench["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in mine] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"]
               == "train_tokens_per_s" for m in mine)
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW_METRICS) <= reported
    # the six that list no cells report here by themselves
    assert {"executor_gap_ms.train", "step_mfu.train",
            "device_step_ms.train", "mosaic_share.train",
            "device_idle.train", "peak_hbm_gib.train"} <= reported
    assert not {"moe_gmm_ms.train", "mla_attn_ms.train",
                "host_prepare_ms.train"} & reported
    for other in ("joyai_flash_ep16_train", "sdar_30b_a3b_ep8_train"):
        theirs = {m["name"] for m in
                  registry.load_cell(other).metrics("per_layer")}
        assert not set(NEW_METRICS) & theirs
    entry = [w for w in cell.bench["workloads"] if w["name"] == CELL][0]
    assert (entry["config"], entry["chips"]) == ("lfm2_8b_a1b_ep4", 1)
    assert entry["traffic"] in ("train_ntp_b2_s4096x8",
                                "train_ntp_b2_s2048x8")
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("phases", [None, {"backend_s": 1.0}])
@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_find_nothing_on_a_program_that_records_nothing(
        metric, phases, monkeypatch):
    ctx = _ctx({"fusion.1 f32[8]": 1.0}, [{"phases": [["feed", 0, 1]]}],
               phases, monkeypatch)
    assert registry.load_reader(metric).read(ctx) is None


def test_readers_on_a_hand_made_trace(monkeypatch, capsys):
    ops = {"short_conv_fwd.2 bf16[2,4096,2048] mosaic": 0.032,
           "short_conv_bwd.2 bf16[2,4096,6144] mosaic": 0.064,
           "flash_bhtd_fwd.3 bf16[64,4096,64] mosaic": 0.064,
           "flash_bhtd_bwd_dq.3 bf16[64,4096,64] mosaic": 0.064,
           "flash_bhtd_bwd_dkv.3 bf16[64,4096,64] mosaic": 0.128,
           "moe_gmm_fwd.1 bf16[16384,3584] mosaic": 0.128,
           "moe_gmm_bwd_dx.1 bf16[16384,1792] mosaic": 0.128,
           "moe_gmm_bwd_dw.1 bf16[8,2048,3584] mosaic": 0.128,
           "short_conv_fwd.9 bf16[1]": 5.0}  # not a Mosaic call: not read
    events = [{"phases": [], "counters": {
        "moe_local_pairs": 32000.0, "moe_max_over_mean": 3.0,
        "moe_rows_walked": 65536.0}},
        {"phases": [], "counters": {
            "moe_local_pairs": 33536.0, "moe_max_over_mean": 5.0,
            "moe_rows_walked": 81920.0}}]
    phases = {"short_conv_sites_kernel": 5, "short_conv_sites_xla": 0}
    ctx = _ctx(ops, events, phases, monkeypatch)
    read = {m: registry.load_reader(m).read(ctx) for m in NEW_METRICS}
    assert "compile_phases" in capsys.readouterr().err
    assert read["short_conv_ms.train"] == pytest.approx(3.0)
    assert read["lfm2_attn_ms.train"] == pytest.approx(8.0)
    assert read["lfm2_moe_gmm_ms.train"] == pytest.approx(12.0)
    assert read["lfm2_moe_imbalance.train"] == pytest.approx(4.0)
    assert read["lfm2_moe_live_share.train"] == pytest.approx(
        100 * 32768 / 73728)
    cell = ctx["cell"]
    f = registry.load_module(cell.path(cell.cfg["flops"]))
    assert read["short_conv_roofline.train"] == pytest.approx(
        100 * f.short_conv_bytes_per_step(cell.cfg, cell.traffic)
        / (0.003 * 819e9))
    assert read["lfm2_attn_roofline.train"] == pytest.approx(
        100 * f.attention_flops_per_step(cell.cfg, cell.traffic)
        / (0.008 * 197e12))
    assert read["lfm2_moe_gmm_roofline.train"] == pytest.approx(
        100 * f.gmm_flops_per_step(cell.cfg, 32768) / 197e12 / 0.012)
    for name in ("short_conv_roofline.train", "lfm2_attn_roofline.train",
                 "lfm2_moe_gmm_roofline.train"):
        assert 0 < read[name] < 100, name


#: the chip readings the limits were set from (limits file, `set_from`):
#: name -> (the program's largest over 12 seeds and the stated draw, the
#: fp8 control's on its three seeds)
READINGS = {
    "grad_diff": (0.2135, (0.695, 0.696, 0.699)),
    "grad_diff_median": (0.0477, (0.373, 0.376, 0.371)),
    "loss0_rms": (5.04e-5, (3.33e-4, 3.49e-4, 3.44e-4)),
    "loss_gap": (1.04e-4, (3.56e-4, 7.82e-4, 3.72e-4)),
    "grad_gap": (1.84e-3, (1.41e-2, 6.14e-3, 7.12e-3)),
    "grad_gap_median": (1.16e-4, (6.75e-4, 4.81e-4, 4.42e-4)),
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_limit_lies_between_its_two_readings(name):
    """Room of a half on both sides: the sound program passes and the fp8
    control fails this very number on every seed (REVIEW 35: three trained
    limits stood above the control's readings)."""
    limit = registry.load_cell(CELL).limits[name]
    largest, control = READINGS[name]
    assert min(control) >= 3 * largest  # an upper reading (PR 24's rule)
    assert 1.5 * largest <= limit <= min(control) / 1.5


def test_a_number_with_no_upper_reading_is_not_compared():
    held = registry.load_cell(CELL).limits
    told = registry.load_json(registry.Cell.path(
        f"limits/{CELL}.json"))["not_compared"]
    assert set(held) == set(READINGS) | {"step_gap", "frozen_moved"}
    assert set(told) == {"loss0_gap", "step_gap_median"}
