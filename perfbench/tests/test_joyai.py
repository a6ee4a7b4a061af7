"""The `joyai_llm_flash_ep16` files on the CPU: the reference against the
program at a tiny preset in float32, the FLOPs and bytes functions against
hand reckoning, the five readers on hand-made contexts (and on a parent
commit's, which records nothing), and the configuration file against the
published config."""

import os

import pytest

import registry
import traffic_gen

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "joyai_flash_ep16_train"

#: config.json of jdopensource/JoyAI-LLM-Flash: every number a width
PUBLISHED_WIDTHS = {
    "hidden_size": 2048, "intermediate_size": 7168, "kv_lora_rank": 512,
    "moe_intermediate_size": 768, "num_attention_heads": 32,
    "num_experts_per_tok": 8, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "head_dim": 64, "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "rope_theta": 32000000, "rms_norm_eps": 1e-06,
    "num_nextn_predict_layers": 1, "first_k_dense_replace": 1,
}


def tiny_cell(amp=False):
    cfg = registry.load_json(os.path.join(HERE, "data/joyai_tiny.json"))
    cfg["amp"] = amp
    return registry.Cell(
        "joyai_tiny", 1, cfg,
        registry.load_json(os.path.join(HERE, "data/train_tiny_joyai.json")),
        {})


def test_reference_follows_the_program_in_float32():
    cell = tiny_cell()
    train = registry.load_driver("train")
    tc = train.TrainCell(cell)
    seed = 2 ** 31 + 11
    feeds = traffic_gen.train_feeds(cell.traffic, cell.cfg, seed)
    assert feeds[0]["ids"].shape == (4, 4, 34, 1)
    obs = tc.first_calls(seed, feeds)
    ref = tc.reference(seed, feeds)
    numbers, where = train.numbers_of(obs, ref)
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["loss0_gap"] < 1e-5, numbers
    assert numbers["grad_diff"] < 1e-3, (numbers, where)
    assert numbers["frozen_moved"] == 0.0, numbers
    assert numbers["grad_gap"] < 1e-4, (numbers, where)
    assert numbers["step_gap"] < 1e-3, (numbers, where)


def test_configuration_keeps_every_published_width():
    cell = registry.load_cell(CELL)
    for key, value in PUBLISHED_WIDTHS.items():
        assert cell.cfg[key] == value, key
    assert (cell.cfg["router_experts"], cell.cfg["n_routed_experts"]) == (
        256, 16)
    assert cell.cfg["vocab_size"] * 8 == 129280
    entry = [c for c in cell.bench["configs"]
             if c["name"] == "joyai_llm_flash_ep16"][0]
    assert sorted(entry["reduced"]) == sorted(cell.cfg["reduced"])
    assert "16 chips share each layer" in cell.cfg["deployment"]
    assert traffic_gen.tokens_per_step(cell.traffic, cell.cfg) == 4096


def test_leaves_add_up_to_the_cut_as_reckoned():
    cell = registry.load_cell(CELL)
    ref = registry.load_module(cell.path(cell.cfg["reference"]))
    size = {}
    for name, shape, _, _ in ref.leaves(cell.cfg, cell.traffic):
        n = 1
        for s in shape:
            n *= s
        size[name] = n
    mla = sum(size[f"layer0.{w}"] for w in (
        "q_a_w", "q_b_w", "kv_a_w", "kv_b_w", "o_w"))
    assert mla == (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
                   + 4096 * 2048)
    assert size["layer1.experts_gate_up_w"] + size[
        "layer1.experts_down_w"] == 16 * 3 * 2048 * 768
    assert size["embed_w"] + size["head_w"] == 2 * 16160 * 2048
    assert round(sum(size.values()) / 1e6, 1) == 680.4


def test_flops_count_what_this_chip_computes():
    cell = registry.load_cell(CELL)
    f = registry.load_module(cell.path(cell.cfg["flops"]))
    cfg, traffic = cell.cfg, cell.traffic
    mla = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    attn = 32 * (192 + 128) * 2049 / 2
    expert = 3 * 2048 * 768
    want = (6 * (mla + attn) + 3 * 2048 * 7168
            + 5 * (2048 * 256 + 1.5 * expert) + 2 * 2048 * 2048
            + 2 * 2048 * 16160)
    assert f.forward_macs_per_token(cfg, 2048) == want
    assert f.flops_per_token(cfg, traffic) == 6 * want
    assert 2.2e9 < f.flops_per_token(cfg, traffic) < 2.35e9
    pairs = 2 * 32 * 6 * 2048 * 2049 // 2
    assert f.attention_flops_per_step(cfg, traffic) == pairs * (
        2 * 320 + 2 * (3 * 192 + 2 * 128))


def test_gmm_bytes_count_each_held_expert_once_a_pass():
    cell = registry.load_cell(CELL)
    f = registry.load_module(cell.path(cell.cfg["flops"]))
    weights = 5 * 16 * 3 * 2048 * 768
    assert f.gmm_bytes_per_step(cell.cfg, 0) == 3 * 2 * weights
    assert f.gmm_bytes_per_step(cell.cfg, 10) - f.gmm_bytes_per_step(
        cell.cfg, 0) == 3 * 2 * 10 * (2 * 2048 + 3 * 768)
    assert f.gmm_flops_per_step(cell.cfg, 10240) == (
        3 * 2 * 3 * 2048 * 768 * 10240)
    # 128 rows an expert: the weights' bandwidth is the bound
    assert (f.gmm_bytes_per_step(cell.cfg, 10240) / 819e9
            > f.gmm_flops_per_step(cell.cfg, 10240) / 197e12)


NEW_METRICS = ["mla_attn_ms.train", "mla_attn_roofline.train",
               "moe_gmm_ms.train", "moe_gmm_roofline.train",
               "moe_imbalance.train"]


def _ctx(ops_s, events, monkeypatch):
    import program_spans

    monkeypatch.setattr(program_spans, "traced_calls", lambda ctx: events)
    return {"cell": registry.load_cell(CELL),
            "result": {"traced": {"steps": 32, "calls": 4}},
            "device": {"kind": "TPU v5 lite"}, "trace": {"ops_s": ops_s}}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_find_nothing_on_a_program_that_records_nothing(
        metric, monkeypatch):
    ctx = _ctx({"fusion.1 f32[8]": 1.0,
                "flash_bthd_fwd.2 bf16[8] mosaic": 1.0},  # T's kernel, not J's
               [{"phases": [["feed", 0, 1]]}], monkeypatch)
    assert registry.load_reader(metric).read(ctx) is None


def test_readers_on_a_hand_made_trace(monkeypatch):
    ops = {"flash_bhtd_fwd.3 bf16[64,2048,128] mosaic": 0.32,
           "flash_bhtd_bwd_dq.3 bf16[64,2048,192] mosaic": 0.64,
           "flash_bhtd_bwd_dkv.3 bf16[64,2048,192] mosaic": 0.64,
           "moe_gmm_fwd.1 bf16[32768,1536] mosaic": 0.064,
           "moe_gmm_bwd_dx.1 bf16[32768,768] mosaic": 0.064,
           "moe_gmm_bwd_dw.1 bf16[16,2048,1536] mosaic": 0.128,
           "flash_bhtd_fwd.9 bf16[1]": 5.0}  # not a Mosaic call: not read
    events = [{"phases": [], "counters": {"moe_local_pairs": 10000.0,
                                          "moe_max_over_mean": 1.5}},
              {"phases": [], "counters": {"moe_local_pairs": 10480.0,
                                          "moe_max_over_mean": 1.7}}]
    ctx = _ctx(ops, events, monkeypatch)
    read = {m: registry.load_reader(m).read(ctx) for m in NEW_METRICS}
    assert read["mla_attn_ms.train"] == pytest.approx(50.0)
    assert read["moe_gmm_ms.train"] == pytest.approx(8.0)
    assert read["moe_imbalance.train"] == pytest.approx(1.6)
    cell = ctx["cell"]
    f = registry.load_module(cell.path(cell.cfg["flops"]))
    assert read["mla_attn_roofline.train"] == pytest.approx(
        100 * f.attention_flops_per_step(cell.cfg, cell.traffic)
        / (0.05 * 197e12))
    assert read["moe_gmm_roofline.train"] == pytest.approx(
        100 * f.gmm_bytes_per_step(cell.cfg, 10240) / 819e9 / 0.008)
    assert 0 < read["mla_attn_roofline.train"] < 100
    assert 0 < read["moe_gmm_roofline.train"] < 100
