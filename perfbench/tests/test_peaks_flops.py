"""The benchmark's copies of the peaks table and of the FLOPs-per-token
functions agree with the program's originals today; an unknown device kind
is an error."""

import pytest

import peaks
import registry


def test_peaks_agree_with_costmodel():
    from paddle_tpu.analysis.costmodel import DEVICE_MODELS

    table = peaks.load_table()
    assert set(table) == {"TPU v5 lite", "TPU v5e"}
    for kind, row in table.items():
        assert row["peak_flops_bf16"] == DEVICE_MODELS[kind].peak_flops
        assert row["peak_hbm_bytes_per_s"] == DEVICE_MODELS[kind].hbm_bytes_per_s


def test_unknown_device_kind_is_an_error():
    with pytest.raises(LookupError):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(LookupError):
        peaks.peaks_for("cpu")


@pytest.mark.parametrize("workload", ["transformer_base_train",
                                      "bert_base_train"])
def test_flops_copies_agree_with_bench(workload):
    import bench

    cell = registry.load_cell(workload)
    mod = registry.load_module(cell.path(cell.cfg["flops"]))
    got = mod.flops_per_token(cell.cfg, cell.traffic)
    if workload.startswith("transformer"):
        c = bench.TRANSFORMER_BASE
        want = bench.transformer_train_flops_per_token(
            c["n_layer"], c["d_model"], c["d_inner_hid"], c["n_head"],
            c["d_key"], 256, c["vocab"])
    else:
        want = bench.bert_train_flops_per_token(12, 768, 3072, 128, 30522)
    assert got == want
