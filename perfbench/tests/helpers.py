"""Tiny cells for the CPU tests: the bench.py tiny presets, with the same
reference, adapter, generator and driver files as the real cells."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import registry  # noqa: E402

TINY = {
    "transformer": ("data/transformer_tiny.json",
                    "data/train_tiny_transformer.json",
                    "transformer_base_train"),
    "bert": ("data/bert_tiny.json", "data/train_tiny_bert.json",
             "bert_base_train"),
}


def tiny_cell(which, amp=True, limits=None, out_dir=None):
    cfg_file, traffic_file, real = TINY[which]
    cfg = registry.load_json(os.path.join(HERE, cfg_file))
    cfg["amp"] = amp
    if limits is None:
        limits = registry.load_json(
            registry.Cell.path(f"limits/{real}.json"))["limits"]
    return registry.Cell(
        which + "_tiny", 1, cfg,
        registry.load_json(os.path.join(HERE, traffic_file)), limits,
        out_dir=out_dir)


def tiny_limits(which):
    return registry.load_json(
        os.path.join(HERE, f"data/limits_tiny_{which}.json"))["limits"]
