"""The trace reduction: its arithmetic on hand-made events, and its
numbers on a real recorded trace (data/recorded.xplane.pb, cut from the
first traced run on the chip; data/recorded.expected.json holds the
numbers written beside the recording)."""

import json
import os

import pytest

import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))


def test_leaves_only_drops_containers():
    evs = [("while", 0, 100), ("a", 10, 20), ("b", 40, 20),
           ("inner", 70, 20), ("c", 75, 5), ("d", 200, 10),
           ("marker", 200, 0)]
    # `d` holds only a zero-length marker and stays an operation
    assert sorted(n for n, _, _ in T.leaves_only(evs)) == [
        "a", "b", "c", "d", "marker"]


def test_union_clip_and_gaps():
    busy = T.union([(10, 30), (20, 40), (60, 70)])
    assert busy == [[10, 40], [60, 70]]
    assert T.clip(busy, 15, 65) == [[15, 40], [60, 65]]
    assert T.gaps_of(busy, 0, 100) == [[0, 10], [40, 60], [70, 100]]


def test_gap_goes_to_the_span_that_covers_most_of_it():
    spans = [("pb.run_steps", 0, 50), ("pb.fetch", 50, 8), ("pb.feed", 58, 2)]
    assert T.attribute([45, 60], spans) == "fetch"
    assert T.attribute([100, 110], spans) == "other"
    assert T.attribute([45, 60], spans, [[0, 40]]) == "fetch:between_programs"
    assert T.attribute([45, 60], spans, [[0, 55]]) == "fetch"


MOSAIC_OP = ('%attn.2 = bf16[64,256,512]{2,1,0} custom-call(bf16[8]{0} %x), '
             'custom_call_target="tpu_custom_call"')


def test_names_are_shortened_and_kernels_marked():
    text = ("%fusion.7 = (f32[8,128]{1,0:T(8,128)}, f32[8]{0}) fusion("
            "bf16[4]{0} %custom-call.3), kind=kLoop")
    assert T.short_name(text) == "fusion.7 f32[8,128]"
    assert not T.is_mosaic(text)
    assert T.short_name(MOSAIC_OP) == "attn.2 bf16[64,256,512] mosaic"


def test_reduce_device_on_hand_made_events():
    ops = [("while.1", 100, 400), ("fusion.1", 100, 100),
           (MOSAIC_OP, 250, 100), ("fusion.1", 400, 100),
           ("while.1", 600, 300), ("fusion.1", 600, 300)]
    modules = [("jit_run_steps", 100, 400), ("jit_run_steps", 600, 300),
               ("jit_convert", 520, 10)]
    ops.append(("convert.9", 520, 10))
    spans = [("pb.run_steps", 90, 420), ("pb.fetch", 510, 30),
             ("pb.feed", 540, 5), ("pb.run_steps", 545, 400)]
    d = T.reduce_device(ops, modules, spans, (100, 900))
    assert d["busy_ns"] == 100 + 100 + 100 + 10 + 300
    assert d["window_ns"] == 800
    assert d["mosaic_ns"] == 100
    # between the two step modules: 500..600 less the 10 ns of convert.9
    assert d["call_gap_ns"] == [90]
    assert d["ops"]["fusion.1"] == 500
    assert "while.1" not in d["ops"]
    # gaps 200-250, 350-400 and 530-600 lie mostly under run_steps; of
    # 500-520 run_steps and fetch cover 10 ns each and the first keeps it
    # and those two lie between the device's programs
    assert d["gap_by_span"] == {"run_steps": 50 + 50,
                                "run_steps:between_programs": 20 + 70}
    assert d["longest_gaps"][0] == ("run_steps:between_programs", 70)


@pytest.mark.skipif(
    not os.path.exists(os.path.join(HERE, "data", "recorded.xplane.pb")),
    reason="no recorded trace")
def test_recorded_trace_gives_the_numbers_written_beside_it():
    with open(os.path.join(HERE, "data", "recorded.expected.json")) as f:
        want = json.load(f)
    got = T.reduce_file(os.path.join(HERE, "data", "recorded.xplane.pb"), 1)
    for key in ("busy_s", "window_s", "mosaic_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert got["call_gap_ms"] == pytest.approx(want["call_gap_ms"], rel=1e-9)
    assert got["gap_by_span_s"] == pytest.approx(want["gap_by_span_s"],
                                                 rel=1e-9)
    assert [n for n, _ in got["breakdown"]["device_ops"]] == [
        n for n, _ in want["breakdown"]["device_ops"]]
    idle = 1.0 - got["busy_s"] / got["window_s"]
    assert idle == pytest.approx(want["idle_share"], rel=1e-9)
    # a second way to the busy union, taken when the recording was cut
    assert got["busy_s"] == pytest.approx(want["independent_busy_s"],
                                          rel=1e-4)
