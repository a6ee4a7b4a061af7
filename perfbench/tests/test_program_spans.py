"""The arithmetic over the program's own spans and counters
(program_spans.py) and the nine readers built on it, on hand-made flight
events, a hand-made reduced trace, and the kernel names the chip's
traces print for the two cells."""

import pytest

import program_spans as P
import registry

TRAIN_CELLS = ["transformer_base_train", "bert_base_train",
               "bert_base_train_seq512", "joyai_flash_ep16_train",
               "sdar_30b_a3b_ep8_train"]
NEW = ["host_outside_ms.train", "host_prepare_ms.train",
       "host_dispatch_ms.train", "gap_unexplained_ms.train",
       "setup_trace_s.train", "setup_lower_s.train", "setup_compile_s.train",
       "attn_fwd_ms.train", "attn_bwd_ms.train"]


def call(t0, feed, key, gather, dispatch, writeback, fetch):
    phases, at = [], 0.0
    for name, d in (("feed", feed), ("key", key), ("gather", gather),
                    ("dispatch", dispatch), ("writeback", writeback),
                    ("fetch", fetch)):
        phases.append([name, at, d])
        at += d
    return {"kind": "executor.run_steps", "t0": t0, "dur": at,
            "phases": phases}


# four traced calls, 0.2 ms apart in the caller; the first one's phases
# are long (a profiler starting) and follow no gap
CALLS = [call(10.0, .020, .010, .030, .005, .001, .700),
         call(10.7662, .002, .001, .004, .0015, .001, .720),
         call(11.4959, .002, .001, .004, .0015, .001, .720),
         call(12.2256, .003, .001, .005, .0025, .001, .720)]
# names as the chip's traces print them for the two cells (ledger, PR 32):
# self-attention and cross-attention sites are both `flash_bthd_*`
OPS = {"flash_bthd_fwd.7 bf16[64,256,8,64] mosaic": 0.040,
       "flash_bthd_fwd.95 bf16[64,256,8,64] mosaic": 0.040,
       "flash_bthd_bwd_dq.47 bf16[64,256,8,64] mosaic": 0.060,
       "flash_bthd_bwd_dkv.47 bf16[64,256,8,64] mosaic": 0.070,
       "flash_bthd_fwd.3 bf16[64,256,8,64] mosaic": 0.010,
       "flash_bthd_bwd_dq.44 bf16[64,256,8,64] mosaic": 0.012,
       "flash_bthd_bwd_dkv.44 bf16[64,256,8,64] mosaic": 0.014,
       "fusion.2391 f32[512]": 0.5,
       "fused_fwd_looking_fusion.1 f32[8]": 0.3}


def test_phase_means_go_over_the_calls_that_follow_a_gap():
    assert P.mean_phase_ms(CALLS, P.PREPARE) == pytest.approx(
        (7 + 7 + 9) / 3)
    assert P.mean_phase_ms(CALLS, P.DISPATCH) == pytest.approx(
        (1.5 + 1.5 + 2.5) / 3)
    assert P.mean_phase_ms(CALLS[:1], P.PREPARE) == pytest.approx(60.0)
    assert P.mean_phase_ms([], P.PREPARE) is None


def test_outside_is_exit_to_entry():
    assert P.outside_ms(CALLS) == pytest.approx(0.2, abs=1e-6)
    assert P.outside_ms(CALLS[:1]) is None and P.outside_ms([]) is None


def test_the_four_gap_metrics_sum_to_the_device_gap():
    gaps = [12.0, 13.5, 13.2]
    parts = [P.outside_ms(CALLS), P.mean_phase_ms(CALLS, P.PREPARE),
             P.mean_phase_ms(CALLS, P.DISPATCH),
             P.unexplained_ms(gaps, CALLS)]
    assert sum(parts) == pytest.approx(sum(gaps) / 3)
    assert P.unexplained_ms(gaps, []) is None
    assert P.unexplained_ms([], CALLS) is None


def test_kernels_split_by_the_one_tag_in_their_name():
    fwd = P.kernel_ms_per_step(OPS, "_fwd", 32)
    bwd = P.kernel_ms_per_step(OPS, "_bwd", 32)
    assert fwd == pytest.approx(1e3 * 0.090 / 32)
    assert bwd == pytest.approx(1e3 * 0.156 / 32)
    mosaic = sum(t for n, t in OPS.items() if n.endswith(" mosaic"))
    assert fwd + bwd == pytest.approx(1e3 * mosaic / 32)
    # the parent's names carry neither tag: nothing to read
    old = {"transpose_jvp___.168 bf16[96,128,768] mosaic": 0.05,
           "closed_call.12 bf16[96,128,768] mosaic": 0.02}
    assert P.kernel_ms_per_step(old, "_fwd", 32) is None
    assert P.kernel_ms_per_step(old, "_bwd", 32) is None
    assert P.kernel_ms_per_step(OPS, "_fwd", 0) is None


class _Ring:
    def __init__(self, events):
        self._events = events

    def events(self, kind=None):
        return [e for e in self._events if e["kind"] == kind]


def _ctx(calls=4):
    return {"result": {"traced": {"calls": calls, "steps": 8 * calls}},
            "trace": {"call_gap_ms": [12.0, 13.5, 13.2], "ops_s": OPS}}


def test_readers_on_a_filled_ring(monkeypatch, capsys):
    from paddle_tpu import monitor
    from paddle_tpu.monitor import flight

    older = dict(call(1.0, .5, .5, .5, .5, .5, .5))  # before the window
    compile_ev = dict(call(2.0, 1, 1, 1, 1, 1, 1), kind="executor.compile")
    monkeypatch.setattr(flight, "default_recorder",
                        lambda: _Ring([older, compile_ev] + CALLS))
    monkeypatch.setattr(monitor, "compile_phases", lambda: {
        "trace_s": 30.5, "lower_s": 9.25, "backend_s": 21.0,
        "cache_load_s": 20.5, "cache_hits": 3, "cache_misses": 1},
        raising=False)
    got = {n: registry.load_reader(n).read(_ctx()) for n in NEW}
    assert got["host_outside_ms.train"] == pytest.approx(0.2, abs=1e-6)
    assert got["host_prepare_ms.train"] == pytest.approx(23 / 3)
    assert got["host_dispatch_ms.train"] == pytest.approx(5.5 / 3)
    assert sum(got[n] for n in NEW[:4]) == pytest.approx(38.7 / 3)
    assert (got["setup_trace_s.train"], got["setup_lower_s.train"],
            got["setup_compile_s.train"]) == (30.5, 9.25, 21.0)
    assert "'cache_hits': 3" in capsys.readouterr().err
    assert got["attn_fwd_ms.train"] + got["attn_bwd_ms.train"] == \
        pytest.approx(1e3 * 0.246 / 32)


def test_readers_find_nothing_on_a_program_that_records_nothing(
        monkeypatch):
    """The parent commit: an empty ring, no `compile_phases`, old kernel
    names.  Every reader returns None and none raises."""
    from paddle_tpu import monitor
    from paddle_tpu.monitor import flight

    monkeypatch.setattr(flight, "default_recorder", lambda: _Ring([]))
    monkeypatch.delattr(monitor, "compile_phases", raising=False)
    ctx = _ctx()
    ctx["trace"]["ops_s"] = {
        "transpose_jvp___.168 bf16[96,128,768] mosaic": 0.05}
    for name in NEW:
        assert registry.load_reader(name).read(ctx) is None, name


def test_benchmark_lists_the_nine_for_the_train_cells():
    bench = registry.load_json(registry.os.path.join(registry.ROOT,
                                                     "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = by_name[name]
        # the executor's seven in every train cell; T's and B's two
        # attention readings in those two (B512, J, S: names of their own)
        assert m["workloads"] == (TRAIN_CELLS[:2] if "attn" in name
                                  else TRAIN_CELLS)
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert m["moves"] == ("setup_s" if name.startswith("setup_")
                              else "train_tokens_per_s")
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW  # together, wherever later ones go
