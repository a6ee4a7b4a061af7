"""Performance-attribution tier tests (tier-1, no TPU): the static
roofline/launch cost model (analysis/costmodel) with a hand-computed
red-gate program, the zero-cost contract of every new attribution gauge,
the executor dispatch-vs-device-wait split, the noise-aware bench sentry
(tools/bench_diff), and the tolerant xplane reader."""

import json
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.analysis.costmodel import (
    DEVICE_MODELS,
    DeviceModel,
    cost_program,
    publish_cost,
    resolve_device_model,
)
from paddle_tpu.flags import FLAGS
from paddle_tpu.generation.kv_cache import KVCache
from paddle_tpu.monitor import StepMonitor, default_registry

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Each test starts with default flags and an empty default registry."""
    FLAGS.reset()
    default_registry().reset()
    yield
    FLAGS.reset()
    default_registry().reset()


def _two_op_program():
    """matmul (4,128)x(128,256) then relu — every cost hand-computable
    from the declared shapes (no -1 dims)."""
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data(name="x", shape=[4, 128], append_batch_size=False)
        y = layers.data(name="y", shape=[128, 256], append_batch_size=False)
        out = layers.matmul(x, y)
        layers.relu(out)
    return prog


# ---------------------------------------------------------------------------
# the red-gate: a fabricated 2-op program checked EXACTLY
# ---------------------------------------------------------------------------


class TestCostModelExact:
    # hand computation:
    #   matmul: flops = 2 * (4*128) * 256            = 262144
    #           bytes = (4*128 + 128*256 + 4*256)*4  = 137216
    #   relu:   flops = 4*256 (elementwise tier)     = 1024
    #           bytes = (4*256 + 4*256) * 4          = 8192
    MM_FLOPS, MM_BYTES = 262144.0, 137216
    RL_FLOPS, RL_BYTES = 1024.0, 8192

    def test_two_op_program_exact(self):
        dev = DeviceModel("test", peak_flops=1e6, hbm_bytes_per_s=1e6,
                          launch_overhead_s=1e-3)
        cost = cost_program(_two_op_program(), name="t", device=dev)
        assert [oc.type for oc in cost.ops] == ["matmul", "relu"]
        assert cost.n_launches == 2
        mm, rl = cost.ops
        assert mm.flops == self.MM_FLOPS and mm.bytes == self.MM_BYTES
        assert rl.flops == self.RL_FLOPS and rl.bytes == self.RL_BYTES
        # classification: matmul t_c=0.262 > t_m=0.137 -> compute;
        # relu t_m=0.0082 > t_c=0.001 -> memory (both above 1ms launch)
        assert mm.bound == "compute"
        assert rl.bound == "memory"
        assert cost.total_flops == self.MM_FLOPS + self.RL_FLOPS
        assert cost.total_bytes == self.MM_BYTES + self.RL_BYTES
        # the ISSUE contract, verbatim
        roofline = max(cost.total_flops / 1e6, cost.total_bytes / 1e6)
        assert cost.roofline_seconds == pytest.approx(roofline)
        assert cost.predicted_seconds == pytest.approx(roofline + 2 * 1e-3)
        assert cost.launch_bound_fraction == pytest.approx(
            2e-3 / (roofline + 2e-3))
        assert cost.bound_counts() == {"compute": 1, "memory": 1,
                                       "launch": 0}
        assert cost.warnings == []

    def test_launch_classification(self):
        # overhead dwarfs both residency floors -> everything launch-bound
        dev = DeviceModel("test", peak_flops=1e15, hbm_bytes_per_s=1e15,
                          launch_overhead_s=1.0)
        cost = cost_program(_two_op_program(), name="t", device=dev)
        assert all(oc.bound == "launch" for oc in cost.ops)
        assert cost.launch_bound_fraction > 0.99

    def test_dynamic_dim_warns_not_fabricates(self):
        # the conventional -1 batch axis: without batch_size the var
        # contributes 0 bytes + ONE named warning; with batch_size it is
        # sized exactly
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[128])  # (-1, 128)
            layers.relu(x)
        dev = DeviceModel("test", 1e6, 1e6, 1e-3)
        cost = cost_program(prog, name="t", device=dev)
        assert any(w["check"] == "dynamic-dim" for w in cost.warnings)
        sized = cost_program(prog, name="t", batch_size=4, device=dev)
        # relu out is also (-1, 128): in + out = 2 * 4*128*4 bytes
        assert sized.ops[0].bytes == 2 * 4 * 128 * 4
        assert not any(w["check"] == "dynamic-dim"
                       for w in sized.warnings)

    def test_unregistered_op_warns(self):
        prog = pt.Program()
        prog.global_block().append_op("totally_made_up_op", {}, {}, {})
        cost = cost_program(prog, name="t",
                            device=DeviceModel("test", 1e6, 1e6, 1e-3))
        assert any(w["check"] == "unregistered-op" for w in cost.warnings)


class TestResolveDevice:
    def test_explicit_and_flag_resolution(self):
        assert resolve_device_model("TPU v5e").peak_flops \
            == DEVICE_MODELS["TPU v5e"].peak_flops
        FLAGS.device_model = "TPU v4"
        assert resolve_device_model().name == "TPU v4"

    def test_flag_overrides_mark_source(self):
        FLAGS.peak_flops = 123.0
        FLAGS.launch_overhead_us = 7.0
        dm = resolve_device_model("TPU v5e")
        assert dm.peak_flops == 123.0
        assert dm.launch_overhead_s == pytest.approx(7e-6)
        assert dm.source == "flags"
        # the table entry itself is untouched
        assert DEVICE_MODELS["TPU v5e"].source == "datasheet"

    def test_unknown_kind_raises_and_cpu_is_cpu_host(self):
        # on the CPU backend auto-detection is "cpu-host"; an unknown
        # name is an error, never a silent host fallback
        assert resolve_device_model().name == "cpu-host"
        with pytest.raises(LookupError, match="no-such-chip"):
            resolve_device_model("no-such-chip")


# ---------------------------------------------------------------------------
# zero-cost contract + /metrics surface
# ---------------------------------------------------------------------------


class TestAttributionTelemetry:
    def test_publish_cost_zero_cost_when_off(self):
        cost = cost_program(_two_op_program(), name="t",
                            device=DeviceModel("test", 1e6, 1e6, 1e-3))
        publish_cost(cost)
        assert default_registry().names() == []

    def test_publish_cost_gauges_and_scrape(self):
        FLAGS.monitor = True
        cost = cost_program(_two_op_program(), name="t",
                            device=DeviceModel("test", 1e6, 1e6, 1e-3))
        publish_cost(cost)
        reg = default_registry()
        assert reg.get("cost.t.op_count").value == 2
        assert reg.get("cost.t.launch_count").value == 2
        assert reg.get("cost.t.predicted_step_seconds").value \
            == pytest.approx(cost.predicted_seconds)
        assert reg.get("cost.t.launch_bound_fraction").value \
            == pytest.approx(cost.launch_bound_fraction)
        # the /metrics scrape renders the attribution gauges
        text = reg.prometheus_text()
        assert "cost.t.launch_bound_fraction" in text.replace(
            "cost_t_launch_bound_fraction", "cost.t.launch_bound_fraction")

    def test_executor_dispatch_split(self):
        """A monitored cache-hit run decomposes into enqueue (dispatch)
        vs transfer-wait time; both histograms populate."""
        FLAGS.monitor = True
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[8])
            m = layers.mean(x)
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        feed = {"x": np.zeros((4, 8), np.float32)}
        exe.run(prog, feed=feed, fetch_list=[m])  # compile call
        exe.run(prog, feed=feed, fetch_list=[m])  # cache hit
        reg = default_registry()
        assert reg.get("executor.dispatch_seconds").count >= 1
        assert reg.get("executor.device_wait_seconds").count >= 1

    def test_executor_split_zero_cost_when_off(self):
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            x = layers.data(name="x", shape=[8])
            m = layers.mean(x)
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        feed = {"x": np.zeros((4, 8), np.float32)}
        exe.run(prog, feed=feed, fetch_list=[m])
        exe.run(prog, feed=feed, fetch_list=[m])
        assert default_registry().names() == []

    def test_kv_cache_hbm_bytes_exact(self):
        c = KVCache("kv", num_layers=2, batch=3, max_t=5, n_head=4,
                    d_head=8, dtype="float32")
        # K + V float32 buffers + int32 length counters
        assert c.hbm_bytes == 2 * (2 * 3 * 5 * 4 * 8) * 4 + 4 * 3


class TestStepMonitorPeak:
    def test_flag_override_wins(self):
        FLAGS.peak_flops = 5e12
        mon = StepMonitor(name="t", flops_per_step=1.0)
        assert mon._resolve_peak() == 5e12

    def test_unknown_device_omits_mfu(self):
        # CPU backend: device_kind is not in the device table and no
        # override is set -> peak unknown -> MFU must be OMITTED, not
        # fabricated from a stale constant
        FLAGS.monitor = True
        mon = StepMonitor(name="t", flops_per_step=1e9)
        assert mon._resolve_peak() is None
        mon.step()
        mon.step()
        rec = mon.records[-1]
        assert "mfu" not in rec and "rolling_mfu" not in rec
        assert default_registry().get("t.rolling_mfu") is None


# ---------------------------------------------------------------------------
# bench sentry (tools/bench_diff.py)
# ---------------------------------------------------------------------------


def _bd():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import bench_diff
    finally:
        sys.path.pop(0)
    return bench_diff


def _rec(metric, value, unit="tokens/sec", runs=None):
    cfg = {"runs": runs} if runs is not None else {}
    return {"metric": metric, "value": value, "unit": unit, "config": cfg}


class TestBenchDiff:
    def test_within_noise_is_not_a_finding(self):
        bd = _bd()
        base = [("m", _rec("m", 100.0, runs=[95.0, 105.0]))]
        fresh = [("m", _rec("m", 90.0, runs=[88.0, 92.0]))]
        regs, notes = bd.diff(base, fresh, rel_tol=0.30)
        assert regs == []
        assert any("within noise" in n for n in notes)

    def test_separated_envelopes_regress_by_name(self):
        bd = _bd()
        base = [("decode_tokens_per_sec_b1",
                 _rec("decode_tokens_per_sec_b1", 1000.0,
                      runs=[950.0, 1050.0]))]
        fresh = [("decode_tokens_per_sec_b1",
                  _rec("decode_tokens_per_sec_b1", 50.0,
                       runs=[45.0, 55.0]))]
        regs, _ = bd.diff(base, fresh, rel_tol=0.30)
        assert len(regs) == 1
        # the named (workload, metric) pair — the sentry's contract
        assert "(decode, decode_tokens_per_sec_b1)" in regs[0]
        assert "REGRESSED" in regs[0]

    def test_lower_better_units(self):
        bd = _bd()
        base = [("d", _rec("d", 100.0, unit="us/launch"))]
        worse = [("d", _rec("d", 500.0, unit="us/launch"))]
        better = [("d", _rec("d", 20.0, unit="us/launch"))]
        regs, _ = bd.diff(base, worse, rel_tol=0.30)
        assert len(regs) == 1
        regs, notes = bd.diff(base, better, rel_tol=0.30)
        assert regs == []
        assert any("improved" in n for n in notes)

    def test_missing_baseline_metric_fails_named(self):
        bd = _bd()
        base = [("a_x", _rec("a_x", 1.0)), ("b_y", _rec("b_y", 2.0))]
        fresh = [("a_x", _rec("a_x", 1.0))]
        regs, _ = bd.diff(base, fresh, rel_tol=0.30)
        assert len(regs) == 1 and "MISSING" in regs[0] and "b_y" in regs[0]

    def test_cli_exit_codes(self, tmp_path):
        base = tmp_path / "base.json"
        fresh = tmp_path / "fresh.json"
        base.write_text(json.dumps(_rec("w_tps", 1000.0)) + "\n")
        fresh.write_text(json.dumps(_rec("w_tps", 10.0)) + "\n")
        clean = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_diff.py"),
             str(base), str(base)], capture_output=True, text=True)
        assert clean.returncode == 0, clean.stdout + clean.stderr
        red = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_diff.py"),
             str(base), str(fresh)], capture_output=True, text=True)
        assert red.returncode == 1
        assert "REGRESSION (w, w_tps)" in red.stdout


# ---------------------------------------------------------------------------
# tolerant xplane reader (synthetic protobuf planes)
# ---------------------------------------------------------------------------


def _vint(v):
    out = b""
    while True:
        b7 = v & 0x7F
        v >>= 7
        if v:
            out += bytes([b7 | 0x80])
        else:
            return out + bytes([b7])


def _field(num, wt, payload):
    tag = _vint((num << 3) | wt)
    if wt == 0:
        return tag + _vint(payload)
    if wt == 2:
        return tag + _vint(len(payload)) + payload
    return tag + payload  # fixed64/fixed32 raw bytes


def _msg(*fields):
    return b"".join(fields)


class TestXPlaneTolerant:
    def _good_plane(self):
        ev_meta = _field(4, 2, _msg(_field(1, 0, 7),
                                    _field(2, 2, _msg(_field(1, 0, 7),
                                                      _field(2, 2, b"opA")))))
        stat_meta = _field(5, 2, _msg(
            _field(1, 0, 3),
            _field(2, 2, _msg(_field(1, 0, 3), _field(2, 2, b"bytes")))))
        ref_meta = _field(5, 2, _msg(
            _field(1, 0, 5),
            _field(2, 2, _msg(_field(1, 0, 5), _field(2, 2, b"kind")))))
        stats = (
            _field(4, 2, _msg(_field(1, 0, 3), _field(3, 0, 42))) +
            # stat id 99 has no metadata entry -> skipped with a warning
            _field(4, 2, _msg(_field(1, 0, 99), _field(3, 0, 1))) +
            # ref stat: value is stat-metadata id 5's NAME
            _field(4, 2, _msg(_field(1, 0, 3), _field(7, 0, 5))))
        event = _field(4, 2, _msg(_field(1, 0, 7), _field(2, 0, 10),
                                  _field(3, 0, 20), stats))
        line = _field(3, 2, _msg(_field(2, 2, b"l0"), event))
        return _msg(_field(2, 2, b"/device:TPU:0"), ev_meta, stat_meta,
                    ref_meta, line)

    def test_stats_resolve_and_missing_metadata_warns(self):
        from paddle_tpu.xplane import parse_xspace

        space = parse_xspace(_field(1, 2, self._good_plane()))
        assert len(space.planes) == 1
        (ev,) = space.planes[0].lines[0].events
        assert ev.name == "opA"
        assert ev.offset_ps == 10 and ev.duration_ps == 20
        # last write wins: the ref stat overwrote the uint64 on id 3
        assert ev.stats["bytes"] == "kind"
        assert any("missing stat-metadata entry #99" in w
                   for w in space.warnings)

    def test_unparseable_plane_skipped_with_named_warning(self):
        from paddle_tpu.xplane import parse_xspace

        # wire type 3 (group) is unsupported -> this "plane" cannot parse
        bad = _field(1, 2, b"\x03")
        space = parse_xspace(bad + _field(1, 2, self._good_plane()))
        # the good plane survives; the bad one is named, not fatal
        assert len(space.planes) == 1
        assert space.planes[0].name == "/device:TPU:0"
        assert any("skipping unparseable plane #0" in w
                   for w in space.warnings)

    def test_unparseable_line_keeps_plane(self):
        from paddle_tpu.xplane import parse_xspace

        plane = _msg(_field(2, 2, b"/host:CPU"), _field(3, 2, b"\x03"))
        space = parse_xspace(_field(1, 2, plane))
        assert len(space.planes) == 1
        assert space.planes[0].lines == []
        assert any("skipping unparseable line" in w
                   for w in space.warnings)

    def test_double_stat_value(self):
        from paddle_tpu.xplane import _parse_stat

        buf = _msg(_field(1, 0, 3),
                   _field(2, 1, struct.pack("<d", 2.5)))
        mid, val, is_ref = _parse_stat(buf)
        assert (mid, val, is_ref) == (3, 2.5, False)
