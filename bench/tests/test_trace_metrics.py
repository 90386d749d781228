"""The readers of the program's host phases, per-tick work counters and
front-end spans, on hand-made tick records and spans: tick.host_s,
step_hbm_share and frontend.emit_lag_p90_s. Each reads nothing from a
program that records none of them, and is then left out of the line."""

import os
import statistics
import types

import pytest

import run
from conftest import FakeChip

NEW = ("tick.host_s", "step_hbm_share", "frontend.emit_lag_p90_s")


def _read(name, data):
    return run.load_module(os.path.join(run.BENCH, "metrics",
                                        name + ".py")).read(data)


def _data(**kw):
    base = dict(records=[], t_open=10.0, t_close=20.0, ticks=[], spans=[],
                cell={"name": "a.cell"})
    base.update(kw)
    return run.RunData(**base)


def _tick(tick, start, wall, wait=0.0, calls=1, kv=0):
    return types.SimpleNamespace(tick=tick, start=start, wall_s=wall,
                                 wait_s=wait, step_calls=calls,
                                 kv_tokens_read=kv)


def _span(name, start, end):
    return types.SimpleNamespace(name=name, start=start, end=end, attrs={},
                                 rid=0, track="http:0")


def test_host_time_is_the_tick_period_less_its_wait():
    ticks = [_tick(1, 9.9, 0.3, 0.25),   # starts before the open: left out
             _tick(2, 10.0, 0.3, 0.28), _tick(3, 10.5, 0.3, 0.27),
             _tick(4, 11.25, 0.3, 0.29),
             _tick(6, 12.0, 0.3, 0.2)]   # 5 is missing: no period from 4
    # periods 2->3 and 3->4: 0.5 - 0.28 and 0.75 - 0.27
    assert _read("tick.host_s", _data(ticks=ticks)) == pytest.approx(
        (0.22 + 0.48) / 2)


def test_step_hbm_share_counts_weights_per_call_and_live_pool_tokens():
    dims = {"n": 2, "d": 8, "h": 4, "kh": 2, "hd": 4, "f": 16, "v": 32}
    # weights: 2 bytes x (1000 matmul parameters + 8 x 32 head) = 2,512
    # per call; a pool token: 2 layers x 2 kv heads x (2 x 4 int8 codes
    # + 2 float32 scales + an int32 position) = 80 bytes
    ticks = [_tick(1, 10.0, 0.5, calls=2, kv=10),
             _tick(2, 10.5, 0.5, calls=1, kv=3)]
    # at 100 bytes/s over the ticks' 1.0 s of wall time, the share in
    # percent is the byte count itself
    data = _data(ticks=ticks, dims=dims, matmul_params=1000,
                 peaks={"hbm_bytes_per_s": 100.0})
    assert _read("step_hbm_share", data) == 3 * 2512 + 13 * 80


def test_emit_lag_is_the_p90_of_sse_writes_ending_in_the_window():
    spans = [_span("sse.write", 9.0, 9.5),     # ends before the open
             _span("sse.write", 10.0, 10.001),
             _span("sse.write", 12.0, 12.004),
             _span("sse.write", 15.0, 15.002),
             _span("http.request", 10.0, 19.0),
             _span("sse.write", 19.99, 20.5)]  # ends after the close
    want = statistics.quantiles([0.001, 0.004, 0.002], n=10,
                                method="inclusive")[8]
    assert _read("frontend.emit_lag_p90_s",
                 _data(spans=spans)) == pytest.approx(want)


def test_a_program_without_the_records_reads_nothing_and_is_left_out():
    # tick records and spans of a program that has no host phases, work
    # counters or front-end spans
    old = types.SimpleNamespace(tick=1, start=11.0, wall_s=0.3)
    data = _data(ticks=[old, types.SimpleNamespace(tick=2, start=11.3,
                                                   wall_s=0.3)],
                 spans=[_span("prefill", 11.0, 11.1)], dims={},
                 matmul_params=0, peaks={})
    entries = [{"name": n, "unit": "s"} for n in NEW]
    for n in NEW:
        assert _read(n, data) is None
    assert run.read_metrics(entries, data) == {}


def test_a_traced_run_reads_the_new_metrics(tiny_spec, monkeypatch):
    # the CPU's profile holds no TPU plane: only the tracer's readers
    monkeypatch.setattr(run, "reduce_profile",
                        lambda profiler, data: (1.0, 2.0, {}))
    out = run.run_cell(tiny_spec, 2**31 + 5, 3.0, True, [FakeChip()])
    assert out["correct"], out["checks"]
    got = {n: out["metrics"][n]["value"] for n in NEW}
    assert got["tick.host_s"] > 0.0
    assert 0.0 < got["step_hbm_share"] <= 100.0
    assert got["frontend.emit_lag_p90_s"] > 0.0
