"""The end-to-end readers on hand-made records: TTFT from the scheduled
time with the window's cut-off rule, per-request TPOT, tokens/s over the
window; and the scheduler readers on hand-made tick records."""

import types

import pytest

import clients
import run


def _rec(due, times, error=None, greedy=True):
    r = clients.Record({"prompt": [1], "greedy": greedy}, due=due, sent=due)
    r.times = list(times)
    r.tokens = [0] * len(times)
    r.error = error
    return r


def _run(records, **kw):
    base = dict(records=records, t_open=10.0, t_close=20.0, ticks=[],
                spans=[], max_slots=4, setup_s=3.0)
    base.update(kw)
    return run.RunData(**base)


def _read(name, data):
    import os
    return run.load_module(os.path.join(run.BENCH, "metrics",
                                        name + ".py")).read(data)


def test_ttft_counts_from_due_time_and_cuts_at_close():
    recs = [
        _rec(5.0, [11.0]),             # due before the window: not counted
        _rec(12.0, [13.5, 14.0]),      # 1.5 s from its due time
        _rec(13.0, []),                # no first token: age at close = 7
        _rec(14.0, [], error="429"),   # refused: age at close = 6
        _rec(19.0, [21.0]),            # first token after close: age 1
    ]
    data = _run(recs)
    vals = sorted([1.5, 7.0, 6.0, 1.0])
    import statistics
    want = statistics.quantiles(vals, n=10, method="inclusive")[8]
    assert _read("ttft_p90_s", data) == pytest.approx(want)


def test_tpot_is_per_request_inside_the_window():
    recs = [_rec(0.0, [9.0, 11.0, 12.0, 13.0]),  # in window: 11,12,13 -> 1.0
            _rec(0.0, [15.0, 19.0]),             # 4.0
            _rec(0.0, [19.5])]                   # one token: left out
    data = _run(recs)
    import statistics
    want = statistics.quantiles([1.0, 4.0], n=10, method="inclusive")[8]
    assert _read("tpot_p90_s", data) == pytest.approx(want)


def test_tokens_per_second_counts_only_the_window():
    recs = [_rec(0.0, [9.0, 10.0, 15.0, 20.0, 20.5]), _rec(0.0, [12.0])]
    assert _read("output_tokens_per_s", _run(recs)) == pytest.approx(0.4)


def test_setup_is_passed_through():
    assert _read("setup_s", _run([])) == 3.0


def _tick(tick, start, wall, active, tokens=0):
    return types.SimpleNamespace(tick=tick, start=start, wall_s=wall,
                                 active_slots=active, tokens=tokens,
                                 mode="chunked", prefilling_slots=0)


def test_scheduler_readers_use_ticks_inside_the_window():
    ticks = [_tick(1, 9.5, 1.0, 4),   # straddles the open: left out
             _tick(2, 11.0, 0.5, 2), _tick(3, 12.0, 1.5, 1)]
    data = _run([], ticks=ticks)
    assert _read("sched.batch_occupancy", data) == pytest.approx(37.5)
    assert _read("tick.mean_s", data) == pytest.approx(1.0)


def test_a_listed_metric_that_reads_nothing_is_an_error():
    # no trace: the roofline reader finds nothing to read
    entry = {"name": "paged_decode_attention_roofline", "unit": "%",
             "workloads": ["a.cell"]}
    data = _run([], trace=None, cell={"name": "a.cell"})
    with pytest.raises(RuntimeError, match="read nothing"):
        run.read_metrics([entry], data)
    data.cell = {"name": "another.cell"}
    assert run.read_metrics([entry], data) == {}


def test_queue_wait_reads_spans_ending_in_the_window():
    sp = lambda s, e: types.SimpleNamespace(name="queued", start=s, end=e,
                                            attrs={}, rid=0)
    spans = [sp(1.0, 2.0), sp(10.5, 11.0), sp(18.0, None)]
    data = _run([], spans=spans)
    import statistics
    want = statistics.quantiles([0.5, 2.0], n=10, method="inclusive")[8]
    assert _read("sched.queue_wait_p90_s", data) == pytest.approx(want)
