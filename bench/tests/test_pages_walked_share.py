"""The reader of the paged decode kernel's walk, attn.pages_walked_share,
on hand-made tick records and on a traced run of a tiny cell. It reads
nothing from a program that does not count the pages its walk covers,
and is then left out of the line."""

import os
import types

import pytest

import run
from conftest import FakeChip

NAME = "attn.pages_walked_share"


def _read(data):
    return run.load_module(os.path.join(run.BENCH, "metrics",
                                        NAME + ".py")).read(data)


def _data(**kw):
    base = dict(records=[], t_open=10.0, t_close=20.0, ticks=[], spans=[],
                cell={"name": "a.cell"})
    base.update(kw)
    return run.RunData(**base)


def _tick(n, start, walked):
    return types.SimpleNamespace(tick=n, start=start, wall_s=0.3,
                                 kv_pages_walked=walked)


def test_pages_walked_share_is_over_the_whole_table_of_decode_ticks():
    # 4 slots; prompts up to 100 and answers up to 60 tokens in 16-token
    # pages: a 10-entry table, 40 entries a decode call could walk
    ticks = [_tick(1, 9.9, 40),    # starts before the open: left out
             _tick(2, 10.0, 12), _tick(3, 10.5, 0),  # 3: no decode call
             _tick(4, 11.0, 8)]
    data = _data(ticks=ticks, max_slots=4,
                 config={"serving": {"page_size": 16}},
                 traffic={"prompt": {"max": 100}, "output": {"max": 60}})
    assert _read(data) == pytest.approx(100.0 * (12 + 8) / (2 * 40))


@pytest.mark.parametrize("walked", [None, 0],
                         ids=["no-counter", "no-decode-tick"])
def test_pages_walked_share_reads_nothing_without_decode_walks(walked):
    # a program that does not count its walk, and one whose window held
    # no decode call
    def tick(n, start):
        t = types.SimpleNamespace(tick=n, start=start, wall_s=0.3)
        if walked is not None:
            t.kv_pages_walked = walked
        return t

    data = _data(ticks=[tick(1, 11.0), tick(2, 11.3)], max_slots=4,
                 config={"serving": {"page_size": 16}},
                 traffic={"prompt": {"max": 100}, "output": {"max": 60}})
    assert _read(data) is None
    assert run.read_metrics([{"name": NAME, "unit": "%"}], data) == {}


def test_a_traced_run_reads_pages_walked_share(tiny_spec, monkeypatch):
    # the CPU's profile holds no TPU plane: only the tracer's readers
    monkeypatch.setattr(run, "reduce_profile",
                        lambda profiler, data: (1.0, 2.0, {}))
    out = run.run_cell(tiny_spec, 2**31 + 7, 3.0, True, [FakeChip()])
    assert out["correct"], out["checks"]
    assert 0.0 < out["metrics"][NAME]["value"] <= 100.0
