"""Trace reduction on a small synthetic event list: the union of busy
time, the sum per operation, idle gaps, and ops placed in modules and
ticks."""

import re
import types

import pytest

import work
import xplane

OPS = [("fusion.1", 0.0, 1.0), ("kernel", 0.5, 1.0),   # overlap: union 1.5
       ("kernel", 3.0, 0.5), ("copy", 5.0, 1.0)]


def test_union_and_sum_per_name():
    assert xplane.union_seconds(OPS) == pytest.approx(3.0)
    assert xplane.seconds_by_name(OPS) == pytest.approx(
        {"fusion.1": 1.0, "kernel": 1.5, "copy": 1.0})


def test_clip_and_gaps():
    ops = xplane.clip(OPS, 0.25, 5.5)
    assert xplane.union_seconds(ops) == pytest.approx(1.25 + 0.5 + 0.5)
    gaps = sorted(xplane.gaps(ops, 0.0, 5.5))
    assert gaps == pytest.approx([(0.0, 0.25), (1.5, 1.5), (3.5, 1.5)])
    assert xplane.gaps(ops, 0.0, 5.5)[-1] == pytest.approx((0.0, 0.25))


def test_ops_land_in_their_module_and_tick():
    mods = [("jit_decode_sample", 0.0, 2.0), ("jit__lambda_", 2.9, 1.0)]
    assert xplane.module_of(OPS, mods) == [
        "jit_decode_sample", "jit_decode_sample", "jit__lambda_", None]
    ticks = [types.SimpleNamespace(tick=7, start=100.0, wall_s=2.0),
             types.SimpleNamespace(tick=8, start=102.9, wall_s=1.0)]
    data = types.SimpleNamespace(
        trace={"devices": {"/device:TPU:0": {"ops": OPS, "modules": mods}}},
        trace_window=(0.0, 6.0), trace_offset=-100.0, ticks=ticks)
    secs = work.kernel_seconds_by_tick(data, re.compile("decode"),
                                       re.compile("^kernel$"))
    assert secs == pytest.approx({7: 1.0, 8: 0.0})


def test_roofline_share_is_needed_time_over_kernel_time():
    data = types.SimpleNamespace(peaks={"bf16_flops_per_s": 100.0,
                                        "hbm_bytes_per_s": 10.0})
    needed = {7: (50.0, 2.0)}  # 0.5 s of compute, 0.2 s of bytes
    assert work.roofline(data, needed, {7: 2.0}) == pytest.approx(25.0)
    assert work.roofline(data, needed, {7: 0.0}) is None


def test_decode_contexts_follow_the_decode_spans():
    spans = [types.SimpleNamespace(name="decode", rid=3,
                                   attrs={"tick": 10, "end_tick": 12}),
             types.SimpleNamespace(name="decode", rid=4, attrs={"tick": 11})]
    ranges = work.decode_ranges(spans, {3: 100, 4: 7})
    assert work.contexts_at(ranges, 10) == [101]
    assert sorted(work.contexts_at(ranges, 12)) == [9, 103]
    assert work.contexts_at(ranges, 13) == [10]
