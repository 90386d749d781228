"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time, time per operation, idle gaps, and
the host marks that put the trace on the benchmark's own clock.

Reading the file is :func:`load`; everything after it works on plain
tuples, so it can be checked on a synthetic event list.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "marks":
    {name: start_s}}`` from one trace. An op or module is (name, start_s,
    dur_s); times are seconds on the trace's clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {"devices": {}, "marks": {}}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                            for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["marks"].setdefault(e.name, e.start_ns * 1e-9)
    return out


def clip(events, lo: float, hi: float) -> list:
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union_seconds(events) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for _, s, d in sorted(events, key=lambda e: e[1]):
        a, b = max(s, end), s + d
        if b > a:
            total += b - a
        end = max(end, b)
    return total


def gaps(events, lo: float, hi: float) -> list:
    """(start, length) of every stretch of [lo, hi] no event covers,
    longest first."""
    out, cur = [], lo
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if s > cur:
            out.append((cur, s - cur))
        cur = max(cur, s + d)
    if hi > cur:
        out.append((cur, hi - cur))
    return sorted(out, key=lambda g: -g[1])


def seconds_by_name(events) -> dict:
    out: dict = {}
    for name, _, d in events:
        out[name] = out.get(name, 0.0) + d
    return out


def module_of(ops, modules) -> list:
    """For each op, the name of the module event whose interval holds
    the op's start (None when none does)."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for _, s, _d in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][1] + mods[i][2]:
            out.append(mods[i][0])
        else:
            out.append(None)
    return out
