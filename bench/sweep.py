"""Rate sweep for an open-loop cell, on the chip, to find the highest
arrival rate the system sustains:

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates 0.1 0.2 0.3

Each rate runs the cell's traffic with its ``rate_per_s`` replaced, in one
process (so that set-up compiles once), for a window of ``--seconds``.
Prints one JSON line per rate: the output tokens/s offered (rate x mean
output length) and served, TTFT percentiles, and how many requests due in
the window had no first token at its close. A rate is sustained while the
served rate keeps up with the offered one and TTFT does not grow from the
window's first half to its second. The cell's rate is then fixed at about
four fifths of the highest sustained one; the benchmark never searches.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

import run


def summary(out_recs, t_open: float, t_close: float) -> dict:
    due = [r for r in out_recs if r.due is not None and t_open <= r.due < t_close]
    ttft = [(r.first if r.first is not None and r.first <= t_close
             else t_close) - r.due for r in due]
    half = t_open + (t_close - t_open) / 2
    early = [t for r, t in zip(due, ttft) if r.due < half]
    late = [t for r, t in zip(due, ttft) if r.due >= half]
    served = sum(sum(t_open <= t <= t_close for t in r.times) for r in out_recs)
    return {"due": len(due),
            "no_first_token": sum(r.first is None or r.first > t_close
                                  for r in due),
            "served_tokens_per_s": served / (t_close - t_open),
            "ttft_median_s": statistics.median(ttft) if ttft else None,
            "ttft_max_s": max(ttft) if ttft else None,
            "ttft_median_first_half_s": statistics.median(early) if early else None,
            "ttft_median_second_half_s": statistics.median(late) if late else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    spec = run.resolve(args.workload)
    if spec["traffic"]["arrival"] != "poisson":
        raise SystemExit("a sweep is for open-loop cells")
    devices = run.check_device(spec["cell"]["chips"])
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    seen = {}

    def keep(data):
        seen.update(recs=data.records, t_open=data.t_open,
                    t_close=data.t_close)

    for rate in args.rates:
        mix = dict(spec["traffic"], rate_per_s=rate)
        olen = statistics.mean(j["max_tokens"] for j in run.jobs_of(
            run.load_module(os.path.join(run.BENCH, "traffic",
                                         mix["arrival"] + ".py")).schedule(
                mix, 2, args.seed, args.seconds)))
        one = dict(spec, traffic=mix, end_to_end=[])
        out = run.run_cell(one, args.seed, args.seconds, False, devices,
                           on_data=keep)
        line = {"workload": args.workload, "rate_per_s": rate,
                "offered_tokens_per_s": rate * olen,
                "correct": out["correct"]}
        line.update(summary(seen["recs"], seen["t_open"], seen["t_close"]))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
