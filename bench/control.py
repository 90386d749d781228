"""Readings that set a cell's limits for ``correct``, on the chip:

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11 12 13

For each seed, in one process (so that set-up compiles once), one run of
the cell as ``run.py`` makes it, then the lower-precision control on the
same sampled requests: the plain reference with every matmul operand
rounded to float8 e4m3 (the configuration serves bfloat16), put in the
program's place in the comparison that decides ``correct``. Prints one
JSON line per seed with the program's compared numbers, the control's,
and whether the control came out correct (it must not); a limit lies
above the first and below the second. The benchmark's own runs never run
the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    spec = run.resolve(args.workload)
    devices = run.check_device(spec["cell"]["chips"])
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    for seed in args.seeds:
        out = run.run_cell(spec, seed, args.seconds, False, devices,
                           control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": out["correct"],
                          "program": out["program"],
                          "control": {k: v["value"]
                                      for k, v in out["checks"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
