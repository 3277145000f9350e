#!/usr/bin/env python3
"""Readings that set the limits of a cell's check.

    python bench/control.py --workload qwen2-7b.chat.ft-all \\
        --seeds 11,12,13 --seconds 20

For each seed, in one process: a run of the cell as ``run.py`` makes it
(set-up, ramp, window, drain), then the reference over the same sample of
finished requests in the configuration's precision and, as the control,
one precision step lower (``bench/models/common.py:site_levels``), both
through the same limits. Prints per seed each number the configuration
compares, the program's (the lower reading) beside the control's (the
upper reading), and whether each came out correct: the control must not.
The benchmark's own runs never run the control.
"""
import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes only
# inside its checkout and its own temporary directories
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    args = ap.parse_args()
    from bench import harness
    from bench.run import compile_cache

    compile_cache()
    rows = []
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.monotonic()
        res = harness.run_cell(args.workload, seed, args.seconds, False, t0,
                               control=True)
        row = {"seed": seed, "correct": res["correct"],
               "control_correct": res["control_correct"],
               "program": res["checks"], "control": res["control_checks"],
               "metrics": res["metrics"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
        gc.collect()
    print(json.dumps({"control": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
