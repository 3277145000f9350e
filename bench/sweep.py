#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate the system
sustains without a growing backlog.

    python bench/sweep.py --workload qwen2-7b.chat.ft-all \\
        --rates 1.5,2,2.5,3,3.5 --seconds 30 --seed 7

One process, one engine: each rate runs the cell's mix with that rate
through its ramp and a window of ``--seconds``, then drains. Per rate it
prints the requests due in the window, the backlog (requests submitted and
not finished) at the window's start and end, the completed output tokens
per second, and the p90 of the time to first token. The chosen rate is
written into the mix's file by hand.
"""
import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes only
# inside its checkout and its own temporary directories
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def backlog(recs, t: float) -> int:
    return sum(1 for r in recs if r["due"] + r["lag"] <= t
               and (r["t_done"] is None or r["t_done"] > t))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    from bench import harness, stats, traffic
    from bench.run import compile_cache

    compile_cache()
    st = harness.setup(args.workload, args.seed, T_PROC0)
    out = []
    for k, rate in enumerate(float(x) for x in args.rates.split(",")):
        st.cell.mix = dict(st.cell.mix, rate_rps=rate)
        specs = traffic.generate(st.cell.mix, args.seed + k, args.seconds,
                                 st.cfg.vocab_size)
        run = harness.drive(st.engine, specs, st.cell, args.seconds,
                            harness.Tracer(False, 0), st.counter)
        recs = harness.request_records(run)
        due = [r for r in recs if r["win"]]
        toks = sum(1 for r in recs for t in r["tok_times"]
                   if run["w0"] <= t < run["w1"])
        row = {"rate_rps": rate, "due_in_window": len(due),
               "backlog_at_window_start": backlog(recs, run["w0"]),
               "backlog_at_window_end": backlog(recs, run["w1"]),
               "tokens_per_s": toks / args.seconds,
               "ttft_p90_ms": stats.nearest_rank(
                   stats.ttft_ms(due, run["t_end"]), 0.90),
               "ttft_p50_ms": stats.nearest_rank(
                   stats.ttft_ms(due, run["t_end"]), 0.50),
               "itl_p95_ms": (stats.nearest_rank(stats.itl_ms(due), 0.95)
                              if stats.itl_ms(due) else None),
               "unfinished_due": sum(1 for r in due
                                     if r["status"] != "done"),
               "drain_s": run["t_end"] - run["w1"]}
        print(json.dumps(row), flush=True)
        out.append(row)
    print(json.dumps({"sweep": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
