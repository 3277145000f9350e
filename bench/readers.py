"""Arithmetic shared by the per-layer metric readers in ``bench/metrics``.

Each reader takes the run's records (see ``harness.layer_records``) and
returns a number, or None where it finds nothing to read.
"""
from __future__ import annotations

import numpy as np

from bench import opcount, stats
from bench import trace as trace_mod

DECODE = "_decode_impl"
PREFILL = "_prefill_packed_impl"
PREFILL_HEAD = "_prefill_head_impl"
KERNEL = "entangled_matmul"


def slot_occupancy_pct(rec):
    occ = [a for t, a in rec["steps"] if rec["w0"] <= t < rec["w1"] and a]
    if not occ:
        return None
    return 100.0 * float(np.mean(occ)) / rec["serve"]["max_batch"]


def step_ms(rec, fragment: str):
    red = rec["trace"]
    if red is None:
        return None
    n, s = trace_mod.programs(red, fragment)
    return s / n * 1e3 if n else None


def idle_pct(rec):
    red = rec["trace"]
    if red is None or not red["devices"] or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def kernel_roofline_pct(rec):
    """Least time the chip needs for the protected sites' plain GEMM work
    of every traced program call, over the summed entangled-kernel time."""
    red = rec["trace"]
    if red is None:
        return None
    ktime = sum(k["device_s"] for name, k in red["kernels"].items()
                if KERNEL in name)
    if ktime <= 0:
        return None
    cell, serve, peaks = rec["cell"], rec["serve"], rec["peaks"]
    prot = [s for s in cell.arch.gemm_sites(cell.config) if s["protected"]]
    layers = [s for s in prot if s["site"] != "head"]
    head = [s for s in prot if s["site"] == "head"]
    bw = peaks["hbm_bytes_per_s"]
    peak = peaks["int8_ops_per_s"]
    least = 0.0
    n, _ = trace_mod.programs(red, DECODE)
    least += n * opcount.kernel_least_time(prot, serve["max_batch"], peak,
                                           bw)
    n, _ = trace_mod.programs(red, PREFILL)
    least += n * opcount.kernel_least_time(layers, serve["token_budget"],
                                           peak, bw)
    n, _ = trace_mod.programs(red, PREFILL_HEAD)
    rows = serve.get("prefill_batch") or serve["max_batch"]
    least += n * opcount.kernel_least_time(head, rows, peak, bw)
    return 100.0 * least / ktime


def prefill_mfu_pct(rec):
    """Model operations of the traced prefill calls' prompt tokens over
    their device time at the cell's peak. The attention of a packed token
    is counted at the token-weighted mean prompt position of the requests
    whose prefill finished in the window."""
    red = rec["trace"]
    if red is None:
        return None
    n, dev_s = trace_mod.programs(red, PREFILL)
    tokens = red["counters"]["packed_tokens"]
    if not n or dev_s <= 0 or tokens <= 0:
        return None
    cell = rec["cell"]
    sites = [s for s in cell.arch.gemm_sites(cell.config)
             if s["site"] != "head"]
    lens = [r["prompt_len"] for r in rec["requests"]
            if r["t_first"] is not None
            and rec["w0"] <= r["t_first"] < rec["w1"]]
    if not lens:
        return None
    mean_pos = sum(x * (x - 1) / 2 for x in lens) / sum(lens)
    ops = tokens * (opcount.gemm_ops_per_token(sites)
                    + cell.arch.context_ops(cell.config, mean_pos))
    return 100.0 * ops / (dev_s * rec["peak_ops"])


def window_mfu_pct(rec):
    """Model operations of every token the window processed, over the
    window at the cell's peak."""
    w0, w1 = rec["w0"], rec["w1"]
    ops = rec["model_ops"](w0, w1)
    if ops <= 0:
        return None
    return 100.0 * ops / ((w1 - w0) * rec["peak_ops"])


def queue_wait_ms(rec, q: float):
    """The q-quantile over the requests due in the window of the end of
    the engine step after which each left the queue, minus its due time;
    a request still queued when the run ended counts with its wait by
    then."""
    due = [r for r in rec["requests"] if r["win"]]
    if not due:
        return None
    return stats.nearest_rank(
        [((r["t_left"] if r["t_left"] is not None else rec["t_end"])
          - r["due"]) * 1e3 for r in due], q)
