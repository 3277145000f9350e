"""Least time for the dense (non-grouped) protected sites' plain GEMM work over the summed device time of kernels named exactly entangled_matmul_pallas, from the trace, %."""
from bench import opcount, readers
from bench import trace as trace_mod

KERNEL = "entangled_matmul_pallas"


def read(rec):
    red = rec["trace"]
    grouped_fn = getattr(rec["cell"].arch, "grouped_sites", None)
    if red is None or grouped_fn is None:
        return None
    ktime = sum(k["device_s"] for name, k in red["kernels"].items()
                if name == KERNEL)
    if ktime <= 0:
        return None
    cell, serve, peaks = rec["cell"], rec["serve"], rec["peaks"]
    grouped = {s for s, _, _ in grouped_fn(cell.config)}
    prot = [s for s in cell.arch.gemm_sites(cell.config)
            if s["protected"] and s["site"] not in grouped]
    layers = [s for s in prot if s["site"] != "head"]
    head = [s for s in prot if s["site"] == "head"]
    peak, bw = peaks["int8_ops_per_s"], peaks["hbm_bytes_per_s"]
    least = 0.0
    n, _ = trace_mod.programs(red, readers.DECODE)
    least += n * opcount.kernel_least_time(prot, serve["max_batch"], peak, bw)
    n, _ = trace_mod.programs(red, readers.PREFILL)
    least += n * opcount.kernel_least_time(layers, serve["token_budget"],
                                           peak, bw)
    n, _ = trace_mod.programs(red, readers.PREFILL_HEAD)
    rows = serve.get("prefill_batch") or serve["max_batch"]
    least += n * opcount.kernel_least_time(head, rows, peak, bw)
    return 100.0 * least / ktime
