"""Least time of the held experts' grouped GEMMs (int8 weights read once a call, the expected routed rows' operations) over the summed entangled_matmul_grouped kernel time, from the trace, %."""
from bench import readers
from bench import trace as trace_mod

KERNEL = "entangled_matmul_grouped"


def read(rec):
    red = rec["trace"]
    least_fn = getattr(rec["cell"].arch, "grouped_least_time", None)
    if red is None or least_fn is None:
        return None
    ktime = sum(k["device_s"] for name, k in red["kernels"].items()
                if KERNEL in name)
    if ktime <= 0:
        return None
    c, serve, peaks = rec["config"], rec["serve"], rec["peaks"]
    peak, bw = peaks["int8_ops_per_s"], peaks["hbm_bytes_per_s"]
    least = 0.0
    for fragment, rows in ((readers.DECODE, serve["max_batch"]),
                           (readers.PREFILL, serve["token_budget"])):
        n, _ = trace_mod.programs(red, fragment)
        least += n * least_fn(c, rows, peak, bw)
    return 100.0 * least / ktime
