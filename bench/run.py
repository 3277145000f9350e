#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for. Set-up makes the weights from the seed on the device, starts the
program's ``ServeEngine`` and drives every program the cell uses once
(from JAX's persistent compilation cache after the first run); then the
cell's traffic runs through an uncounted ramp, a window of ``--seconds``
is measured, and the check in ``bench/check.py`` decides ``correct``. The
last line of standard output is the result object; with ``--trace 1`` it
carries the per-layer metrics and the device breakdown instead of the
end-to-end metrics. Exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes only
# inside its checkout and its own temporary directories
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def compile_cache() -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else at the fixed path ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "BENCHMARK.json").is_file() or not (ROOT / "src").is_dir():
        print("[bench] FAIL: run from a checkout that holds BENCHMARK.json "
              "and the program under src/", file=sys.stderr)
        return 2
    from bench import harness

    try:
        compile_cache()
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_PROC0)
    except harness.NoChip as e:
        print(f"[bench] FAIL: {e}", file=sys.stderr)
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
