"""Benchmark harness — one module per paper table/figure.

  table1_bitwidth      paper Table I (l, k, bitwidths; exact reproduction)
  complexity_model     paper Sec. IV op-count model + claims
  fig2_conv_throughput paper Fig. 2 (conv throughput, NE vs checksum)
  gemm_overhead        Sec. IV GEMM cost, measured (beyond-paper)
  kernel_micro         codec bandwidth + fused-vs-separate ledger
  serve_throughput     batched vs per-slot engine tok/s + entangled-head
                       overhead, plus the prompt-heavy admission wave
                       (bucketed batched prefill >= 2x per-request gate)
                       (writes BENCH_serve.json)
  roofline_report      dry-run three-term roofline summary (if artifacts)

Prints ``name,us_per_call,derived`` CSV and writes every record to
``BENCH_<mode>.json`` (the artifact CI uploads). ``--quick`` shrinks
problem sizes; ``--smoke`` is the CI mode — the validation-bearing subsets
(table1, complexity, gemm, micro incl. the fused-codec ledger) at small
sizes, suitable for CPU interpret mode.
"""
from __future__ import annotations

import argparse
import sys

import jax

jax.config.update("jax_enable_x64", True)  # exact f64 conv (paper uses
# ippsConv_64f); benchmarks run in their own process, tests are unaffected.

from benchmarks.common import emit, write_bench_json  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: validation subsets at small sizes")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    enable_compile_cache()

    print("name,us_per_call,derived")
    ok = True
    quick = args.quick or args.smoke

    def want(name):
        if args.only:
            return name in args.only.split(",")
        if args.smoke:
            return name in ("table1", "complexity", "gemm", "micro", "serve")
        return True

    if want("table1"):
        from benchmarks import table1_bitwidth

        ok &= table1_bitwidth.run(emit)
    if want("complexity"):
        from benchmarks import complexity_model

        ok &= complexity_model.run(emit)
    if want("fig2"):
        from benchmarks import fig2_conv_throughput

        n = 50_000 if quick else 200_000
        ks = (100, 1000) if quick else (100, 1000, 4500)
        fig2_conv_throughput.run(emit, n_in=n, kernel_sizes=ks)
    if want("gemm"):
        from benchmarks import gemm_overhead

        gemm_overhead.run(emit, sizes=(128, 256) if quick else (128, 256, 512))
    if want("micro"):
        from benchmarks import kernel_micro

        fusion_sizes = (
            ((4, 64, 64, 64), (4, 128, 64, 128)) if quick else None
        )
        ok &= kernel_micro.run(emit, n=1 << (18 if quick else 20),
                               fusion_sizes=fusion_sizes)
    if want("serve"):
        from benchmarks import serve_throughput

        # not shrunk under --quick/--smoke: waves shorter than ~16x8 tokens
        # are dispatch-noise-dominated and make the 2x gate flaky
        ok &= serve_throughput.run(emit)
    if want("roofline"):
        from benchmarks import roofline_report

        roofline_report.run(emit)

    mode = "smoke" if args.smoke else ("quick" if args.quick else "full")
    if args.only:  # a subset run must not masquerade as a full artifact
        mode = "only-" + args.only.replace(",", "-")
    path = write_bench_json(mode, {"mode": mode, "ok": bool(ok)})
    print(f"[bench] wrote {path}", file=sys.stderr)

    if not ok:
        print("benchmark_validation,0.0,FAILED", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
