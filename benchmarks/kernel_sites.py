#!/usr/bin/env python3
"""Time the fused entangled GEMM on the chip at every protected site of a
benchmark configuration, one kernel call at a time.

    python benchmarks/kernel_sites.py --out artifacts/sites.json
    python benchmarks/kernel_sites.py --sweep --out artifacts/sweep.json

Each site of ``bench/configs/qwen2-7b.json`` and ``falcon-mamba-7b.json``
runs ``entangled_matmul_pallas`` (packed int8 weights, int8 activations,
``fuse_epilogue=True``: the serving path) at decode rows (``max_batch``
per stream group) and packed-prefill rows (``token_budget`` per group)
with the blocks ``repro.ft.registry.default_blocks`` gives on
``pallas_tpu``. ``--sweep`` adds weight tiles and row counts around them;
``--check`` first compares each site's kernel with the jnp oracle.
A call is timed on the device: one jitted scan runs the kernel on
``REPS`` distinct activation blocks under the profiler, and the kernel's
device time is read from the trace (``bench/trace.py``) and divided by the
number of kernel events found there, so neither the host's dispatch nor a
hoisted or elided call enters the time. Writes one JSON record per timed
call; prints one line each. Needs a TPU.
"""
from __future__ import annotations

import argparse
import glob
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import trace  # noqa: E402

from repro.core.plan import make_plan  # noqa: E402
from repro.ft.registry import default_blocks  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.entangled_matmul import entangled_matmul_pallas  # noqa

CONFIGS = ("qwen2-7b", "falcon-mamba-7b")


def sites(cfg: dict) -> list:
    """(site, K, N) of every protected GEMM of a benchmark configuration."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    if cfg["arch"] == "mamba1":
        di = cfg["intermediate_size"]
        return [("in_proj", d, 2 * di), ("out_proj", di, d), ("head", d, v)]
    hd = d // cfg["num_attention_heads"]
    kv, ff = cfg["num_key_value_heads"] * hd, cfg["intermediate_size"]
    return [("q", d, d), ("k", d, kv), ("v", d, kv), ("o", d, d),
            ("gate", d, ff), ("up", d, ff), ("down", ff, d), ("head", d, v)]


def load(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


REPS = 16  # kernel calls per timed scan


def time_call(B: int, K: int, N: int, blocks: dict, *,
              packed: bool = True) -> tuple:
    """Device seconds per call of the fused kernel at [M, B, K] x [K, N],
    and host wall seconds per call of the untraced scan (an upper bound)."""
    plan = make_plan(4)
    key = jax.random.PRNGKey(0)
    rows = -(-B // blocks["bb"]) * blocks["bb"]  # as ops pads them
    cs = jax.random.randint(key, (REPS, plan.M, rows, K), -127, 128,
                            jnp.int8)
    g = jax.random.randint(key, (K // 4 if packed else K, N), -2 ** 31,
                           2 ** 31 - 1, jnp.int32)
    if not packed:
        g = g.astype(jnp.int8)

    @jax.jit
    def calls(cs, g):
        def body(acc, c):
            out = entangled_matmul_pallas(c, g, plan=plan, fuse_epilogue=True,
                                          packed=packed, **blocks)
            return acc + out[:, 0, 0].sum(), None
        return jax.lax.scan(body, jnp.int32(0), cs)[0]

    calls(cs, g).block_until_ready()  # compile
    t0 = time.perf_counter()
    calls(cs, g).block_until_ready()
    wall = (time.perf_counter() - t0) / REPS
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            calls(cs, g).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        durs = [dur for dev in trace.load(glob.glob(
                    f"{d}/**/*.xplane.pb", recursive=True)[0])[
                    "devices"].values()
                for name, _, dur in dev["ops"]
                if trace.kernel_name(name) == "entangled_matmul_pallas"]
    if len(durs) != REPS:
        raise SystemExit(f"{len(durs)} kernel events in the trace at "
                         f"{(B, K, N)} {blocks}, expected {REPS}")
    return sum(durs) / len(durs) / 1e9, wall


def check(B: int, K: int, N: int, blocks: dict) -> None:
    """The compiled kernel against the jnp oracle on the chip, bit for bit,
    healthy and with stream 1 failed (activations on the eq.-13 grid)."""
    plan = make_plan(4)
    lim = max(1, plan.max_output_magnitude // (K * 127))
    key = jax.random.PRNGKey(1)
    c = jax.random.randint(key, (plan.M, B, K), -lim, lim + 1, jnp.int8)
    g = jax.random.randint(key, (K // 4, N), -2 ** 31, 2 ** 31 - 1,
                           jnp.int32)
    for failed in (0, 1):
        got, want = (ops.entangled_matmul(
            c, g, plan, fuse_epilogue=True, failed=failed, packed=True,
            blocks=blocks, backend=be) for be in ("pallas_tpu", "reference"))
        if not bool(jnp.array_equal(got, want)):
            raise SystemExit(f"kernel != oracle at {(B, K, N)} {blocks} "
                             f"failed={failed}")


def cases(sweep: bool):
    """(config, site, rows per group, K, N, blocks, packed) to time."""
    for name in CONFIGS:
        cfg = load(name)
        s = cfg["serve"]
        dec, pre = s["max_batch"] // s["ft_M"], s["token_budget"] // s["ft_M"]
        for site, K, N in sites(cfg):
            # the head runs on decode and admission rows, never packed
            for B in (dec,) if site == "head" else (dec, pre):
                bl = default_blocks(B, K, N, "pallas_tpu")
                yield name, site, B, K, N, bl, True
            if site == "head":  # the wrapper's bn = bk = 128 of old
                yield name, site, dec, K, N, {**bl, "bn": 128, "bk": 128}, True
    if not sweep:
        return
    # weight tiles at decode rows; row counts across the 1-2 MiB tiles
    for name, site, B, K, N in (
            ("falcon-mamba-7b", "in_proj", 16, 4096, 16384),
            ("qwen2-7b", "gate", 8, 3584, 18944),
            ("qwen2-7b", "k", 8, 3584, 512)):
        for bk in (512, 1792, 2048, 3584):
            for bn in (256, 512, 1024, 2048):
                if K % bk == 0 and bk * bn <= (4 << 20) and bn <= N:
                    yield name, site, B, K, N, {"bb": 32, "bn": bn,
                                                "bk": bk}, True
    for B in (16, 64, 128, 512):
        bl = default_blocks(B, 4096, 16384, "pallas_tpu")
        for b in (bl, {"bb": bl["bb"], "bn": 256, "bk": 256}):
            yield "falcon-mamba-7b", "in_proj", B, 4096, 16384, b, True
    # packed-prefill tiles around the policy's (bn, bk)
    for name, site, K, N, tiles in (
            ("qwen2-7b", "gate", 3584, 18944,
             ((256, 256), (512, 512), (1024, 512), (1536, 1792))),
            ("qwen2-7b", "down", 18944, 3584,
             ((256, 256), (512, 512), (1792, 512), (3584, 512))),
            ("qwen2-7b", "q", 3584, 3584, ((256, 256), (1792, 1792))),
            ("qwen2-7b", "k", 3584, 512, ((256, 256), (512, 1792))),
            ("falcon-mamba-7b", "in_proj", 4096, 16384,
             ((1024, 2048), (2048, 1024))),
            ("falcon-mamba-7b", "out_proj", 8192, 4096,
             ((256, 256), (1024, 2048)))):
        for bn, bk in tiles:
            yield name, site, 512, K, N, {"bb": 128, "bn": bn, "bk": bk}, True
    # the weights as int8 in HBM, not packed: the unpack's share
    yield ("falcon-mamba-7b", "in_proj", 16, 4096, 16384,
           default_blocks(16, 4096, 16384, "pallas_tpu"), False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="first compare each site's kernel with the oracle")
    ap.add_argument("--out", default="artifacts/kernel_sites.json")
    a = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    recs, seen = [], set()
    for name, site, B, K, N, bl, packed in cases(a.sweep):
        k = (name, site, B, K, N, tuple(sorted(bl.items())), packed)
        if k in seen:
            continue
        seen.add(k)
        if a.check and packed:
            check(B, K, N, bl)
        t, wall = time_call(B, K, N, bl, packed=packed)
        steps = (-(-B // bl["bb"])) * (-(-N // bl["bn"])) * (K // bl["bk"])
        rec = dict(config=name, site=site, rows=B, K=K, N=N, packed=packed,
                   **bl, ms=round(t * 1e3, 4),
                   wall_ms=round(wall * 1e3, 4), steps=steps,
                   us_per_step=round(t * 1e6 / steps, 4),
                   weight_gb_s=round(K * N / t / 1e9, 1),
                   device=dev.device_kind)
        recs.append(rec)
        print(json.dumps(rec), flush=True)
    out = pathlib.Path(a.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(recs, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
