#!/usr/bin/env python3
"""Chip smoke test: the entangled serving path at full width on a TPU.

    python chip_smoke.py               # one chip (the default phase)
    python chip_smoke.py --four-chips  # the slot batch sharded over 4 chips

Default phase, one process on one chip:

  1. kernels   the compiled Pallas kernels (``pallas_tpu`` backend) at the
               model's widths on a small batch, against an int64 numpy
               reference, healthy and with stream 1 fail-stopped;
  2. entangle  ``llama3.2-1b`` at full width (random weights from
               ``SEED``) behind ``ServeEngine`` with ``ft_mode='entangle'``
               and ``ft_scope='all'``: one wave of 8 requests (prompts of
               32-512 tokens, 32 new tokens each), healthy and then with
               ``failed_group=1`` injected on every step. Before the
               engine starts, its two start-up quantizations (the layers'
               q8 copies, the packed head) run once on their own, so the
               device memory after each is printed apart;
  3. ft-off    the same wave with ``ft_mode='none'``.

It fails unless the kernels match the reference, the healthy and injected
tokens are identical (exact roll-forward), no protected site missed its
startup plan (``CompiledPlans.misses == 0``) and every plan ran on
``pallas_tpu``. The share of tokens on which the protected and ft-off runs
agree is printed for information only. The last line of standard output
is one JSON object naming the device; any failure exits non-zero before
it, and so does a host where JAX finds no TPU.

``--four-chips`` runs only the multi-chip serving path: the same wave on a
4-chip mesh (the engine's default on a multi-device host), healthy and
injected, compared with each other and with the wave on one of the four
chips in the same process.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.plan import make_plan  # noqa: E402
from repro.ft import prepare_params  # noqa: E402
from repro.ft.quantize import (activation_budget,  # noqa: E402
                               quantize_weight_stacked)
from repro.kernels import ops  # noqa: E402
from repro.kernels.codec import pack_int8  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.serve.engine import Request, ServeConfig, ServeEngine  # noqa: E402

ARCH = "llama3.2-1b"
SEED = 0  # weights, prompts and the kernel check's operands
N_REQUESTS, MAX_NEW, MAX_BATCH, FT_M = 8, 32, 8, 4
PROMPT_MIN, PROMPT_MAX = 32, 512
MAX_SEQ = 640  # >= PROMPT_MAX + MAX_NEW
PREFILL_CHUNK = 128  # token-packed admission: one [8, 128] prefill program
FAILED = 1

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event: str, secs: float, **_):
    if event in COMPILE_EVENTS:
        _compile_s[0] += secs


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def memory() -> dict:
    """Device 0's allocator counters (bytes), where the backend has them."""
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


class Phase:
    """Wall and compile (trace + lower + compile) seconds of one phase, and
    device 0's memory after it."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), _compile_s[0]
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        comp = _compile_s[0] - self.c0
        status = "FAILED" if exc[0] else "done"
        mem = " ".join(f"{k}={v}" for k, v in memory().items())
        log(f"phase {self.name} {status}: wall_s={wall} compile_s={comp} "
            f"{mem}")
        return False


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[chip_smoke] FAIL: {what}")


# ------------------------------------------------------------- kernels ----

def kernel_phase() -> None:
    """Compiled kernels at the model's widths vs an int64 numpy reference.
    ``head/4`` is one device's column slice of the head on a 4-chip mesh:
    32064 columns end in a partial 128-lane block."""
    plan = make_plan(FT_M)
    rng = np.random.default_rng(SEED)
    b = 8  # rows per stream

    def rand(lim, shape):
        return rng.integers(-lim, lim + 1, size=shape)

    for name, K, N in (("q", 2048, 2048), ("gate", 2048, 8192),
                       ("down", 8192, 2048), ("head/4", 2048, 32064)):
        c = rand(activation_budget(plan, K), (FT_M, b, K))
        g = rand(127, (K, N))
        want = np.einsum("mbk,kn->mbn", c, g)
        gp = pack_int8(jnp.asarray(g, jnp.int32), axis=0)
        for failed in (None, FAILED):
            got = ops.entangled_matmul(
                jnp.asarray(c, jnp.int8), gp, plan, fuse_epilogue=True,
                failed=failed, packed=True, backend="pallas_tpu")
            check(np.array_equal(np.asarray(got), want),
                  f"entangled_matmul {name} K={K} N={N} failed={failed}")

    E, K, N = 8, 2048, 512
    c = rand(activation_budget(plan, K), (FT_M, E, b, K))
    g = rand(127, (E, K, N))
    want = np.einsum("meck,ekn->mecn", c, g)
    got = ops.entangled_matmul_grouped(
        jnp.asarray(c, jnp.int8), jnp.asarray(g, jnp.int32), plan,
        fuse_epilogue=True, failed=FAILED, backend="pallas_tpu")
    check(np.array_equal(np.asarray(got), want),
          f"entangled_matmul_grouped E={E} K={K} N={N}")

    D, T, kf = 256, 1024, 4
    x = rand(1000, (FT_M, 1, D, T))
    w = rand(127, (D, kf))
    xp = np.pad(x, ((0, 0), (0, 0), (0, 0), (kf - 1, 0)))
    want = sum(w[None, None, :, j:j + 1] * xp[..., j:j + T]
               for j in range(kf))
    got = ops.entangled_conv1d(
        jnp.asarray(x, jnp.int32), jnp.asarray(w, jnp.int32), plan,
        fuse_epilogue=True, failed=FAILED, backend="pallas_tpu")
    check(np.array_equal(np.asarray(got), want),
          f"entangled_conv1d D={D} T={T} K_f={kf}")
    log("kernels match the int64 reference (dense q/gate/down/head/4, "
        "grouped, conv1d; healthy and failed stream 1)")


# -------------------------------------------------------------- serving ----

def make_requests(vocab: int) -> list:
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, size=N_REQUESTS)
    return [Request(rid=i, max_new=MAX_NEW,
                    prompt=rng.integers(0, vocab, size=int(n)).astype(
                        np.int32))
            for i, n in enumerate(lens)]


def serve_config(ft_mode: str) -> ServeConfig:
    return ServeConfig(
        max_batch=MAX_BATCH, max_seq=MAX_SEQ, ft_mode=ft_mode, ft_M=FT_M,
        ft_scope="all", prefill_chunk=PREFILL_CHUNK,
        token_budget=MAX_BATCH * PREFILL_CHUNK)


def wave(eng: ServeEngine, vocab: int, failed) -> dict:
    reqs = make_requests(vocab)
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_steps=10_000, failed_group=failed)
    check(all(r.status == "done" and len(r.out) == MAX_NEW for r in reqs),
          f"wave (failed_group={failed}) did not complete every request")
    out = {r.rid: np.asarray(r.out) for r in reqs}
    check(all(((o >= 0) & (o < vocab)).all() for o in out.values()),
          "token ids outside the vocabulary")
    return out


def check_entangled_engine(eng: ServeEngine) -> None:
    check(eng.plans is not None and len(eng.plans.plans()) > 0,
          "no compiled protection plans")
    backends = {p.backend for p in eng.plans.plans()}
    check(backends == {"pallas_tpu"},
          f"protected sites resolved to backends {backends}")
    check(eng.plans.misses == 0,
          f"CompiledPlans.misses = {eng.plans.misses} (startup census gap)")


def same_tokens(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(
        np.array_equal(a[k], b[k]) for k in a)


def agreement(a: dict, b: dict) -> float:
    same = sum(int((a[k] == b[k]).sum()) for k in a)
    return same / sum(len(v) for v in a.values())


def serving_phase(devices, label: str) -> dict:
    """Entangled engine on ``devices``: healthy wave, then injected wave.
    The weights are made in place, replicated on ``devices``."""
    cfg = get_config(ARCH)
    model = get_model(cfg)
    with Phase(f"{label}/init"):
        on = NamedSharding(Mesh(np.asarray(devices), ("d",)), P())
        init = jax.jit(lambda k: model.init(k, cfg, max_seq=MAX_SEQ),
                       out_shardings=on)
        params = jax.block_until_ready(init(jax.random.PRNGKey(SEED)))
    # the engine's start-up quantizations, each alone (the engine reuses
    # their compiled programs): device memory after each, then freed
    with Phase(f"{label}/quantize-layers"):
        q = jax.block_until_ready(prepare_params(params, scope="all"))
    with Phase(f"{label}/quantize-head"):
        h = jax.block_until_ready(quantize_weight_stacked(
            model.head_weights(params, cfg), packed=True))
    del q, h
    with Phase(f"{label}/engine-startup"):
        eng = ServeEngine(cfg, serve_config("entangle"), params,
                          devices=devices)
    with Phase(f"{label}/healthy"):
        healthy = wave(eng, cfg.vocab_size, None)
    with Phase(f"{label}/failed_group={FAILED}"):
        injected = wave(eng, cfg.vocab_size, FAILED)
    check_entangled_engine(eng)
    check(same_tokens(healthy, injected),
          f"{label}: tokens differ between healthy and failed_group="
          f"{FAILED} (roll-forward not exact)")
    log(f"{label}: healthy == failed_group={FAILED} on all "
        f"{N_REQUESTS * MAX_NEW} tokens; plans misses = "
        f"{eng.plans.misses}; protected sites = {len(eng.plans.plans())}")
    return {"params": params, "cfg": cfg, "healthy": healthy}


def one_chip() -> None:
    with Phase("kernels"):
        kernel_phase()
    dev = jax.devices()[:1]
    r = serving_phase(dev, "entangle")
    with Phase("ft-off"):
        eng = ServeEngine(r["cfg"], serve_config("none"), r["params"],
                          devices=dev)
        plain = wave(eng, r["cfg"].vocab_size, None)
    log(f"token agreement protected vs ft-off (information only): "
        f"{agreement(r['healthy'], plain)}")


def four_chips() -> None:
    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    mesh4 = serving_phase(devs, "mesh4")["healthy"]
    # the one-chip engine remakes the same weights on device 0 once the
    # mesh engine's buffers are gone, so device 0 never holds both
    one = serving_phase(devs[:1], "one-of-four")["healthy"]
    log(f"token agreement 4-chip mesh vs one chip: {agreement(mesh4, one)}")
    check(same_tokens(mesh4, one),
          "tokens differ between the 4-chip mesh and one chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded serving path and its "
                         "one-chip comparison")
    args = ap.parse_args()

    cache = enable_compile_cache(ROOT)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[chip_smoke] FAIL: JAX finds no TPU (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        return 1
    backend = ops.resolve_backend()
    if backend != "pallas_tpu":
        print(f"[chip_smoke] FAIL: kernel backend resolves to {backend!r}, "
              f"not 'pallas_tpu'", file=sys.stderr)
        return 1
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    log(f"devices: {len(devs)} x {devs[0].device_kind}; backend {backend}; "
        f"compile cache {cache}")
    if args.four_chips:
        four_chips()
        count = 4
    else:
        one_chip()
        count = 1
    log(f"device 0 peak_bytes_in_use = {memory()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
