"""Serving launcher: ``python -m repro.launch.serve --arch <id> --smoke``

Boots the batched continuous-batching engine with random weights (or a
checkpoint directory) and runs a synthetic request wave. Fault tolerance is
first-class: ``--ft-mode entangle`` turns on the fused entangled int8 head
GEMM on every decode step AND on every admission batch's first token
(slot -> group = slot % ft_M), ``--ft-scope`` widens protection to the
in-model projections (``qkv`` | ``mlp`` | ``out`` | ``moe`` | ``all`` —
QKV, MLP up/down + router, output projections and MoE per-expert GEMMs
run entangled through the repro.ft subsystem; protection plans and weight
quantization are compiled once at startup), ``--failed-group r``
injects a fail-stop into group r's compute on every step, and ``--smoke``
prints a per-scope recovery summary (healthy vs injected outputs compared
token-by-token, for the head scope and the configured scope) plus the
engine's prefill/decode shape census and the autotune warmup counters.

Admission is the bucketed, chunked batched prefill pipeline:
``--prefill-buckets 8,16,32`` overrides the geometric default length
buckets, ``--prefill-chunk C`` interleaves C-token prefill chunks with
decode steps (0 = whole bucket per call), and ``--token-budget N`` turns
on token-packed admission — up to N prompt tokens per step, drawn from
ALL in-flight admission batches into ONE fixed-shape token-parallel
program (requires ``--prefill-chunk > 0``, N a multiple of it, and
``N / prefill-chunk <= max-batch`` rows; all checked at parse time).

Steady-state flags: ``--arrival-rate r`` replays a seeded open-loop
Poisson arrival trace (r requests/sec; 0 = submit the whole wave up
front), ``--deadline-ms d`` attaches an SLA to every request (queued
requests past it are shed loudly), ``--no-refill`` forces boundary
admission — new batches plan only when no admission batch is in flight
(the A/B baseline for mid-flight refill, which is the default).

Fleet flags: ``--replicas N`` (N > 1, or any fleet flag) serves the wave
through the multi-replica fabric (:mod:`repro.serve.fleet`) instead of a
single engine — router-owned admission, per-replica engines behind the
in-process transport. ``--kill-replica-at S --kill-replica R`` injects a
fail-stop into replica R at fleet step S (mid-wave machine loss; the
router migrates R's in-flight requests and the wave still completes),
``--max-replicas M --scale-up-depth D`` turns on queue-depth autoscaling
between the initial pool size and M. All cross-flag contracts are
validated at parse time.

Tracing: ``--trace-dir DIR`` turns the engine's host spans on
(:mod:`repro.serve.spans`), writes a JAX profiler trace of the wave to
DIR (the spans appear on its host plane beside the device timeline) and
prints the engine's start-up seconds, the median wait from submit to
admission, and the mean host milliseconds per step (each ``serve.step``
less its blocking device reads). Single-engine waves only.
"""
import argparse
import contextlib
import dataclasses
import time

import numpy as np
import jax

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.ft import SCOPES
from repro.kernels import autotune
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.serve.engine import Request, ServeConfig, ServeEngine
from repro.serve.fleet import Fleet, FleetConfig, ScalingPolicy
from repro.serve.spans import SpanLog
from repro.train.checkpoint import CheckpointManager

# shared drain bound for closed waves — kill/scaling schedules are
# validated against it at parse time so a mis-typed step count fails
# before engine startup rather than hanging a wave
MAX_WAVE_STEPS = 10_000


def _wave(eng: ServeEngine, n_requests: int, vocab: int, max_new: int,
          failed_group, arrival_rate: float = 0.0, deadline_ms=None):
    rng = np.random.default_rng(0)
    reqs = [Request(
        rid=r,
        prompt=rng.integers(0, vocab, size=8).astype(np.int32),
        max_new=max_new, deadline_ms=deadline_ms)
        for r in range(n_requests)]
    if not arrival_rate:
        for rq in reqs:
            eng.submit(rq)
        done = eng.run_to_completion(max_steps=MAX_WAVE_STEPS,
                                     failed_group=failed_group)
        return {r.rid: np.asarray(r.out) for r in done}
    # open-loop: submit each request at its seeded Poisson arrival time
    # (wall clock), stepping the engine in between — requests keep
    # arriving whether or not earlier ones have drained
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate,
                                         size=n_requests))
    t0, i, steps = time.monotonic(), 0, 0
    while i < n_requests or not eng.idle():
        now = time.monotonic() - t0
        if i < n_requests and eng.idle() and arrivals[i] > now:
            time.sleep(arrivals[i] - now)  # nothing to serve yet
            now = time.monotonic() - t0
        while i < n_requests and arrivals[i] <= now:
            eng.submit(reqs[i])
            i += 1
        eng.step(failed_group=failed_group)
        steps += 1
        assert steps < MAX_WAVE_STEPS, "open-loop wave failed to drain"
    if any(r.status == "shed" for r in reqs):
        print(f"[launch.serve] shed "
              f"{sum(r.status == 'shed' for r in reqs)} queued requests "
              f"past --deadline-ms {deadline_ms}")
    return {r.rid: np.asarray(r.out) for r in reqs if r.status == "done"}


def _print_spans(eng: ServeEngine, spans: SpanLog, trace_dir: str) -> None:
    waits = [r.t_admitted - r.t_submit for r in eng.done
             if r.t_admitted is not None]
    host = spans.step_host_s()
    print(f"[launch.serve] spans: engine init "
          f"{sum(spans.durations('serve.init'))} s; median admit wait "
          f"{np.median(waits) * 1e3 if waits else None} ms over "
          f"{len(waits)} requests; host "
          f"{np.mean(host) * 1e3 if host else None} ms per step over "
          f"{len(host)} steps; profiler trace in {trace_dir}")


def _fleet_wave(cfg, scfg: ServeConfig, params, args, failed_group):
    """Serve the synthetic wave through the multi-replica fabric, with an
    optional scheduled replica fail-stop, and print the migration
    summary. The wave must complete every request even when a replica is
    killed mid-flight — an incomplete wave exits nonzero."""
    pol = None
    if args.max_replicas:
        pol = ScalingPolicy(min_replicas=args.replicas,
                            max_replicas=args.max_replicas,
                            scale_up_depth=args.scale_up_depth)
    fleet = Fleet(cfg, scfg, params,
                  FleetConfig(replicas=args.replicas, policy=pol))
    rng = np.random.default_rng(0)
    reqs = [Request(
        rid=r,
        prompt=rng.integers(0, cfg.vocab_size, size=8).astype(np.int32),
        max_new=args.max_new, deadline_ms=args.deadline_ms)
        for r in range(args.requests)]
    for rq in reqs:
        fleet.submit(rq)
    steps = 0
    while not fleet.idle():
        if steps == args.kill_replica_at:
            print(f"[launch.serve] killing replica {args.kill_replica} "
                  f"at fleet step {steps} (fail-stop injected)")
            fleet.kill_replica(args.kill_replica)
        fleet.step(failed_group=failed_group)
        steps += 1
        assert steps < MAX_WAVE_STEPS, "fleet wave failed to drain"
    m = fleet.fleet_metrics()
    states = {rid: rep["state"] for rid, rep in m["replicas"].items()}
    done = sum(r.status == "done" for r in reqs)
    print(f"[launch.serve] fleet: {done}/{args.requests} requests "
          f"completed in {steps} fleet steps over {m['spawned']} replicas "
          f"(states: {states})")
    print(f"[launch.serve] fleet migration summary: "
          f"failed={m['failed']} migrated={m['router_migrated']} "
          f"(prefix-resume={m['router_resume_prefix']}, "
          f"recompute={m['router_resume_recompute']}, "
          f"replayed={m['router_replayed']}) "
          f"scale_ups={m['scale_ups']} scale_downs={m['scale_downs']} "
          f"shed={m['router_shed']}")
    if done + sum(r.status == "shed" for r in reqs) != args.requests:
        raise SystemExit(1)


def _validate_args(ap: argparse.ArgumentParser, args) -> None:
    """Fail FT/admission misconfigurations loudly at PARSE time.

    Every one of these would otherwise surface deep inside engine startup
    or a traced step (a mid-wave shape error, a silent mod-M wrap of the
    injected group, an autotune sweep of an impossible plan) — the
    launcher is the first place all the flags meet, so it owns the
    cross-flag contracts. ``--ft-scope`` itself is validated by argparse
    ``choices`` against the one true scope set (``repro.ft.SCOPES``).
    Returns the parsed ``--prefill-buckets`` tuple (or None) so ``main``
    consumes the exact value that was validated."""
    if args.ft_mode == "entangle":
        if args.ft_M < 3:
            ap.error(f"--ft-M must be >= 3 (the paper's minimum stream "
                     f"count), got {args.ft_M}")
        if args.max_batch % args.ft_M:
            ap.error(f"--max-batch ({args.max_batch}) must be divisible "
                     f"by --ft-M ({args.ft_M}): slots map round-robin "
                     f"onto the M entangled request groups")
    if args.failed_group >= 0:
        if args.ft_mode != "entangle":
            ap.error("--failed-group requires --ft-mode entangle")
        if args.failed_group >= args.ft_M:
            ap.error(f"--failed-group must be < --ft-M ({args.ft_M}); the "
                     f"kernel indexes streams mod M, so wrapping silently "
                     f"would drill a different group than requested")
    if args.prefill_chunk < 0:
        ap.error(f"--prefill-chunk must be >= 0, got {args.prefill_chunk}")
    if args.token_budget < 0:
        ap.error(f"--token-budget must be >= 0, got {args.token_budget}")
    if args.token_budget:
        # the packed program is [token_budget / prefill_chunk rows x
        # prefill_chunk tokens] — the budget must tile exactly into
        # chunk-wide rows, and every row stages in a distinct slot
        if args.prefill_chunk <= 0:
            ap.error(f"--token-budget ({args.token_budget}) requires "
                     f"--prefill-chunk > 0: packed rows are prefill-chunk "
                     f"tokens wide")
        if args.token_budget % args.prefill_chunk:
            ap.error(f"--token-budget ({args.token_budget}) must be a "
                     f"multiple of --prefill-chunk ({args.prefill_chunk}) "
                     f"— the packed program has ONE compiled shape, so "
                     f"the budget must tile exactly into chunk-wide rows")
        if args.token_budget // args.prefill_chunk > args.max_batch:
            ap.error(f"--token-budget/--prefill-chunk = "
                     f"{args.token_budget // args.prefill_chunk} packed "
                     f"rows > --max-batch ({args.max_batch}): every packed "
                     f"row stages in a distinct slot")
    buckets = None
    if args.prefill_buckets:
        try:
            buckets = tuple(int(b) for b in args.prefill_buckets.split(","))
        except ValueError:
            ap.error(f"--prefill-buckets must be comma-separated ints, "
                     f"got {args.prefill_buckets!r}")
        if any(b < 1 or b > args.max_seq for b in buckets):
            ap.error(f"--prefill-buckets {list(buckets)} must lie in "
                     f"[1, max-seq={args.max_seq}]")
    if args.arrival_rate < 0:
        ap.error(f"--arrival-rate must be >= 0 (requests/sec; 0 = closed "
                 f"wave), got {args.arrival_rate}")
    if args.deadline_ms is not None and args.deadline_ms <= 0:
        ap.error(f"--deadline-ms must be > 0, got {args.deadline_ms}")
    # -- fleet flags ---------------------------------------------------------
    if args.replicas < 1:
        ap.error(f"--replicas must be >= 1, got {args.replicas}")
    if args.max_replicas:
        if args.max_replicas < args.replicas:
            ap.error(f"--max-replicas ({args.max_replicas}) must be >= "
                     f"--replicas ({args.replicas}): autoscaling grows the "
                     f"pool above the initial size, never below it")
    if args.scale_up_depth < 1:
        ap.error(f"--scale-up-depth must be >= 1 (queued requests per "
                 f"healthy replica), got {args.scale_up_depth}")
    if args.kill_replica_at >= 0:
        if args.replicas < 2 and not args.max_replicas:
            ap.error(f"--kill-replica-at requires --replicas >= 2 or "
                     f"--max-replicas autoscaling: a surviving replica "
                     f"must absorb the migrated requests or the wave "
                     f"cannot drain")
        if args.kill_replica_at >= MAX_WAVE_STEPS:
            ap.error(f"--kill-replica-at ({args.kill_replica_at}) must be "
                     f"< {MAX_WAVE_STEPS}, the wave's drain bound — a "
                     f"later kill step would never fire")
        if not 0 <= args.kill_replica < args.replicas:
            ap.error(f"--kill-replica ({args.kill_replica}) must name a "
                     f"replica in the initial pool [0, {args.replicas})")
    elif args.kill_replica:
        ap.error(f"--kill-replica ({args.kill_replica}) requires "
                 f"--kill-replica-at to schedule the fail-stop")
    if args.trace_dir and (args.replicas > 1 or args.max_replicas
                           or args.kill_replica_at >= 0):
        ap.error("--trace-dir traces a single-engine wave; it takes no "
                 "fleet flags")
    return buckets


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ft-mode", default="none", choices=["none", "entangle"],
                    help="entangle: fused entangled int8 head GEMM on every "
                         "decode step")
    ap.add_argument("--ft-M", type=int, default=4,
                    help="entangled request groups (max-batch %% ft-M == 0)")
    ap.add_argument("--ft-scope", default="head", choices=sorted(SCOPES),
                    help="which projections run entangled: head only, or "
                         "also the in-model QKV / MLP+router / output-proj "
                         "/ MoE-expert sites (all = everything)")
    ap.add_argument("--failed-group", type=int, default=-1,
                    help=">= 0: inject a fail-stop into this group's head "
                         "GEMM on every decode step (rolled forward "
                         "in-kernel)")
    ap.add_argument("--blocks", default="",
                    help="head-GEMM block sizes: '' (defaults) or 'auto' "
                         "(autotune warmup at startup)")
    ap.add_argument("--prefill-buckets", default="",
                    help="comma-separated prompt length buckets for batched "
                         "admission (default: geometric 8,16,...,max-seq)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help=">0: split bucketed prefill into chunks of this "
                         "many tokens, one chunk per engine step "
                         "(interleaved with decode)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help=">0: token-packed admission — pack up to this "
                         "many prompt tokens per step from ALL in-flight "
                         "admission batches into one fixed-shape program "
                         "(requires --prefill-chunk > 0; must be a "
                         "multiple of it; budget/chunk rows <= max-batch)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help=">0: open-loop seeded Poisson arrivals at this "
                         "many requests/sec (0 = submit the whole wave "
                         "up front)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request SLA; queued requests past it are "
                         "shed loudly instead of served late")
    ap.add_argument("--no-refill", action="store_true",
                    help="boundary admission: plan new batches only when "
                         "no admission batch is in flight (disables "
                         "mid-flight slot refill)")
    ap.add_argument("--replicas", type=int, default=1,
                    help=">1 (or any fleet flag): serve through the "
                         "multi-replica fabric — router-owned admission "
                         "over this many in-process engine replicas")
    ap.add_argument("--kill-replica-at", type=int, default=-1,
                    help=">= 0: inject a whole-replica fail-stop at this "
                         "fleet step; the router migrates its in-flight "
                         "requests to healthy replicas")
    ap.add_argument("--kill-replica", type=int, default=0,
                    help="which replica id --kill-replica-at kills "
                         "(must lie in the initial pool)")
    ap.add_argument("--max-replicas", type=int, default=0,
                    help=">0: queue-depth autoscaling between --replicas "
                         "and this bound (0 = fixed-size pool)")
    ap.add_argument("--scale-up-depth", type=int, default=4,
                    help="autoscaling trigger: spawn a replica when the "
                         "router queue exceeds this many requests per "
                         "healthy replica")
    ap.add_argument("--trace-dir", default="",
                    help="record the engine's host spans, write a profiler "
                         "trace of the wave here and print the span "
                         "summary")
    args = ap.parse_args()
    buckets = _validate_args(ap, args)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg, max_seq=args.max_seq)
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        state_like = {"params": params}
        restored, step = mgr.restore(state_like)
        params = restored["params"]
        print(f"[launch.serve] restored params from step {step}")

    scfg = ServeConfig(
        max_batch=args.max_batch, max_seq=args.max_seq,
        ft_mode=args.ft_mode, ft_M=args.ft_M, ft_scope=args.ft_scope,
        blocks=(args.blocks or None),
        prefill_buckets=buckets, prefill_chunk=args.prefill_chunk,
        token_budget=args.token_budget, refill=not args.no_refill)
    failed = args.failed_group if args.failed_group >= 0 else None

    if (args.replicas > 1 or args.max_replicas > 0
            or args.kill_replica_at >= 0):
        _fleet_wave(cfg, scfg, params, args, failed)
        return

    spans = SpanLog() if args.trace_dir else None
    eng = ServeEngine(cfg, scfg, params, spans=spans)
    with (jax.profiler.trace(args.trace_dir) if spans is not None
          else contextlib.nullcontext()):
        outs = _wave(eng, args.requests, cfg.vocab_size, args.max_new,
                     failed, arrival_rate=args.arrival_rate,
                     deadline_ms=args.deadline_ms)
    if spans is not None:
        _print_spans(eng, spans, args.trace_dir)
    first = list(outs[0][:8]) if 0 in outs else "<request 0 not completed>"
    print(f"[launch.serve] {len(outs)}/{args.requests} requests completed in "
          f"{eng.decode_calls} batched decode calls; first output: {first}")
    print(f"[launch.serve] shape census: {eng.census}")
    if args.ft_mode == "entangle":
        m = eng.metrics
        print(f"[launch.serve] weight tiles: {m['ft_decode_tile_sites']} of "
              f"{m['ft_sites']} protected GEMMs ({m['ft_decode_sites']} at "
              f"decode rows) take decode-sized tiles, the smallest "
              f"{m['ft_decode_tile_min_bytes']} B")
    if cfg.moe is not None:
        m = eng.metrics
        print(f"[launch.serve] experts: {m['moe_experts_held']} of "
              f"{cfg.moe.n_experts} held, {m['moe_grouped_sites']} grouped "
              f"protected GEMMs")

    if args.smoke and args.ft_mode == "entangle":
        # per-scope recovery summary: drill the head scope AND the
        # configured scope (deduped). For the configured scope, the wave
        # above is one side of the comparison (healthy if no
        # --failed-group, injected otherwise) and only the missing side
        # runs; other scopes run both sides — every protected GEMM must
        # roll the failure forward so tokens match token-for-token.
        inj = failed if failed is not None else 0
        any_mismatch = False
        for scope in dict.fromkeys(["head", args.ft_scope]):
            sc = dataclasses.replace(scfg, ft_scope=scope)
            if scope == args.ft_scope:
                other = _wave(ServeEngine(cfg, sc, params), args.requests,
                              cfg.vocab_size, args.max_new,
                              inj if failed is None else None)
                healthy, injected = ((outs, other) if failed is None
                                     else (other, outs))
            else:
                healthy = _wave(ServeEngine(cfg, sc, params), args.requests,
                                cfg.vocab_size, args.max_new, None)
                injected = _wave(ServeEngine(cfg, sc, params), args.requests,
                                 cfg.vocab_size, args.max_new, inj)
            mismatches = sum(
                0 if np.array_equal(healthy[r], injected[r]) else 1
                for r in healthy)
            tokens = sum(len(v) for v in healthy.values())
            print(f"[launch.serve] recovery summary [scope={scope}]: "
                  f"failed_group={inj} injected on every step; "
                  f"{len(healthy)} requests / {tokens} tokens compared; "
                  f"mismatching requests: {mismatches} "
                  f"({'EXACT ROLL-FORWARD' if mismatches == 0 else 'RECOVERY FAILED'})")
            any_mismatch |= bool(mismatches)
        if args.blocks == "auto":
            print(f"[launch.serve] autotune: {autotune.stats()}; head-GEMM "
                  f"winners: {eng.census.get('head_gemm')}; protected "
                  f"sites warmed: {len(eng.census.get('protected', {}))}")
        if any_mismatch:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
