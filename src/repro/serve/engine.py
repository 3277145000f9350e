"""Batched continuous-batching serving engine with the entangled logits
head on the real hot path — decode AND admission.

One engine step issues ONE jitted decode call over the whole slot pool:

  * the KV/recurrent cache is slot-batched — a single pytree with batch
    dim ``max_batch``, every slot one row;
  * each slot decodes at its own position (the model decode contract takes
    an int32 position VECTOR [B]); admission and eviction only flip values
    in the position/active arrays, never shapes, so the decode program
    compiles once and is never retraced as traffic churns;
  * slot recycling is explicit: finished slots' cache rows are zeroed (one
    batched scatter per step, not one insert per request), so no tenant can
    observe a predecessor's KV or recurrent state.

Admission is a bucketed, chunked batched prefill pipeline (NOT one batch-1
call per request):

  * queued prompts are padded to a small geometric set of length buckets
    (``ServeConfig.prefill_buckets``; default 8, 16, 32, ..., max_seq) and
    all same-bucket admits prefill in ONE batched [Bp, T_bucket] call via
    the model's ``prefill_chunk`` contract (per-row true lengths keep
    rolling-window and recurrent caches exact under padding) — the prefill
    program retraces at most once per (bucket, chunk) shape, never per
    prompt length;
  * long prompts are split into fixed-size chunks
    (``ServeConfig.prefill_chunk``; Sarathi/vLLM-style): each engine step
    advances the pending admission by ONE chunk and still runs the full
    decode step, so decode latency stays flat while a long prompt batch is
    being admitted;
  * the whole admission batch's filled caches are scattered into their
    slots in ONE jitted batched row scatter; the first generated tokens
    come from the gathered per-row last-prompt hidden states.

Steady-state serving (mid-flight refill + async frontend + deadlines):

  * **mid-flight refill** (``ServeConfig.refill``, default on): the moment
    a slot finishes (``max_new`` reached, EOS, cancel) it is recycled into
    the LIVE prefill chunk stream — the engine plans a new admission batch
    over freed slots while other batches are still mid-chunk, instead of
    waiting for the current wave to drain to a bucket boundary. Several
    admission batches can be in flight at once (``_inflight``); each still
    runs the census'd ``[Bp, bucket]`` chunk programs with the same static
    shapes, so refill NEVER retraces and never creates a plan-registry
    entry (asserted at runtime via ``CompiledPlans.misses``). Slot ->
    group stays ``slot % M`` — group assignment is positional, plans are
    keyed by (site, shape), and activation quantization is per row
    (:mod:`repro.ft.quantize`), so WHEN a slot was refilled can never move
    another request's integer grid: the entangled roll-forward is
    bit-identical under refill and boundary admission alike (tested as a
    refill x fail-stop matrix).
  * **async frontend**: ``submit()`` returns a
    :class:`~repro.serve.scheduler.RequestHandle` — iterate it to stream
    tokens from a per-request ring buffer as decode steps land, call
    ``cancel()`` in any state, set ``Request.deadline_ms`` for an SLA.
  * **deadline-aware chunk scheduling**
    (:class:`~repro.serve.scheduler.ChunkScheduler`): admission batches
    form and advance earliest-deadline-first; decode is never starved more
    than ``max_prefill_per_step`` chunks per step; ``max_queue`` bounds
    the wait queue with a typed :class:`AdmissionRejected` at saturation,
    and queued requests whose deadline lapses are shed loudly before any
    prefill compute is spent on them (``metrics`` records all of it).
  * recycled-row zeroing and admission inserts share ONE batched scatter:
    a landing chunk's ``_scatter_rows`` call carries the pending zero rows
    in its spare capacity (``zero`` mask), so a steady-state step costs a
    single scatter — free rows are always zeroed again before the next
    decode, exactly as under boundary admission.

Token-packed admission (``ServeConfig.token_budget > 0``): the per-batch
``[Bp, bucket]`` chunk programs are replaced by ONE fixed-shape
token-parallel program per step — each step gathers up to ``token_budget``
prompt tokens from ALL in-flight admission batches (scheduler-ordered:
EDF + shortest-remaining-prefill, :meth:`ChunkScheduler.pack_rows`) as
``token_budget / prefill_chunk`` rows of ``prefill_chunk`` tokens, each
row one request's next chunk with per-row (slot, pos0, length) metadata:

  * rows advance to the request's TRUE prompt length — bucket padding is
    never packed, so the packed program runs denser than the bucketed
    chunk pipeline it replaces (the FT codec cost per true token drops
    with packing density);
  * per-slot cache state is gathered/scattered by the row metadata from a
    slot-indexed STAGING cache; ragged co-resident rows attend through
    per-row absolute-position masks (``attend_prefill_packed``) and the
    rolling-window / Mamba / RG-LRU recurrences carry per-slot state the
    same way, so a fresh row at offset 0 co-packs with a mid-prompt row
    bit-exactly;
  * the program is padded to the budget — exactly ONE compiled
    ``[Rp, Cp]`` shape regardless of the packing mix (mixed buckets,
    ragged tails, cancels), so ``CompiledPlans.misses`` stays 0 for any
    traffic, same as refill;
  * FT transparency is structural: slot -> group stays ``slot % M``,
    activation quantization is per row, and the entangled roll-forward is
    exact — packed admission is bit-identical to per-batch chunking under
    fail-stop injection in every group (tested as a packed x arch x scope
    x failed-group matrix).

Fault tolerance (the paper's technique in the serving path): with
``ft_mode='entangle'`` the final logits projection of EVERY decode step —
and of every admission batch's first token — runs as the fused entangled
int8 GEMM over M request groups (repro.ft.heads), slots mapped round-robin
to groups (slot -> group = slot % M). ``ServeConfig.ft_scope`` widens the
protection beyond the head through the unified protected-GEMM subsystem
(:mod:`repro.ft`): ``"qkv"`` additionally runs the mixer input projections
(attention Q/K/V, Mamba in_proj, RG-LRU in_x/in_gate) entangled, ``"mlp"``
the FFN projections (MLP gate/up/down, MoE router), ``"out"`` the mixer
output projections (attention/MLA wo, Mamba out_proj, RG-LRU out),
``"moe"`` the MoE per-expert GEMMs (the grouped entangled kernel), and
``"all"`` every protected site — on the decode hot path AND inside every
prefill-admission chunk, where the QKV/MLP GEMMs dominate the FLOP budget.
Protection parameters are compiled AHEAD OF TIME: the startup census is
frozen into immutable per-site ProtectionPlans (``repro.ft.compile_plans``)
and every in-model site's weights are int8-quantized once at startup
(``repro.ft.prepare_params``), so traced steps only look up plans and
never re-quantize weights.
``step(failed_group=r)`` injects a fail-stop into group r's compute at
every protected site of the step; the in-kernel roll-forward recovers its
outputs from the other M-1 groups' entangled accumulators, so decoded
tokens are bit-identical with and without the failure — no request
observes it, at any scope.

Autotune warmup contract: with ``blocks='auto'`` the engine sweeps the head
GEMM's block sizes at startup (``warm_autotune``) for its decode AND
prefill-admission shape census, so the in-jit ``blocks='auto'`` resolution
is a pure cache hit — sweeps must never run inside a traced decode step or
a traced prefill.

Host spans: ``ServeEngine(..., spans=SpanLog())`` times the start-up
phases and the phases of every ``step`` (plan, pack, prefill, land,
flush, decode, the two blocking device reads, emit) on the engine clock
and as profiler annotations (:mod:`repro.serve.spans`); off by default.
``Request.t_admitted`` (always stamped) splits a request's time to first
token into its wait for admission and its admission pipeline.

On hosts with more than one device the decode step traces under
``dist.sharding.serve_mesh()``, sharding the slot batch (and the head GEMM)
across devices; ``ServeEngine(..., devices=[d])`` confines an engine to
one device instead.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.plan import make_plan
from repro.dist import sharding
from repro.ft import (SCOPES, FTContext, PlanRegistry, compile_plans,
                      prepare_params)
from repro.ft.quantize import quantize_weight_stacked
from repro.ft.registry import TILE_MIN, default_blocks, group_rows
from repro.ft.heads import ft_logits_decode, ft_logits_prefill
from repro.kernels import ops as kops
from repro.models.api import get_model
from repro.models.layers import ACT_DTYPE
from repro.models.transformer import readout_scale
from repro.serve.scheduler import (ChunkScheduler, RequestHandle, TokenRing)
from repro.serve.spans import OFF, SpanLog


def geometric_buckets(max_seq: int, base: int = 8) -> tuple:
    """Default prefill length buckets: powers of two from ``base`` up,
    capped with ``max_seq`` itself — a small set, so the batched prefill
    retraces a handful of times total, never per prompt length."""
    out = []
    b = base
    while b < max_seq:
        out.append(b)
        b *= 2
    out.append(max_seq)
    return tuple(out)


def resolve_buckets(scfg: "ServeConfig") -> tuple:
    """The admission bucket set a ServeConfig implies — shared by the
    engine and the fleet router, which must validate prompt capacity and
    plan migration resumes against the same bounds WITHOUT building an
    engine of its own."""
    buckets = tuple(sorted(set(
        int(b) for b in (scfg.prefill_buckets
                         or geometric_buckets(scfg.max_seq)))))
    if buckets[0] < 1 or buckets[-1] > scfg.max_seq:
        raise ValueError(
            f"prefill_buckets {buckets} must lie in [1, "
            f"max_seq={scfg.max_seq}]")
    return buckets


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 4  # slot count; must be divisible by ft_M if entangling
    max_seq: int = 256
    ft_mode: str = "none"  # none | entangle
    ft_M: int = 4
    ft_w: int = 32
    # protected-GEMM scope: head | qkv | mlp | out | moe | all
    # (repro.ft.SCOPES) — which projections beyond the head run entangled
    ft_scope: str = "head"
    # store protected q8 weights int8-packed 4-per-int32-word (kernels
    # unpack on load): 4x fewer protected-weight HBM bytes per step.
    # False keeps the legacy int32-container copies (A/B baseline).
    ft_packed: bool = True
    # share one quantize+group codec pass across fanout site groups
    # (attention Q/K/V, MLP gate/up, ...); census marks groups either way
    ft_chain: bool = True
    greedy: bool = True
    # head-GEMM block sizes: None | dict | "auto" (autotuned at startup)
    blocks: Optional[object] = None
    use_pallas: bool = True  # entangled head via Pallas (False: XLA einsum)
    # -- admission (bucketed, chunked batched prefill) -----------------------
    prefill_buckets: Optional[Sequence[int]] = None  # None = geometric set
    prefill_chunk: int = 0  # >0: chunk prompts, one chunk per engine step
    prefill_batch: int = 0  # admission batch rows; 0 = max_batch
    # token-packed admission: > 0 packs up to token_budget prompt tokens
    # per step from ALL in-flight admission batches into ONE fixed-shape
    # [token_budget // prefill_chunk, prefill_chunk] token-parallel
    # program (requires prefill_chunk > 0, token_budget a multiple of it,
    # and rows <= max_batch). 0 = legacy per-batch [Bp, bucket] chunking.
    token_budget: int = 0
    # -- steady-state scheduling (repro.serve.scheduler) ---------------------
    # mid-flight refill: plan new admission batches over freed slots while
    # earlier batches are still mid-chunk. False = boundary mode (one
    # admission batch at a time — the legacy A/B baseline).
    refill: bool = True
    # chunked mode: prefill chunks advanced per step before the decode call
    # (decode is never starved more); unchunked admission ignores it
    max_prefill_per_step: int = 1
    max_queue: int = 0  # wait-queue bound; submit raises past it. 0 = off
    # injectable monotonic clock (seconds) for deadlines/latency metrics;
    # None = time.monotonic. Tests pass a fake for determinism.
    clock: Optional[Callable[[], float]] = None


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [T] int32
    max_new: int = 16
    out: Optional[np.ndarray] = None
    # SLA: shed from the wait queue (loudly — iterating the handle raises
    # DeadlineExceeded) if not admitted within deadline_ms of submit.
    # None = no deadline (ranks last in the EDF chunk schedule, FIFO).
    deadline_ms: Optional[float] = None
    eos_token: Optional[int] = None  # greedy-decoded EOS ends the request
    # -- engine-owned runtime state (set by submit/step, not the caller) ----
    # queued | prefill | decoding | done | cancelled | shed
    status: str = "new"
    t_submit: float = 0.0
    # first admission into a prefill batch (the end of the queue wait)
    t_admitted: Optional[float] = None
    t_first: Optional[float] = None  # first-token wall time (TTFT source)
    t_done: Optional[float] = None
    tok_times: list = dataclasses.field(default_factory=list)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, scfg: ServeConfig, params,
                 warm: Optional[dict] = None,
                 devices: Optional[Sequence] = None,
                 spans: Optional[SpanLog] = None):
        """``devices`` (default: every visible device) are the devices the
        slot batch shards across, with the weights replicated on each; a
        single device runs the engine unsharded there. ``spans`` records
        the engine's host spans (:mod:`repro.serve.spans`); None = off."""
        self._spans = spans
        if spans is not None:
            spans.clock = scfg.clock or time.monotonic
        with self._span("serve.init"):
            self._start(cfg, scfg, params, warm, devices)

    def _span(self, name: str):
        return OFF if self._spans is None else self._spans.span(name)

    def _start(self, cfg, scfg, params, warm, devices):
        self.cfg, self.scfg, self.params = cfg, scfg, params
        if not scfg.greedy:
            raise NotImplementedError("only greedy decode is implemented")
        if warm is not None and warm.get("sig") != self._warm_sig():
            # a mismatched warm state would silently serve stale plans /
            # quantized weights for a DIFFERENT program set — refuse
            raise ValueError(
                "warm state was built by a differently-configured engine; "
                "replicas sharing startup products must share (cfg, scfg "
                "modulo clock)")
        self.model = get_model(cfg)
        B, S = scfg.max_batch, scfg.max_seq
        # THE slot-batched cache: one pytree, slot i = batch row i
        self.cache = self.model.init_cache(cfg, B, S)
        self.slots: list[Optional[dict]] = [None] * B
        self.queue: list[Request] = []
        self.done: list[Request] = []
        self.pos = np.zeros(B, np.int32)  # per-slot next decode position
        self.last_tok = np.zeros(B, np.int32)
        self.census: dict[str, dict] = {"prefill": {}, "decode": {}}
        self.decode_calls = 0  # jitted decode invocations (one per step)
        self.prefill_calls = 0  # jitted prefill invocations (chunk/packed)
        self.mesh = sharding.serve_mesh(devices)
        if self.mesh is not None:
            # replicate the weights onto the mesh ONCE: uncommitted
            # weights would be copied to every device on every step
            params = jax.device_put(params, NamedSharding(self.mesh, P()))
        elif devices is not None:
            params = jax.device_put(params, list(devices)[0])
        self.params = params

        # admission pipeline configuration
        self.buckets = resolve_buckets(scfg)
        if scfg.prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, got "
                             f"{scfg.prefill_chunk}")
        if scfg.token_budget < 0:
            raise ValueError(f"token_budget must be >= 0, got "
                             f"{scfg.token_budget}")
        if scfg.token_budget:
            # loud parse-time geometry checks: the packed program has ONE
            # compiled [Rp, Cp] shape, so the budget must tile exactly into
            # chunk-wide rows and every row must map to a distinct slot
            if not scfg.prefill_chunk:
                raise ValueError(
                    f"token_budget={scfg.token_budget} requires "
                    f"prefill_chunk > 0 (rows are prefill_chunk tokens "
                    f"wide)")
            if scfg.token_budget % scfg.prefill_chunk:
                raise ValueError(
                    f"token_budget={scfg.token_budget} must be a multiple "
                    f"of prefill_chunk={scfg.prefill_chunk}")
            if scfg.token_budget // scfg.prefill_chunk > B:
                raise ValueError(
                    f"token_budget={scfg.token_budget} / prefill_chunk="
                    f"{scfg.prefill_chunk} = "
                    f"{scfg.token_budget // scfg.prefill_chunk} packed "
                    f"rows > max_batch={B} (each row stages in a distinct "
                    f"slot)")
        # packed geometry: Rp rows x Cp tokens; Rp == 0 means legacy
        self.Rp = (scfg.token_budget // scfg.prefill_chunk
                   if scfg.token_budget else 0)
        self.Cp = scfg.prefill_chunk
        self.Bp = scfg.prefill_batch or B
        if not 1 <= self.Bp <= B:
            # the batched row scatter maps every admission row to a DISTINCT
            # slot (pad rows write back the slot's own content), which needs
            # Bp <= max_batch; rows beyond the slot pool could never land
            raise ValueError(
                f"prefill_batch={self.Bp} must be in [1, max_batch={B}]")
        # zero admission-batch template: prefill start state AND the zeros
        # source for batched slot recycling (invariant: every free slot's
        # row is zeroed again before the next decode call)
        self._fresh_prefill = self.model.init_cache(cfg, self.Bp, S)
        if self.Rp:
            # token-packed staging: a slot-indexed cache (row i = slot i,
            # same layout as the decode pool) holding every in-flight
            # row's mid-prefill state; packed calls gather/scatter rows
            # by slot id. Fresh rows (pos0 == 0) are zeroed IN-PROGRAM,
            # so recycled staging rows never need host-side zeroing.
            self._pack_cache = self.model.init_cache(cfg, B, S)
            self._pack_hlast = jnp.zeros((B, cfg.d_model), ACT_DTYPE)
        self._inflight: list[dict] = []  # in-flight admission batches
        self._reserved: set[int] = set()  # slots claimed by in-flight rows
        self._dirty: list[int] = []  # freed slots awaiting batched zeroing
        self._rings: dict[int, TokenRing] = {}  # id(req) -> token ring
        self.scatter_calls = 0  # jitted _scatter_rows invocations
        self.sched = ChunkScheduler(
            max_prefill_per_step=scfg.max_prefill_per_step,
            max_queue=scfg.max_queue,
            clock=scfg.clock or time.monotonic)
        self._clock = self.sched.clock
        self.metrics = {"queue_depth_peak": 0, "rejected": 0, "shed": 0,
                        "refill_admissions": 0, "landings": 0,
                        "merged_zero_rows": 0, "cancelled": 0,
                        # token-packed admission accounting: TRUE prompt
                        # tokens packed (pad rows and intra-row padding
                        # excluded), packed program invocations, and the
                        # peak number of distinct admission batches
                        # co-packed into one program
                        "packed_tokens": 0, "packed_calls": 0,
                        "packed_batches_peak": 0}

        if scfg.ft_mode == "entangle":
            if B % scfg.ft_M:
                raise ValueError(
                    f"max_batch={B} must be divisible by ft_M={scfg.ft_M}")
            if scfg.ft_scope not in SCOPES:
                raise ValueError(
                    f"unknown ft_scope {scfg.ft_scope!r}; expected one of "
                    f"{sorted(SCOPES)}")
            if scfg.ft_scope != "head" and cfg.family == "encdec":
                raise ValueError(
                    "in-model protected GEMMs are decoder-only; enc-dec "
                    "supports ft_scope='head' only")
            if warm is not None:
                # fleet warm start: reuse the sibling replica's quantized
                # head and plan registry verbatim — same config, same
                # shapes, same grids
                self.plan = warm["plan"]
                self.head_q, self.w_scale = warm["head_q"], warm["w_scale"]
                self._head_dims = warm["head_dims"]
                self.registry = warm["registry"]
            else:
                # plan reuse: made ONCE, shared by every decode step, every
                # admission-batch head projection, every in-model protected
                # site and every autotune key
                self.plan = make_plan(scfg.ft_M, scfg.ft_w)
                head = self.model.head_weights(params, cfg)
                # true [D, V] head dims — the packed copy's contraction
                # axis holds ceil(D/4) words, not D
                self._head_dims = tuple(head.shape)
                with self._span("serve.init.quantize"):
                    q8 = quantize_weight_stacked(head,
                                                 packed=scfg.ft_packed)
                self.head_q, self.w_scale = q8["w"], q8["scale"]
                # the protected-GEMM subsystem: one registry for the whole
                # forward pass; layer sites get "auto" blocks only when the
                # engine itself autotunes (a user dict targets the HEAD
                # shape and must not leak onto differently-shaped layer
                # GEMMs)
                self.registry = PlanRegistry(
                    self.plan,
                    blocks="auto" if scfg.blocks == "auto" else None,
                    packed=scfg.ft_packed)
            self.ftx = FTContext(registry=self.registry,
                                 scope=scfg.ft_scope,
                                 use_pallas=scfg.use_pallas,
                                 chain=scfg.ft_chain)
        elif scfg.ft_mode != "none":
            raise ValueError(f"unknown ft_mode {scfg.ft_mode!r}")
        self._head_blocks = self._default_head_blocks()

        # donate the slot-batched cache through decode/insert so XLA aliases
        # it in place instead of copying the engine's largest buffer every
        # token (donation is a no-op warning on CPU, so gate it)
        donate = jax.default_backend() != "cpu"
        self._scatter_rows = jax.jit(self._scatter_rows_impl,
                                     donate_argnums=(0,) if donate else ())
        # NO donation on chunk 0: it is fed the shared _fresh_prefill
        # template, which must survive every admission. Continuation
        # chunks exclusively own their cache/h_last carry — donate them.
        # failed_group is static like on the decode path: each injected
        # variant is its own program sharing plans and autotune winners
        # (always None when ft_scope == 'head', so no extra retraces).
        self._prefill_chunk = jax.jit(
            self._prefill_chunk_impl,
            static_argnames=("pos0", "failed_group"))
        self._prefill_chunk_cont = jax.jit(
            self._prefill_chunk_impl,
            static_argnames=("pos0", "failed_group"),
            donate_argnums=(2, 4) if donate else ())
        # the token-packed prefill step exclusively owns the staging
        # cache + h_last carry — donate both so XLA updates them in place
        self._prefill_packed = jax.jit(
            self._prefill_packed_impl,
            static_argnames=("failed_group",),
            donate_argnums=(1, 2) if donate else ())
        self._gather_rows = jax.jit(self._gather_rows_impl)
        self._prefill_head = jax.jit(self._prefill_head_impl,
                                     static_argnames=("failed_group",))
        self._decode = jax.jit(self._decode_impl,
                               static_argnames=("failed_group",),
                               donate_argnums=(1,) if donate else ())
        # startup plan compilation (the v2 AOT flow): prime the registry
        # with every protected shape the engine can trace (decode + all
        # chunk widths) via census-only abstract traces, freeze it into
        # immutable per-site ProtectionPlans, and hoist the eq.-13 int8
        # weight quantization of every in-model protected site out of the
        # traced graph — ``ft_params`` carries the startup-quantized q8
        # copies alongside the float masters, so a traced decode/prefill
        # step contains ZERO weight-quantization ops (tested via the
        # quantize.TRACE_STATS trace counter)
        if warm is not None:
            # fleet warm start: the census, compiled ProtectionPlans and
            # startup-quantized params are immutable after startup, so a
            # spawned replica of identical config reuses one copy —
            # NO census retrace, NO plan compile, NO eq.-13 weight
            # re-quantization, NO autotune sweep (tested: spawning the
            # second replica leaves quantize.TRACE_STATS and the autotune
            # sweep counter untouched). The shared CompiledPlans pools
            # its ``misses`` counter across the fleet.
            self.protected_census = warm["census"]
            self._chunk_widths = self._all_chunk_widths()
            self.plans = warm["plans"]
            self.ft_params = warm["ft_params"]
            if self.plans is not None:
                self.ftx = self.ftx.with_plans(self.plans)
        else:
            self._compile_plans(params)
        if scfg.ft_mode == "entangle":
            self.metrics.update(self._tile_counts())
        if cfg.moe is not None:
            self.metrics.update(self._moe_counts())

    def _compile_plans(self, params) -> None:
        """Census every protected shape, freeze the plans, quantize the
        protected sites' weights once and, with ``blocks="auto"``, sweep
        the autotuner — a fresh (not warm-started) engine's startup."""
        scfg = self.scfg
        with self._span("serve.init.census"):
            self.protected_census = self._protected_shape_census()
        # every chunk width any admission — boundary or refill — can run:
        # refill-time plan reuse is checked against this set, because a
        # refilled batch replays one of exactly these census'd programs
        self._chunk_widths = self._all_chunk_widths()
        self.plans = None
        self.ft_params = params
        if scfg.ft_mode == "entangle" and scfg.ft_scope != "head":
            with self._span("serve.init.plans"):
                self.plans = compile_plans(self.registry,
                                           self.protected_census)
                # census / compile drift fails loudly at startup — a lazy
                # mid-serve plan entry would mean refill retraced a shape
                # the startup census missed
                self.plans.assert_covers(self.protected_census)
            self.ftx = self.ftx.with_plans(self.plans)
            with self._span("serve.init.params"):
                self.ft_params = prepare_params(params, scope=scfg.ft_scope,
                                                packed=scfg.ft_packed)
        if scfg.blocks == "auto":
            with self._span("serve.init.autotune"):
                self.warm_autotune()

    def _warm_sig(self) -> tuple:
        """Config signature warm-started replicas must share. The clock is
        excluded — it is the only per-process field and shapes no traced
        program."""
        return (self.cfg, dataclasses.replace(self.scfg, clock=None))

    def warm_state(self) -> dict:
        """Shareable startup products for spawning engine replicas of
        IDENTICAL config — the fleet's scale-up seam. The protected-site
        census, compiled :class:`~repro.ft.plans.CompiledPlans`,
        startup-quantized ``ft_params`` and the quantized head are all
        immutable after startup, so sibling replicas share one copy:
        constructing ``ServeEngine(cfg, scfg, params, warm=...)`` re-runs
        no census trace, no plan compile, no weight quantization and no
        autotune sweep. Sharing CompiledPlans also pools its ``misses``
        counter, so the fleet's ``misses == 0`` invariant covers every
        replica at once."""
        w = {"sig": self._warm_sig(), "census": self.protected_census,
             "plans": self.plans, "ft_params": self.ft_params}
        if self.scfg.ft_mode == "entangle":
            w.update(plan=self.plan, head_q=self.head_q,
                     w_scale=self.w_scale, head_dims=self._head_dims,
                     registry=self.registry)
        return w

    def submit(self, req: Request) -> RequestHandle:
        """Enqueue a request and return its async handle (iterate for the
        token stream; ``cancel()``; ``result()``). Raises
        :class:`~repro.serve.scheduler.AdmissionRejected` at saturation
        (``max_queue``) — a typed rejection, never a silent drop."""
        # loud capacity checks: past max_seq the vector cache scatter would
        # silently DROP K/V writes, and a prompt longer than the largest
        # bucket would either retrace per length or OOM the bucket planner —
        # both turn overflow into wrong tokens / stalls instead of an error
        if len(req.prompt) > self.buckets[-1]:
            raise ValueError(
                f"request rid={req.rid} prompt length {len(req.prompt)} > "
                f"largest prefill bucket {self.buckets[-1]} (configure "
                f"prefill_buckets / raise max_seq)")
        need = len(req.prompt) + req.max_new
        if need > self.scfg.max_seq:
            raise ValueError(
                f"request rid={req.rid} needs {need} positions "
                f"(prompt {len(req.prompt)} + max_new {req.max_new}) "
                f"> max_seq={self.scfg.max_seq}")
        try:
            self.sched.check_admission(req.rid, len(self.queue))
        except Exception:
            self.metrics["rejected"] += 1
            raise
        req.status = "queued"
        req.t_submit = self._clock()
        ring = TokenRing(req.max_new)
        self._rings[id(req)] = ring
        self.queue.append(req)
        self.metrics["queue_depth_peak"] = max(
            self.metrics["queue_depth_peak"], len(self.queue))
        return RequestHandle(self, req, ring)

    def _bucket_for(self, req: Request) -> int:
        """Smallest configured bucket covering the prompt."""
        n = len(req.prompt)
        for b in self.buckets:
            if n <= b:
                return b
        raise AssertionError("unreachable: submit() rejects oversize")

    def _default_head_blocks(self):
        """Head-GEMM block sizes when the user gave none: the protected
        sites' shape policy (:func:`repro.ft.registry.default_blocks`) at
        the decode step's rows per group. The admission head runs on at
        most as many rows, so the same blocks suit it."""
        if self.scfg.blocks is not None or self.scfg.ft_mode != "entangle":
            return self.scfg.blocks
        D, V = self._head_dims
        return default_blocks(group_rows(self.scfg.max_batch, self.scfg.ft_M),
                              D, V, kops.resolve_backend(), plan=self.plan)

    def _tile_counts(self) -> dict:
        """Protected GEMMs by weight tile, counted once at plan compile:
        every (site, call shape) plan plus the decode head, those at the
        decode step's rows, those of them given decode-sized weight tiles
        (at least :data:`repro.ft.registry.TILE_MIN` int8 bytes), and the
        smallest weight tile among them."""
        Bg = group_rows(self.scfg.max_batch, self.scfg.ft_M)
        calls = [(Bg, self._head_blocks)] + [
            (p.shape[-3], p.blocks) for p in self.plans or ()]
        tiles = [b["bn"] * b["bk"] for rows, b in calls
                 if rows == Bg and isinstance(b, dict) and "bn" in b
                 and "bk" in b]
        return {"ft_sites": len(calls),
                "ft_decode_sites": sum(rows == Bg for rows, _ in calls),
                "ft_decode_tile_sites": sum(t >= TILE_MIN for t in tiles),
                "ft_decode_tile_min_bytes": min(tiles, default=0)}

    def _moe_counts(self) -> dict:
        """The expert layer's shape, counted once at plan compile: routed
        experts held here and grouped (per-expert) protected GEMM plans."""
        return {"moe_experts_held": self.cfg.moe.held,
                "moe_grouped_sites": sum(p.grouped for p in self.plans or ())}

    # -- jitted programs ------------------------------------------------------

    def _pad_sids(self, taken: list) -> tuple:
        """(sids [Bp], valid [Bp]) for ``_scatter_rows``: the ``taken``
        slots first, padded to Bp rows with DISTINCT unused slots (pad rows
        are write-back no-ops, and distinctness keeps the scatter
        order-independent). Single source of the invariant for admission
        scatter and recycle flush; requires len(taken) <= Bp <= max_batch
        (enforced at init)."""
        spare = [s for s in range(self.scfg.max_batch) if s not in taken]
        sids = np.asarray(taken + spare[: self.Bp - len(taken)], np.int32)
        valid = np.arange(self.Bp) < len(taken)
        return jnp.asarray(sids), jnp.asarray(valid)

    def _scatter_rows_impl(self, cache, pcache, sids, valid, zero):
        """Scatter ALL rows of an admission-batch (or zeros-template)
        pytree into the batched cache in ONE call: row j lands in slot
        ``sids[j]``; rows with ``valid[j] == False`` are sent past the
        slot axis and dropped (the slot keeps its content), and rows with
        ``zero[j] == True`` write ZEROS instead of their pcache content —
        recycled-slot zeroing rides in the SAME scatter as the admission
        insert, so one trace (and one dispatch) serves any mix of
        admission rows, recycle rows and padding. ``sids``/``valid``/
        ``zero`` are traced; the caller guarantees sids are DISTINCT
        slots. Dropping (rather than gathering the slots' own rows to
        write back) keeps the temporaries to about one copy of the
        batch's rows."""
        def ins(big, small):
            z = zero.reshape((1, -1) + (1,) * (big.ndim - 2))
            src = jnp.where(z, jnp.zeros_like(small), small)
            idx = jnp.where(valid, sids, big.shape[1])
            return big.at[:, idx].set(src, mode="drop")
        return jax.tree.map(ins, cache, pcache)

    def _scatter(self, pcache, sids, valid, zero):
        """Host wrapper over the jitted batched scatter: one call = one
        dispatch (``scatter_calls`` is the trace-count evidence that
        recycling and insert really share a scatter per step)."""
        self.cache = self._scatter_rows(
            self.cache, pcache, sids, jnp.asarray(valid), jnp.asarray(zero))
        self.scatter_calls += 1

    def _model_ft(self, failed_group: Optional[int]):
        """The FT context threaded INTO the model forward pass, or None
        when no in-model site is protected (ft off, or scope == 'head'
        where protection lives entirely in the engine's head projection)."""
        if self.scfg.ft_mode != "entangle" or self.scfg.ft_scope == "head":
            return None
        return self.ftx.with_failed(failed_group)

    def _prefill_chunk_impl(self, params, tokens, cache, lengths, h_last,
                            pos0: int = 0,
                            failed_group: Optional[int] = None):
        """ONE chunk of the batched admission prefill: tokens [Bp, C] at
        absolute positions pos0..pos0+C-1, per-row true ``lengths``.
        Captures each row's last-prompt hidden state in ``h_last`` as soon
        as the chunk containing position lengths-1 is processed. With an
        ft_scope beyond 'head', the chunk's QKV/MLP/router GEMMs run
        entangled and ``failed_group`` is rolled forward inside them."""
        ctx = (sharding.axis_rules(self.mesh) if self.mesh is not None
               else contextlib.nullcontext())
        with ctx:
            h, new_cache = self.model.prefill_chunk(
                params, tokens, self.cfg, cache, pos0=pos0, lengths=lengths,
                ft=self._model_ft(failed_group))
            C = tokens.shape[1]
            idx = lengths - 1 - pos0
            in_chunk = (idx >= 0) & (idx < C)
            h_at = jnp.take_along_axis(
                h, jnp.clip(idx, 0, C - 1)[:, None, None], axis=1)[:, 0]
            h_last = jnp.where(in_chunk[:, None], h_at, h_last)
            return h_last, new_cache

    def _prefill_packed_impl(self, params, pack_cache, hlast, tok, sids,
                             pos0r, lengths, valid,
                             failed_group: Optional[int] = None):
        """ONE token-packed prefill step: ``tok`` [Rp, Cp] holds each
        packed row's next chunk of TRUE prompt tokens, row r staged in
        slot ``sids[r]`` at absolute offset ``pos0r[r]`` with true prompt
        length ``lengths[r]``. All metadata is TRACED — one compiled shape
        serves every packing mix. Gathers the rows' staging state (slot
        axis 1), zeroes FRESH rows (pos0 == 0) so a recycled staging row
        can never leak a predecessor's state into a new prompt, runs the
        model's token-packed prefill, captures each row's last-prompt
        hidden state, and scatters ``valid`` rows back (pad rows write
        their own gathered content back — a no-op; sids are DISTINCT, so
        the scatter is order-free)."""
        ctx = (sharding.axis_rules(self.mesh) if self.mesh is not None
               else contextlib.nullcontext())
        with ctx:
            fresh = pos0r == 0
            def take(a):
                rows = jnp.take(a, sids, axis=1)
                f = fresh.reshape((1, -1) + (1,) * (rows.ndim - 2))
                return jnp.where(f, jnp.zeros_like(rows), rows)
            rows = jax.tree.map(take, pack_cache)
            h, new_rows = self.model.prefill_packed(
                params, tok, self.cfg, rows, pos0=pos0r, lengths=lengths,
                ft=self._model_ft(failed_group))
            Cp = tok.shape[1]
            idx = lengths - 1 - pos0r
            in_chunk = (idx >= 0) & (idx < Cp)
            h_at = jnp.take_along_axis(
                h, jnp.clip(idx, 0, Cp - 1)[:, None, None], axis=1)[:, 0]
            hrow = jnp.where(in_chunk[:, None], h_at,
                             jnp.take(hlast, sids, axis=0))
            def put(big, small):
                cur = jnp.take(big, sids, axis=1)
                v = valid.reshape((1, -1) + (1,) * (big.ndim - 2))
                return big.at[:, sids].set(jnp.where(v, small, cur))
            pack_cache = jax.tree.map(put, pack_cache, new_rows)
            hlast = hlast.at[sids].set(
                jnp.where(valid[:, None], hrow,
                          jnp.take(hlast, sids, axis=0)))
            return pack_cache, hlast

    def _gather_rows_impl(self, pack_cache, hlast, sids):
        """Landing gather: pull a finished admission batch's staging rows
        (slot axis 1) and last-prompt hidden states into [Bp]-row order so
        the legacy landing tail (``_prefill_head`` + ``_scatter``) runs
        unchanged on packed batches."""
        rows = jax.tree.map(lambda a: jnp.take(a, sids, axis=1), pack_cache)
        return rows, jnp.take(hlast, sids, axis=0)

    def _head_logits(self, params, h, mask, head, failed_group, ft_fn):
        """Shared head epilogue of decode steps and admission batches:
        rows where ``mask`` is False are zeroed so their garbage logits
        are deterministic (activation quantization is PER ROW, so masked
        rows could not move a live row's grid either way); with ft on,
        ``ft_fn`` (ft_logits_decode / ft_logits_prefill) runs the fused
        entangled int8 GEMM with the startup plan, scaled back to
        head_project's muP readout temperature (argmax-neutral; keeps ft
        and plain logits on one scale)."""
        if self.scfg.ft_mode != "entangle":
            return self.model.head_project(params, h, self.cfg)
        head_q, w_scale = head
        hf = jnp.where(mask[:, None], h.astype(jnp.float32), 0.0)
        logits = ft_fn(
            hf, head_q, w_scale, plan=self.plan, failed_group=failed_group,
            use_pallas=self.scfg.use_pallas, blocks=self._head_blocks)
        return logits * readout_scale(self.cfg)

    def _prefill_head_impl(self, params, h_last, valid, head,
                           failed_group: Optional[int] = None):
        """First generated token of every admission row: project the
        gathered last-prompt hidden states. With ft on this runs the SAME
        fused entangled int8 GEMM (and plan) as the decode head, so a
        fail-stop during admission rolls forward in-kernel."""
        ctx = (sharding.axis_rules(self.mesh) if self.mesh is not None
               else contextlib.nullcontext())
        with ctx:
            logits = self._head_logits(params, h_last, valid, head,
                                       failed_group, ft_logits_prefill)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _decode_impl(self, params, cache, last_tok, pos, active, head,
                     failed_group: Optional[int] = None):
        """ONE decode step for the whole slot pool. ``pos`` is the per-slot
        position vector; ``active`` masks which rows carry live requests
        (inactive rows compute garbage that admission later overwrites).
        ``head`` is (head_q, w_scale) — passed as a jit argument, not a
        closure constant, so every failed_group retrace shares ONE device
        buffer for the [D, V] quantized head (None when ft is off)."""
        ctx = (sharding.axis_rules(self.mesh) if self.mesh is not None
               else contextlib.nullcontext())
        with ctx:
            tok = last_tok[:, None]
            h, new_cache = self.model.decode_hidden(
                params, tok, cache, pos, self.cfg,
                ft=self._model_ft(failed_group))
            logits = self._head_logits(params, h, active, head,
                                       failed_group, ft_logits_decode)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, new_cache

    # -- admission pipeline ---------------------------------------------------

    def _census_bump(self, kind: str, sig: tuple):
        self.census[kind][sig] = self.census[kind].get(sig, 0) + 1

    def _plan_admission(self) -> bool:
        """Form the next admission batch: order the wait queue
        earliest-deadline-first (FIFO among deadline-less requests — the
        legacy order when nobody sets deadlines), pick the most urgent
        request's bucket, then batch every same-bucket queued request (EDF
        within the bucket) up to the free-slot / admission-row budget.

        With ``refill`` on this runs while other batches are still
        mid-chunk — freed slots re-enter the live prefill stream
        immediately; boundary mode admits one batch at a time (legacy).
        Planned rows RESERVE their slots so concurrent batches never claim
        the same row. Returns True if a batch was formed."""
        if not self.queue:
            return False
        if self._inflight and not self.scfg.refill:
            return False  # boundary mode: wait for the in-flight batch
        free = [i for i, s in enumerate(self.slots)
                if s is None and i not in self._reserved]
        if not free:
            return False
        ordered = self.sched.order_queue(self.queue)
        b0 = self._bucket_for(ordered[0])
        # refill-time plan reuse: the batch replays a census'd [Bp, bucket]
        # chunk program — a bucket outside the startup census would retrace
        assert b0 in self.buckets
        budget = min(len(free), self.Bp)
        take, rest = [], []
        for req in ordered:
            if len(take) < budget and self._bucket_for(req) == b0:
                take.append(req)
            else:
                rest.append(req)
        self.queue = rest
        if self._inflight:
            # a MID-FLIGHT refill: a new batch enters the live prefill
            # chunk stream while earlier batches are still mid-chunk —
            # exactly what boundary mode forbids (its engines report 0)
            self.metrics["refill_admissions"] += 1
        tokens = np.zeros((self.Bp, b0), np.int32)
        lengths = np.zeros(self.Bp, np.int32)
        now = self._clock()
        for j, req in enumerate(take):
            tokens[j, : len(req.prompt)] = req.prompt
            lengths[j] = len(req.prompt)
            req.status = "prefill"
            if req.t_admitted is None:
                req.t_admitted = now
        slots = free[: len(take)]
        self._reserved.update(slots)
        self._inflight.append({
            "reqs": list(zip(slots, take)),
            "tokens": jnp.asarray(tokens),
            "lengths": jnp.asarray(lengths),
            "cache": self._fresh_prefill,
            "h_last": jnp.zeros((self.Bp, self.cfg.d_model), ACT_DTYPE),
            "pos0": 0,
            "bucket": b0,
            # host-side per-row state for token packing (pack_rows /
            # _advance_packed): true lengths, each row's prefill offset,
            # and the raw tokens to slice packed chunks from
            "tokens_np": tokens,
            "lengths_np": lengths,
            "rowpos": np.zeros(self.Bp, np.int32),
        })
        return True

    def _advance_prefill(self, p: dict, failed_group: Optional[int]):
        """Run ONE chunk of admission batch ``p``; on the last chunk,
        project first tokens and scatter the batch's cache rows — plus any
        deferred recycle-zero rows that fit the spare capacity — into the
        slot pool in ONE batched scatter."""
        Tb = p["bucket"]
        C = self.scfg.prefill_chunk or Tb
        pos0 = p["pos0"]
        sz = min(C, Tb - pos0)
        chunk_fn = self._prefill_chunk if pos0 == 0 else \
            self._prefill_chunk_cont
        # fail-stop injection reaches the chunk's protected GEMMs only
        # when an in-model scope is on; at scope 'head' the single healthy
        # chunk program serves every failed_group (head injection happens
        # in _prefill_head)
        fg = (failed_group if self._model_ft(failed_group) is not None
              else None)
        with self._span("serve.prefill"):
            p["h_last"], p["cache"] = chunk_fn(
                self.ft_params, p["tokens"][:, pos0 : pos0 + sz],
                p["cache"], p["lengths"], p["h_last"], pos0=pos0,
                failed_group=fg)
        self.prefill_calls += 1
        p["pos0"] = pos0 + sz
        if p["pos0"] < Tb:
            return
        # census records BUCKET shapes (admission rows, padded length) —
        # the traced call signature — never raw prompt lengths
        self._census_bump("prefill", (self.Bp, Tb))
        with self._span("serve.land"):
            self._land(p, failed_group)

    def _land(self, p: dict, failed_group: Optional[int]):
        """Land a COMPLETE admission batch (``p["cache"]`` / ``p["h_last"]``
        hold [Bp]-row final state — from the last legacy chunk or gathered
        out of the packed staging cache): project first tokens and scatter
        the batch's cache rows — plus any deferred recycle-zero rows that
        fit the spare capacity — into the slot pool in ONE batched scatter.
        Rows whose request was cancelled mid-prefill are masked invalid
        (they computed garbage under static shapes but never land)."""
        valid = [req is not None for _, req in p["reqs"]]
        vfull = np.zeros(self.Bp, bool)
        vfull[: len(valid)] = valid
        head = (None if self.scfg.ft_mode != "entangle"
                else (self.head_q, self.w_scale))
        first = self._prefill_head(
            self.ft_params, p["h_last"], jnp.asarray(vfull), head,
            failed_group=failed_group)
        with self._span("serve.land.sync"):
            first = np.asarray(first)
        sids = [i for i, _ in p["reqs"]]
        vrows, zero = list(valid), [False] * len(sids)
        merge = [i for i in self._dirty
                 if self.slots[i] is None and i not in self._reserved
                 and i not in sids][: self.Bp - len(sids)]
        for i in merge:
            sids.append(i)
            vrows.append(True)
            zero.append(True)
            self._dirty.remove(i)
        self.metrics["merged_zero_rows"] += len(merge)
        spare = [s for s in range(self.scfg.max_batch) if s not in sids]
        sids = np.asarray(sids + spare[: self.Bp - len(sids)], np.int32)
        vmask = np.zeros(self.Bp, bool)
        vmask[: len(vrows)] = vrows
        zmask = np.zeros(self.Bp, bool)
        zmask[: len(zero)] = zero
        self._scatter(p["cache"], jnp.asarray(sids), vmask, zmask)
        now = self._clock()
        for j, (i, req) in enumerate(p["reqs"]):
            self._reserved.discard(i)
            if req is None:  # cancelled mid-prefill: row never lands
                continue
            self.slots[i] = {"req": req, "toks": [int(first[j])]}
            self.pos[i] = len(req.prompt)
            self.last_tok[i] = first[j]
            req.status = "decoding"
            self._emit(req, int(first[j]), now)
            if req.max_new <= 1 or (req.eos_token is not None
                                    and int(first[j]) == req.eos_token):
                self._finish(i)
        self.metrics["landings"] += 1
        self._inflight.remove(p)

    # -- token-packed admission ----------------------------------------------

    def _advance_packed(self, failed_group: Optional[int]) -> bool:
        """Run ONE token-packed prefill step: draw up to ``Rp`` rows from
        ALL in-flight admission batches (EDF + shortest-remaining-prefill,
        token-granular — :meth:`ChunkScheduler.pack_rows`), build the
        fixed-shape [Rp, Cp] token block with per-row (slot, pos0, length)
        metadata, advance every packed row by one chunk of its TRUE prompt
        in a single program, then land every batch whose live rows have
        all finished (cancelled rows pack nothing and all-cancelled
        batches drain without compute). Returns True if any row packed."""
        with self._span("serve.pack"):
            rows = self.sched.pack_rows(self._inflight, self.Rp)
            block = self._pack_block(rows) if rows else None
        if rows:
            tok, sids, pos0r, lens, valid, true_toks = block
            fg = (failed_group if self._model_ft(failed_group) is not None
                  else None)
            with self._span("serve.prefill"):
                self._pack_cache, self._pack_hlast = self._prefill_packed(
                    self.ft_params, self._pack_cache, self._pack_hlast,
                    jnp.asarray(tok), jnp.asarray(sids), jnp.asarray(pos0r),
                    jnp.asarray(lens), jnp.asarray(valid), failed_group=fg)
            self.prefill_calls += 1
            self.metrics["packed_calls"] += 1
            self.metrics["packed_tokens"] += true_toks
            self.metrics["packed_batches_peak"] = max(
                self.metrics["packed_batches_peak"],
                len({id(p) for p, _ in rows}))
            # ONE compiled shape whatever the packing mix — the census
            # records the [Rp, Cp] program signature, never the mix
            self._census_bump("prefill", (self.Rp, self.Cp))
            for p, i in rows:
                p["rowpos"][i] = min(int(p["rowpos"][i]) + self.Cp,
                                     int(p["lengths_np"][i]))
        for p in list(self._inflight):
            live = [i for i, (_, r) in enumerate(p["reqs"])
                    if r is not None]
            if all(int(p["rowpos"][i]) >= int(p["lengths_np"][i])
                   for i in live):
                with self._span("serve.land"):
                    self._land_packed(p, failed_group)
        return bool(rows)

    def _pack_block(self, rows: list) -> tuple:
        """The host side of one packed step: the [Rp, Cp] token block and
        its per-row (slot, pos0, length, valid) metadata for ``rows``, and
        the count of true prompt tokens packed."""
        tok = np.zeros((self.Rp, self.Cp), np.int32)
        sids = np.zeros(self.Rp, np.int32)
        pos0r = np.zeros(self.Rp, np.int32)
        lens = np.zeros(self.Rp, np.int32)
        valid = np.zeros(self.Rp, bool)
        used = []
        true_toks = 0
        for r, (p, i) in enumerate(rows):
            off = int(p["rowpos"][i])
            n = min(self.Cp, int(p["lengths_np"][i]) - off)
            tok[r, :n] = p["tokens_np"][i, off : off + n]
            sids[r] = p["reqs"][i][0]
            pos0r[r] = off
            lens[r] = p["lengths_np"][i]
            valid[r] = True
            used.append(int(sids[r]))
            true_toks += n
        # pad rows stage in DISTINCT spare slots (their content is
        # gathered, run, and written back unchanged — valid is False)
        spare = [s for s in range(self.scfg.max_batch) if s not in used]
        for r in range(len(rows), self.Rp):
            sids[r] = spare.pop()
        return tok, sids, pos0r, lens, valid, true_toks

    def _land_packed(self, p: dict, failed_group: Optional[int]):
        """Gather a finished packed batch's staging rows into [Bp]-row
        order (original admission row order j — so the landing head's
        row -> group mapping ``j % M`` matches legacy chunking bit-for-
        bit) and run the shared landing tail."""
        sids_l = [i for i, _ in p["reqs"]]
        spare = [s for s in range(self.scfg.max_batch) if s not in sids_l]
        gsids = np.asarray(sids_l + spare[: self.Bp - len(sids_l)],
                           np.int32)
        p["cache"], p["h_last"] = self._gather_rows(
            self._pack_cache, self._pack_hlast, jnp.asarray(gsids))
        self._land(p, failed_group)

    def _emit(self, req: Request, tok: int, now: float):
        """Push a generated token into the request's streaming ring and
        stamp the latency bookkeeping (TTFT, per-token times)."""
        if req.t_first is None:
            req.t_first = now
        req.tok_times.append(now)
        ring = self._rings.get(id(req))
        if ring is not None:
            ring.push(tok)

    def _finish(self, i: int):
        s = self.slots[i]
        req = s["req"]
        req.out = np.asarray(s["toks"][: req.max_new], np.int32)
        req.status = "done"
        req.t_done = self._clock()
        self._rings.pop(id(req), None)  # handle keeps its own ring ref
        self.done.append(req)
        self._recycle(i)

    def cancel(self, req: Request):
        """Abandon a request in whatever state it is in: queued requests
        leave the queue; mid-prefill rows are voided (their chunk keeps
        computing under static shapes but never claims a slot, and the
        reserved slot frees immediately); decoding slots finalize their
        partial output and recycle. Finished requests are a no-op."""
        if req.status in ("done", "cancelled", "shed"):
            return
        if req.status == "queued":
            self.queue = [r for r in self.queue if r is not req]
        elif req.status == "prefill":
            for p in self._inflight:
                for j, (slot, r) in enumerate(p["reqs"]):
                    if r is req:
                        p["reqs"][j] = (slot, None)
                        self._reserved.discard(slot)
        else:  # decoding
            for i, s in enumerate(self.slots):
                if s is not None and s["req"] is req:
                    req.out = np.asarray(s["toks"], np.int32)
                    self._recycle(i)
        req.status = "cancelled"
        if req.out is None:
            req.out = np.zeros(0, np.int32)
        req.t_done = self._clock()
        self._rings.pop(id(req), None)
        self.metrics["cancelled"] += 1

    def _recycle(self, i: int):
        """Explicit slot recycling: mark the slot free and queue its cache
        row for zeroing, so no later tenant (or FT quantization scan) can
        see the old request's state.

        Admission would overwrite the row anyway, so this buys the
        invariant "a free slot's row is zeroed again before the next
        decode" — the zeroing itself is DEFERRED: it rides in the next
        landing scatter's spare capacity (``_advance_prefill``) or, when
        no landing absorbs it, one batched ``_flush_recycled`` scatter
        before decode — never one jitted insert per finished request."""
        self.slots[i] = None
        self.pos[i] = 0
        self.last_tok[i] = 0
        self._dirty.append(i)

    def _flush_recycled(self):
        """Zero freed slot rows that no landing scatter absorbed, one
        batched scatter per Bp slots. Re-occupied slots are skipped (their
        row was fully overwritten at landing); slots reserved by an
        in-flight batch stay queued for later (landing overwrites them —
        unless the row gets cancelled, in which case a later flush zeroes
        them)."""
        keep, flush = [], []
        for i in sorted(set(self._dirty)):
            if self.slots[i] is not None:
                continue
            (keep if i in self._reserved else flush).append(i)
        self._dirty = keep
        while flush:
            batch, flush = flush[: self.Bp], flush[self.Bp :]
            sids, vmask = self._pad_sids(batch)
            self._scatter(self._fresh_prefill, sids,
                          np.asarray(vmask), np.asarray(vmask))

    def step(self, failed_group: Optional[int] = None) -> int:
        """One engine step: advance the bucketed admission pipeline, then
        ONE batched jitted decode call for all active slots. Returns the
        number of active slots.

        Unchunked (``prefill_chunk=0``): every bucket batch completes in a
        single call, and the step keeps admitting further batches while
        free slots and queued requests remain. Chunked: at most
        ``max_prefill_per_step`` prefill chunks (default 1, EDF-ordered
        across the in-flight batches) run per step before the decode call,
        so a long prompt batch being admitted never stalls the decode
        latency of active slots — and with ``refill`` on, slots freed by
        finishing requests are planned straight back into the live chunk
        stream instead of waiting for the wave to drain.

        ``failed_group`` injects a fail-stop into that entangled group's
        head-GEMM compute for this step — decode and admission projections
        alike; the kernel rolls it forward, so outputs are unchanged."""
        with self._span("serve.step"):
            return self._step(failed_group)

    def _step(self, failed_group: Optional[int]) -> int:
        if failed_group is not None:
            if self.scfg.ft_mode != "entangle":
                raise ValueError("failed_group requires ft_mode='entangle'")
            if not 0 <= failed_group < self.scfg.ft_M:
                # the kernel indexes streams mod M; wrapping silently would
                # make an injection drill report a group it never failed
                raise ValueError(
                    f"failed_group={failed_group} out of range for "
                    f"ft_M={self.scfg.ft_M}")
        # shed lapsed deadlines BEFORE spending any prefill compute on
        # them — they would miss their SLA anyway, and the refunded chunk
        # budget goes to requests that can still make it
        with self._span("serve.shed"):
            if any(r.deadline_ms is not None for r in self.queue):
                kept, shed = self.sched.shed_expired(self.queue)
                self.queue = kept
                for req in shed:
                    req.status = "shed"
                    req.out = np.zeros(0, np.int32)
                    req.t_done = self._clock()
                    self._rings.pop(id(req), None)
                    self.metrics["shed"] += 1
        # admission: plan (EDF over the wait queue; with refill, freed
        # slots re-enter the stream mid-flight) and advance up to the
        # chunk budget. Unchunked admission completes a batch per call, so
        # the budget is infinite and the loop drains queue + free slots
        # within the step exactly like boundary admission always did.
        if self.Rp:
            # token-packed admission: plan every formable batch FIRST so
            # mixed-bucket admissions co-pack into the same [Rp, Cp]
            # program, then run up to max_prefill_per_step packed steps
            for _ in range(self.scfg.max_prefill_per_step):
                with self._span("serve.plan"):
                    while self._plan_admission():
                        pass
                if not self._advance_packed(failed_group):
                    break
        else:
            budget = (self.scfg.max_prefill_per_step
                      if self.scfg.prefill_chunk else float("inf"))
            while budget > 0:
                with self._span("serve.plan"):
                    self._plan_admission()
                p = self.sched.pick_batch(self._inflight)
                if p is None:
                    break
                self._advance_prefill(p, failed_group)
                budget -= 1
        # zero any freed rows no landing scatter absorbed: decode below
        # sees exactly the state boundary admission would have produced
        with self._span("serve.flush"):
            self._flush_recycled()
        active_idx = [i for i, s in enumerate(self.slots) if s is not None]
        if active_idx:
            B = self.scfg.max_batch
            active = np.zeros(B, bool)
            active[active_idx] = True
            head = (None if self.scfg.ft_mode != "entangle"
                    else (self.head_q, self.w_scale))
            with self._span("serve.decode"):
                nxt, self.cache = self._decode(
                    self.ft_params, self.cache, jnp.asarray(self.last_tok),
                    jnp.asarray(self.pos), jnp.asarray(active), head,
                    failed_group=failed_group)
            self.decode_calls += 1
            self._census_bump("decode", (len(active_idx), B))
            with self._span("serve.decode.sync"):
                nxt = np.asarray(nxt)
            with self._span("serve.emit"):
                now = self._clock()
                for i in active_idx:
                    s = self.slots[i]
                    req = s["req"]
                    self.pos[i] += 1
                    tok = int(nxt[i])
                    s["toks"].append(tok)
                    self.last_tok[i] = nxt[i]
                    self._emit(req, tok, now)
                    if (len(s["toks"]) >= req.max_new
                            or (req.eos_token is not None
                                and tok == req.eos_token)):
                        self._finish(i)
        return sum(s is not None for s in self.slots)

    def idle(self) -> bool:
        """True when the engine has nothing to serve: empty wait queue, no
        admission batch mid-chunk, every slot free. Open-loop drivers poll
        this to decide between stepping and waiting for the next arrival."""
        return (not self.queue and not self._inflight
                and all(s is None for s in self.slots))

    def run_to_completion(self, max_steps: int = 1000,
                          failed_group: Optional[int] = None) -> list[Request]:
        """Drain the queue. ``failed_group`` injects the fail-stop on EVERY
        decode step and admission projection — the strongest roll-forward
        drill."""
        steps = 0
        while not self.idle() and steps < max_steps:
            self.step(failed_group=failed_group)
            steps += 1
        return self.done

    # -- startup autotune warmup ---------------------------------------------

    def warm_autotune(self) -> dict:
        """Warm the kernel autotune cache for the engine's protected-GEMM
        shape census — the head's decode AND prefill-admission shapes plus,
        with an ``ft_scope`` beyond ``head``, EVERY in-model protected site
        at every decode/chunk call shape (the ROADMAP contract). Sweeps run
        HERE, eagerly; the in-jit ``blocks='auto'`` resolution then only
        ever cache-hits, whether it fires inside the traced decode step,
        a traced prefill chunk or a traced head projection. No-op unless
        the entangled head is on and ``blocks == 'auto'``."""
        if self.scfg.ft_mode != "entangle" or self.scfg.blocks != "auto":
            return {}
        M, B = self.plan.M, self.scfg.max_batch
        D, V = self._head_dims  # true dims; self.head_q may be packed
        packed = self.scfg.ft_packed
        # prefill admission batches are padded to a multiple of M
        # (ft_logits_prefill), so the per-group row count is ceil(Bp / M)
        shapes = {(M, B // M, D, V), (M, -(-self.Bp // M), D, V)}
        won = {}
        for shape in sorted(shapes):
            won[shape] = kops.warm_entangled_matmul(
                *shape, self.plan, fuse_epilogue=True, packed=packed)
            self.census.setdefault("head_gemm", {})[shape] = won[shape]
        for site, shape in sorted(self.protected_census):
            # 5-tuple shapes are grouped (MoE per-expert) sites
            warm = (kops.warm_entangled_matmul_grouped if len(shape) == 5
                    else kops.warm_entangled_matmul)
            w = warm(*shape, self.plan, fuse_epilogue=True, packed=packed)
            self.census.setdefault("protected", {})[(site, shape)] = w
            won[(site, shape)] = w
        return won

    def _all_chunk_widths(self) -> frozenset:
        """Every prefill-chunk width any admission can run, derived from
        the bucket set and chunk size alone. Mid-flight refill replays
        these SAME widths — a refilled batch is just another [Bp, bucket]
        program — which is why refill can never retrace or miss a compiled
        plan (``CompiledPlans.misses`` stays 0; tested)."""
        widths = set()
        for Tb in self.buckets:
            step = self.scfg.prefill_chunk or Tb
            pos0 = 0
            while pos0 < Tb:
                sz = min(step, Tb - pos0)
                widths.add(sz)
                pos0 += sz
        return frozenset(widths)

    def _protected_shape_census(self) -> dict:
        """{(site, (M, Bg, K, N)): blocks} for every in-model protected
        GEMM the engine can trace, enumerated by abstract-evaluating the
        decode step and one prefill chunk per distinct chunk width with a
        census-only :class:`repro.ft.FTContext` — every PlanEntry is
        constructed HERE, at startup, in the engine's own registry; no
        kernel runs, nothing compiles. Empty at ft_scope='head'."""
        if self.scfg.ft_mode != "entangle" or self.scfg.ft_scope == "head":
            return {}
        ctx = dataclasses.replace(self.ftx, census_only=True)
        B = self.scfg.max_batch
        jax.eval_shape(
            lambda p, c: self.model.decode_hidden(
                p, jnp.zeros((B, 1), jnp.int32), c,
                jnp.zeros((B,), jnp.int32), self.cfg, ft=ctx),
            self.params, self.cache)
        if self.Rp:
            # token-packed mode runs exactly ONE prefill program shape —
            # [Rp, Cp] tokens over Rp gathered staging rows — for every
            # packing mix, so the census holds one prefill entry set and
            # CompiledPlans.misses == 0 is checkable for any traffic
            jax.eval_shape(
                lambda p, c: self.model.prefill_packed(
                    p, jnp.zeros((self.Rp, self.Cp), jnp.int32), self.cfg,
                    c, pos0=jnp.zeros((self.Rp,), jnp.int32),
                    lengths=jnp.zeros((self.Rp,), jnp.int32), ft=ctx),
                self.params,
                self.model.init_cache(self.cfg, self.Rp, self.scfg.max_seq))
        else:
            for C in sorted(self._all_chunk_widths()):
                jax.eval_shape(
                    lambda p, c, _C=C: self.model.prefill_chunk(
                        p, jnp.zeros((self.Bp, _C), jnp.int32), self.cfg, c,
                        pos0=0, lengths=jnp.zeros((self.Bp,), jnp.int32),
                        ft=ctx),
                    self.params, self._fresh_prefill)
        return self.registry.census()
