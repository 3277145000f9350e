"""ProtectedLinear — the paper's entangled roll-forward wrapped around any
hot-path GEMM.

:func:`protected_matmul` is the one code path every plain protected
projection runs through: float activations of ANY leading shape are
flattened to rows, quantized onto the plan's eq. (13) integer grid
(:mod:`repro.ft.quantize` — PER-ROW scales, so no row's grid depends on
its batch neighbours), padded with zero rows to a multiple of M
(exact — zeros entangle to zeros and cannot perturb any other stream's
accumulator), mapped round-robin onto the
M entangled streams (row -> group = row % M, the serving engine's
slot -> group contract), and pushed through the fused kernel behind
:mod:`repro.kernels.ops` (backend-pluggable: Pallas TPU, interpret CPU,
reference, or a registered port): entangle-on-load, int GEMM, extraction
in the flush epilogue — one kernel call, zero codec HBM sweeps. A
fail-stopped group's accumulator is statically excluded from the in-kernel
extraction (``failed=r``), so its outputs are rolled forward from the
other M-1 streams and the recovered integers are bit-identical to a
healthy run.

:func:`protected_matmul_grouped` is the grouped (per-expert) twin for MoE:
activations ``[..., E, C, K]`` against per-expert weights ``[E, K, N]``
run as ONE grouped entangled kernel call — rows map round-robin onto the M
streams *within each expert*, so recovery holds independently and
identically for every expert.

:class:`FTContext` is the object threaded through the model
(``models/api.py -> transformer.apply_stack -> layers``): it decides which
site categories the configured ``ft_scope`` protects, resolves each call
site's :class:`~repro.ft.registry.ProtectionPlan` — ahead-of-time from the
immutable :class:`~repro.ft.plans.CompiledPlans` the engine builds at
startup, or lazily from the registry for library users — and carries the
static ``failed_group`` of the current traced program.  Site names are
``"<category>.<proj>"`` — categories:

  ``head``  the vocab projection (always protected when FT is on)
  ``qkv``   mixer input projections: attention Q/K/V, MLA q/kv_a,
            Mamba in_proj, RG-LRU in_x/in_gate
  ``mlp``   FFN projections: MLP gate/up/down (dense and MoE-shared) and
            the MoE router
  ``out``   mixer output projections: attention/MLA wo, Mamba out_proj,
            RG-LRU out
  ``moe``   MoE per-expert gate/up/down GEMMs (the grouped kernel)

``ft_scope`` widens protection cumulatively: ``"head"`` | ``"qkv"`` |
``"mlp"`` | ``"out"`` | ``"moe"`` (each includes the head) | ``"all"`` —
which, since v2, genuinely covers every hot-path GEMM.

Each protected call traces under ``jax.named_scope("ft.<site>")`` (a
fanout group's shared codec pass under ``ft.<site>+<site>...``), so each
compiled operation's metadata (``op_name``) names its site; the scopes
change no operation.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.entangle import disentangle as core_disentangle
from repro.core.entangle import entangle as core_entangle
from repro.core.failstop import GARBAGE
from repro.core.plan import EntanglePlan
from repro.ft.quantize import (chain_budget, quantize_acts, quantize_weight,
                               quantize_weight_stacked)
from repro.kernels.codec import unpack_int8
from repro.ft.registry import PlanRegistry, ProtectionPlan, group_rows

# scope -> protected site categories (cumulative; head is always in)
SCOPES: dict[str, frozenset] = {
    "head": frozenset({"head"}),
    "qkv": frozenset({"head", "qkv"}),
    "mlp": frozenset({"head", "mlp"}),
    "out": frozenset({"head", "out"}),
    "moe": frozenset({"head", "moe"}),
    "all": frozenset({"head", "qkv", "mlp", "out", "moe"}),
}

# float weight, or (int8-range int32 weights, scale) pre-quantized at startup
Weight = Union[jax.Array, tuple]


def group_order(R: int, M: int) -> tuple[np.ndarray, np.ndarray]:
    """Static permutation realizing round-robin grouping (row -> group =
    row % M) on top of a contiguous [M, R/M] stream layout.

    ``order[g * R//M + j] = j * M + g`` — position p of the permuted batch
    holds row ``order[p]``; ``inv`` undoes it (``inv[row]`` = position of
    that row's output in the permuted result). Round-robin keeps every
    entangled group populated whenever >= M rows are live, so a fail-stop
    in any group is recoverable from M-1 *other* live groups.
    """
    assert R % M == 0, f"row count {R} must split into M={M} groups"
    order = np.arange(R, dtype=np.int32).reshape(R // M, M).T.reshape(R)
    inv = np.argsort(order).astype(np.int32)
    return order, inv


def _split_weight(w: Weight):
    """(wq, w_scale) from a float master (in-graph quantization — the
    legacy/library path) or a pre-quantized (wq, scale) pair (the v2
    prepared-params path; no quantization op enters the trace)."""
    if isinstance(w, tuple):
        return w
    return quantize_weight(w)


def _is_packed(wq: jax.Array, K: int, axis: int = -2) -> bool:
    """Packedness of a pre-quantized weight, from its contraction-axis
    length: the packed copy carries ceil(K/4) int32 words for K int8
    lanes. Every protected K is >= 2, so the lengths can never collide."""
    return wq.shape[axis] != K


def _unpacked_f32(wq: jax.Array, K: int, axis: int) -> jax.Array:
    """Float view of a maybe-packed weight for the census einsums (the
    abstract traces only need shapes; a float master passes through)."""
    if _is_packed(wq, K, axis=axis):
        wq = unpack_int8(wq, axis=axis, n=K)
    return wq.astype(jnp.float32)


def protected_matmul(
    x: jax.Array,  # [..., K] float activations
    w: Weight,  # [K, N] float weights, or (wq, w_scale) pre-quantized
    *,
    plan: EntanglePlan,
    failed_group: Optional[int] = None,
    use_pallas: bool = True,
    fuse_epilogue: bool = True,
    blocks=None,
    contiguous: bool = False,
    interpret=None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Entangled int8 GEMM with in-kernel fail-stop roll-forward.

    Returns dequantized float32 outputs ``[..., N]``. ``contiguous=True``
    keeps the caller's row order as the [M, R/M] group layout (the library
    :func:`repro.ft.heads.ft_logits` contract); the default maps rows
    round-robin onto groups. ``fuse_epilogue=False`` keeps the separate
    disentangle pass for callers that must inject/persist entangled
    outputs; ``use_pallas=False`` is the XLA reference path; ``backend``
    routes to a registered kernel backend (default: the platform rule).
    """
    wq, w_scale = _split_weight(w)
    lead, K = x.shape[:-1], x.shape[-1]
    N = wq.shape[1]
    packed = _is_packed(wq, K)
    R = int(np.prod(lead, dtype=np.int64)) if lead else 1
    M = plan.M

    xf = x.reshape(R, K).astype(jnp.float32)
    xq, a_scale = quantize_acts(xf, plan, K)
    pad = (-R) % M
    if pad:
        xq = jnp.concatenate([xq, jnp.zeros((pad, K), xq.dtype)], axis=0)
    Rp = R + pad
    if contiguous:
        inv = None
        xg = xq.reshape(M, Rp // M, K)
    else:
        order, inv = group_order(Rp, M)
        xg = xq[order].reshape(M, Rp // M, K)

    from repro.kernels import ops as kops  # deferred: keeps core import-light

    if use_pallas and fuse_epilogue:
        # production hot path: entangle -> GEMM -> extract in ONE
        # kernel call; a fail-stopped group is rolled forward in-kernel by
        # statically excluding its accumulator from the extraction (the
        # algebra never reads it, so injecting garbage is equivalent)
        rec = kops.entangled_matmul(
            xg, wq, plan, fuse_epilogue=True, failed=failed_group,
            packed=packed, blocks=blocks, interpret=interpret,
            backend=backend)
    else:
        if use_pallas:
            delta = kops.entangled_matmul(xg, wq, plan, packed=packed,
                                          blocks=blocks, interpret=interpret,
                                          backend=backend)
        else:
            eps = core_entangle(xg.astype(jnp.int32), plan)
            wq_full = unpack_int8(wq, axis=0, n=K) if packed else wq
            delta = jnp.einsum("mbk,kn->mbn", eps, wq_full).astype(jnp.int32)
        if failed_group is not None:
            delta = delta.at[failed_group].set(GARBAGE)
        rec = core_disentangle(delta, plan, failed=failed_group)

    y = rec.reshape(Rp, N).astype(jnp.float32)
    if inv is not None:
        y = y[inv]
    y = y[:R] / (a_scale * w_scale)
    return y.reshape(*lead, N)


def protected_matmul_grouped(
    x: jax.Array,  # [..., E, C, K] float activations (C rows per expert)
    w: Weight,  # [E, K, N] float, or (wq [E, K, N], w_scale scalar or [E])
    *,
    plan: EntanglePlan,
    failed_group: Optional[int] = None,
    use_pallas: bool = True,
    fuse_epilogue: bool = True,
    blocks=None,
    interpret=None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Grouped (per-expert) entangled int8 GEMM — the MoE form.

    Expert e's C rows (times any leading batch axes) multiply expert e's
    [K, N] weights; all E GEMMs run in ONE grouped entangled kernel call
    (:func:`repro.kernels.ops.entangled_matmul_grouped`). Rows map
    round-robin onto the M streams within each expert, zero rows pad each
    expert's bucket to a multiple of M (exact), and ``failed_group``
    statically excludes that stream's accumulators from extraction — the
    roll-forward recovers every expert's outputs bit-identically at once.
    Returns dequantized float32 ``[..., E, C, N]``.
    """
    if isinstance(w, tuple):
        wq, w_scale = w
    else:
        q8 = quantize_weight_stacked(w)  # per-expert grids
        wq, w_scale = q8["w"], q8["scale"]
    E, N = wq.shape[0], wq.shape[2]
    K = x.shape[-1]
    packed = _is_packed(wq, K)
    lead = x.shape[:-3]
    C = x.shape[-2]
    assert x.shape[-3] == E, (x.shape, wq.shape)
    L = int(np.prod(lead, dtype=np.int64)) if lead else 1
    R = L * C  # rows per expert
    M = plan.M

    # [..., E, C, K] -> [E, R, K]: expert-major rows, leading axes folded
    xf = jnp.moveaxis(x.reshape(L, E, C, K), 1, 0).reshape(E, R, K)
    xf = xf.astype(jnp.float32)
    xq, a_scale = quantize_acts(xf, plan, K)
    pad = (-R) % M
    if pad:
        xq = jnp.concatenate(
            [xq, jnp.zeros((E, pad, K), xq.dtype)], axis=1)
    Rp = R + pad
    order, inv = group_order(Rp, M)
    # per-expert round-robin onto streams: [E, Rp, K] -> [M, E, Rp/M, K]
    xg = jnp.moveaxis(xq[:, order].reshape(E, M, Rp // M, K), 1, 0)

    from repro.kernels import ops as kops  # deferred: keeps core import-light

    if use_pallas and fuse_epilogue:
        rec = kops.entangled_matmul_grouped(
            xg, wq, plan, fuse_epilogue=True, failed=failed_group,
            packed=packed, blocks=blocks, interpret=interpret,
            backend=backend)
    else:
        if use_pallas:
            delta = kops.entangled_matmul_grouped(
                xg, wq, plan, packed=packed, blocks=blocks,
                interpret=interpret, backend=backend)
        else:
            eps = core_entangle(xg.astype(jnp.int32), plan)
            wq_full = unpack_int8(wq, axis=1, n=K) if packed else wq
            delta = jnp.einsum("meck,ekn->mecn", eps,
                               wq_full.astype(jnp.int32)).astype(jnp.int32)
        if failed_group is not None:
            delta = delta.at[failed_group].set(GARBAGE)
        rec = core_disentangle(delta, plan, failed=failed_group)

    y = jnp.moveaxis(rec, 0, 1).reshape(E, Rp, N).astype(jnp.float32)
    y = y[:, inv][:, :R]
    w_s = jnp.asarray(w_scale)
    scale = a_scale * (w_s if w_s.ndim == 0 else w_s[:, None, None])
    y = y / scale
    return jnp.moveaxis(y.reshape(E, L, C, N), 0, 1).reshape(*lead, E, C, N)


def entangled_chain(
    x: jax.Array,  # [..., K] float activations of the FIRST hop
    ws: list,  # per-hop weights: float [K_i, N_i] or (wq, w_scale) pairs
    *,
    plan: EntanglePlan,
    failed_group: Optional[int] = None,
    blocks=None,  # None, or one blocks policy per hop
    contiguous: bool = False,
    interpret=None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Run N consecutive strictly-linear protected GEMMs WITHOUT leaving
    the entangled domain: one entangle, N GEMMs, one extract.

    Entanglement is linear over streams, so ``(E c) @ g = E (c @ g)`` —
    the first hop entangles on load and returns raw entangled accumulators
    (``fuse_epilogue=False``), every middle hop multiplies them through a
    plain per-stream GEMM (``'chain'``: no re-entangle, no extract), and
    the last hop extracts at its flush (``'chain_final'``). A fail-stopped
    stream's garbage propagates only within its own stream (each hop is
    per-stream), and the final extraction statically excludes it — the
    roll-forward is exact for any single failed stream failing at ANY
    point in the chain.

    The price is overflow headroom: the single extraction must absorb the
    whole chain's amplification, so the first hop quantizes onto
    :func:`~repro.ft.quantize.chain_budget`'s grid. When that budget is 0
    the chain is infeasible under this plan and the call falls back to
    per-hop :func:`protected_matmul` extraction (same protection, one
    extract per hop, requantizing between hops).

    Returns dequantized float32 outputs ``[..., N_last]``.
    """
    assert len(ws) >= 1
    split = [_split_weight(w) for w in ws]
    lead, K = x.shape[:-1], x.shape[-1]
    depths = [K]
    for wq, _ in split[:-1]:
        n = wq.shape[1]
        # a packed hop's true N is its column count (packing is along K
        # only), so the next hop's depth is simply shape[1]
        depths.append(n)
    budget = chain_budget(plan, depths)
    if budget < 1 or len(ws) == 1:
        # infeasible under this plan (or trivial): extract per hop
        y = x
        bl = blocks if blocks is not None else [None] * len(ws)
        for w, b in zip(ws, bl):
            y = protected_matmul(
                y, w, plan=plan, failed_group=failed_group, blocks=b,
                contiguous=contiguous, interpret=interpret, backend=backend)
        return y

    M = plan.M
    R = int(np.prod(lead, dtype=np.int64)) if lead else 1
    xf = x.reshape(R, K).astype(jnp.float32)
    xq, a_scale = quantize_acts(xf, plan, K, budget=budget)
    pad = (-R) % M
    if pad:
        xq = jnp.concatenate([xq, jnp.zeros((pad, K), xq.dtype)], axis=0)
    Rp = R + pad
    if contiguous:
        inv = None
        xg = xq.reshape(M, Rp // M, K)
    else:
        order, inv = group_order(Rp, M)
        xg = xq[order].reshape(M, Rp // M, K)

    from repro.kernels import ops as kops  # deferred: keeps core import-light

    bl = blocks if blocks is not None else [None] * len(ws)
    cur, depth = xg, K
    for i, (wq, _) in enumerate(split):
        if i == 0:
            mode = False  # entangle on load, keep entangled
        elif i == len(split) - 1:
            mode = "chain_final"  # extract at the last flush
        else:
            mode = "chain"
        cur = kops.entangled_matmul(
            cur, wq, plan, fuse_epilogue=mode, failed=failed_group,
            packed=_is_packed(wq, depth), blocks=bl[i],
            interpret=interpret, backend=backend)
        depth = wq.shape[1]

    N = split[-1][0].shape[1]
    y = cur.reshape(Rp, N).astype(jnp.float32)
    if inv is not None:
        y = y[inv]
    w_prod = 1.0
    for _, s in split:
        w_prod = w_prod * s
    y = y[:R] / (a_scale * w_prod)
    return y.reshape(*lead, N)


@dataclasses.dataclass(frozen=True)
class ProtectedLinear:
    """Thin executor over ONE compiled :class:`ProtectionPlan`.

    Since v2 this class holds no resolution logic: the plan (site, shape,
    entanglement parameters, block sizes, backend, grouped-ness) is fixed
    at construction — built ahead of time by
    :func:`repro.ft.plans.compile_plans` — and calling the executor just
    runs :func:`protected_matmul` / :func:`protected_matmul_grouped` with
    those static parameters. The serving engine holds one per protected
    (site, shape) implicitly through :class:`FTContext`; library users can
    bind one directly from a registry entry.
    """

    plan: ProtectionPlan
    use_pallas: bool = True
    interpret: Optional[bool] = None

    def __call__(self, x: jax.Array, w: Weight, *,
                 failed_group: Optional[int] = None,
                 contiguous: bool = False) -> jax.Array:
        p = self.plan
        if p.grouped:
            return protected_matmul_grouped(
                x, w, plan=p.plan, failed_group=failed_group,
                use_pallas=self.use_pallas, blocks=p.blocks,
                interpret=self.interpret, backend=p.backend)
        return protected_matmul(
            x, w, plan=p.plan, failed_group=failed_group,
            use_pallas=self.use_pallas, blocks=p.blocks,
            contiguous=contiguous, interpret=self.interpret,
            backend=p.backend)


def _backend() -> str:
    """Registry backend tag — the kernel-registry namespace this process
    resolves to (mirrors :func:`repro.kernels.ops.resolve_backend`)."""
    from repro.kernels import ops as kops

    return kops.resolve_backend()


@dataclasses.dataclass(frozen=True)
class FTContext:
    """Protection context threaded through the model forward pass.

    Created once by the serving engine at startup and specialized per
    traced program via :meth:`with_failed` (``failed_group`` is a static
    jit argument, so each injected-failure variant is its own compiled
    program sharing the same plans and autotune winners).

    ``plans`` (the v2 flow) is the immutable
    :class:`~repro.ft.plans.CompiledPlans` built by ``compile_plans`` at
    startup: every protected projection resolves there, and a lookup miss
    — a census gap — falls back to a lazily created registry entry with a
    warning instead of crashing the serving process. ``plans=None`` keeps
    the pure lazy-registry behavior for library users.

    ``census_only=True`` turns :meth:`matmul` / :meth:`matmul_grouped`
    into plain float einsums that merely REGISTER the call shape — the
    engine abstract-traces the forward pass with such a context to
    enumerate every protected shape without running (or compiling) any
    kernel; ``compile_plans`` then freezes exactly that census.
    """

    registry: PlanRegistry
    scope: str = "head"
    use_pallas: bool = True
    failed_group: Optional[int] = None
    census_only: bool = False
    plans: Optional[object] = None  # repro.ft.plans.CompiledPlans
    # share one quantize/permute codec pass across fanout site groups
    # (sites consuming the same activations — attention Q/K/V, MLP
    # gate/up, ...); census-only traces mark the groups either way, so
    # the compiled plans always expose what COULD chain
    chain: bool = True

    def __post_init__(self):
        if self.scope not in SCOPES:
            raise ValueError(
                f"unknown ft_scope {self.scope!r}; expected one of "
                f"{sorted(SCOPES)}")

    @property
    def plan(self) -> EntanglePlan:
        return self.registry.plan

    def protects(self, site: str) -> bool:
        return site.split(".", 1)[0] in SCOPES[self.scope]

    def with_failed(self, failed_group: Optional[int]) -> "FTContext":
        return dataclasses.replace(self, failed_group=failed_group)

    def with_plans(self, plans) -> "FTContext":
        return dataclasses.replace(self, plans=plans)

    def _resolve(self, site: str, rows: int, K: int, N: int,
                 groups: Optional[int] = None) -> ProtectionPlan:
        """AOT plan lookup with a loud-but-degrading lazy fallback."""
        if self.plans is not None:
            shape = self.registry.shape_for(rows, K, N, groups)
            p = self.plans.lookup(site, shape)
            if p is not None:
                return p
            warnings.warn(
                f"protected site {site!r} shape {shape} is missing from "
                f"the compiled plans (startup census gap); creating a "
                f"lazy registry entry", RuntimeWarning)
        return self.registry.entry(site, rows, K, N, _backend(),
                                   groups=groups)

    def matmul(self, site: str, x: jax.Array, w: Weight) -> jax.Array:
        """Run (or, census-only, record) one protected GEMM site."""
        wq = w[0] if isinstance(w, tuple) else w
        # K comes from the ACTIVATIONS: a packed q8 copy's contraction
        # axis holds ceil(K/4) words, never the true depth
        K, N = x.shape[-1], wq.shape[-1]
        rows = int(np.prod(x.shape[:-1], dtype=np.int64)) if x.ndim > 1 else 1
        if self.census_only:
            self.registry.entry(site, rows, K, N, _backend())
            return jnp.einsum("...k,kn->...n", x.astype(jnp.float32),
                              _unpacked_f32(wq, K, axis=0))
        plan = self._resolve(site, rows, K, N)
        with jax.named_scope(f"ft.{site}"):
            return ProtectedLinear(plan=plan, use_pallas=self.use_pallas)(
                x, w, failed_group=self.failed_group)

    def matmul_fanout(self, sites: tuple, x: jax.Array,
                      ws: tuple) -> list:
        """Run (or record) a FANOUT site group: every site in ``sites``
        multiplies the SAME activations ``x`` against its own weight.

        With ``chain=True`` the group shares one quantize + group-permute
        + pad codec pass — the dominant non-GEMM cost of a protected site
        — and each member then runs its own fused entangle-GEMM-extract
        kernel call. Bit-identical to per-site :meth:`matmul` calls: the
        activation grid depends only on (x, plan, K), which the group
        shares by construction, and extraction is per output column.
        Census-only traces additionally mark the group as chainable
        (:meth:`~repro.ft.registry.PlanRegistry.note_chain`), so the
        compiled plans expose the chain sites at plan-compile time.
        Returns one output per site, in order.
        """
        K = x.shape[-1]
        rows = int(np.prod(x.shape[:-1], dtype=np.int64)) if x.ndim > 1 else 1
        wqs = [w[0] if isinstance(w, tuple) else w for w in ws]
        if self.census_only:
            self.registry.note_chain(tuple(sites))
            return [self.matmul(s, x, w) for s, w in zip(sites, ws)]
        if not self.chain:
            return [self.matmul(s, x, w) for s, w in zip(sites, ws)]

        plans = [self._resolve(s, rows, K, wq.shape[-1])
                 for s, wq in zip(sites, wqs)]
        plan = plans[0].plan
        M = plan.M
        lead = x.shape[:-1]
        # the shared codec pass is named by the whole group
        with jax.named_scope("ft." + "+".join(sites)):
            xf = x.reshape(rows, K).astype(jnp.float32)
            xq, a_scale = quantize_acts(xf, plan, K)
            pad = (-rows) % M
            if pad:
                xq = jnp.concatenate([xq, jnp.zeros((pad, K), xq.dtype)],
                                     axis=0)
            Rp = rows + pad
            order, inv = group_order(Rp, M)
            xg = xq[order].reshape(M, Rp // M, K)

        from repro.kernels import ops as kops

        outs = []
        for s, p, w in zip(sites, plans, ws):
            with jax.named_scope(f"ft.{s}"):
                wq_i, w_scale = _split_weight(w)
                N = wq_i.shape[-1]
                rec = kops.entangled_matmul(
                    xg, wq_i, p.plan, fuse_epilogue=True,
                    failed=self.failed_group, packed=_is_packed(wq_i, K),
                    blocks=p.blocks, backend=p.backend)
                y = rec.reshape(Rp, N).astype(jnp.float32)
                y = y[inv][:rows] / (a_scale * w_scale)
                outs.append(y.reshape(*lead, N))
        return outs

    def matmul_grouped(self, site: str, x: jax.Array,
                       w: Weight) -> jax.Array:
        """Run (or record) one grouped per-expert protected GEMM site:
        x [..., E, C, K] against per-expert weights [E, K, N]."""
        wq = w[0] if isinstance(w, tuple) else w
        E, N = wq.shape[-3], wq.shape[-1]
        K = x.shape[-1]
        rows = int(np.prod(x.shape[:-3], dtype=np.int64)) * x.shape[-2]
        if self.census_only:
            self.registry.entry(site, rows, K, N, _backend(), groups=E)
            return jnp.einsum("...eck,ekn->...ecn", x.astype(jnp.float32),
                              _unpacked_f32(wq, K, axis=1))
        plan = self._resolve(site, rows, K, N, groups=E)
        with jax.named_scope(f"ft.{site}"):
            return ProtectedLinear(plan=plan, use_pallas=self.use_pallas)(
                x, w, failed_group=self.failed_group)
