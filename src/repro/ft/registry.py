"""Plan registry: one :class:`ProtectionPlan` per (site, shape, M, backend)
protected GEMM.

The serving engine constructs ONE registry at startup; every protected
projection — head, QKV, MLP up/down, MoE router, the attention/SSM output
projections and the MoE per-expert GEMMs — resolves its
:class:`ProtectionPlan` here, so the whole forward pass shares a single
:class:`~repro.core.plan.EntanglePlan` (stable autotune/compile keys across
the serving lifetime) while each call shape gets its own block-size
decision:

  * ``blocks`` policy ``None`` — :func:`default_blocks`: on the compiled
    TPU backend, weight tiles of 1-2 MiB of int8 whose ``bk`` divides K,
    within the kernel's VMEM budget; elsewhere shape-clamped powers of
    two. Either way the row block covers the per-group row count, which
    at decode is tiny (max_batch / M), so the wrapper's 128-row default
    would pad it ~64x with zero rows every step;
  * ``blocks`` policy ``"auto"`` — the :mod:`repro.kernels.autotune`
    subsystem; the engine's ``warm_autotune`` pre-sweeps every registered
    shape eagerly so the in-jit resolution is a pure cache hit.

In the v2 flow the registry is populated ONCE at startup by the engine's
census-only abstract traces and then frozen into an immutable
:class:`repro.ft.plans.CompiledPlans` via :func:`repro.ft.plans.
compile_plans`; lazy trace-time creation remains for library users calling
:class:`~repro.ft.protected.FTContext` without a compile step.

Plan shapes: a plain GEMM site's shape is ``(M, Bg, K, N)``; a grouped
(MoE per-expert) site's shape is ``(M, E, Bg, K, N)`` with
``grouped=True`` — ``Bg`` then counts per-expert rows per stream.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core.plan import EntanglePlan
from repro.kernels.entangled_matmul import VMEM_BUDGET_BYTES, vmem_bytes


def group_rows(rows: int, M: int) -> int:
    """Per-group row count after padding ``rows`` to a multiple of M —
    the single source of the kernel-call batch dim, shared by the
    protected matmul, the registry keys and the autotune warmup."""
    return -(-rows // M)


def _pow2_cover(n: int, lo: int, hi: int) -> int:
    """Smallest power of two >= min(n, hi), floored at ``lo``."""
    p = lo
    while p < min(max(n, 1), hi):
        p *= 2
    return p


# smallest blocks per backend: the compiled TPU kernels feed int8 operands
# to the MXU, and int8 blocks tile as (32, 128) — rows (bb) in 32s, the
# contraction (bk) and output lanes (bn) in 128s
_FLOORS = {"pallas_tpu": {"bb": 32, "bn": 128, "bk": 128}}
_FLOOR_DEFAULT = {"bb": 8, "bn": 32, "bk": 32}


def row_block(Bg: int, backend: Optional[str] = None) -> int:
    """Row block (``bb``) covering ``Bg`` per-group rows on ``backend``."""
    return _pow2_cover(Bg, _FLOORS.get(backend, _FLOOR_DEFAULT)["bb"], 128)


def _divisor_block(K: int, step: int, cap: int) -> int:
    """Largest multiple of ``step`` up to ``cap`` that divides ``K``
    (0 if none)."""
    for b in range(min(cap, K) // step * step, 0, -step):
        if K % b == 0:
            return b
    return 0


# weight tiles of the compiled dense kernel: a grid step costs a fixed
# ~0.24 us besides pushing its weight tile through the MXU once, so every
# call takes a tile of about TILE_BYTES int8 bytes (one v5e: at 16, 64, 128
# and 512 rows per group such tiles beat 256 x 256 ones by 1.4-2x). ``bk``
# divides K (a ``bk`` that does not makes ``ops`` pad — copy — the whole
# weight matrix on every call) and stops at 2048 unless a narrow N needs a
# deeper tile to reach TILE_MIN; ``bn`` need not divide N (the last column
# block may be partial), is balanced over the column blocks, and shrinks —
# then ``bk`` — until the working set of the call's rows and streams fits
# the kernel's VMEM budget. The grouped (per-expert) kernel still pushes
# each tile once per stream product and runs under the default scoped
# VMEM limit, so grouped sites keep the shape-clamped powers of two.
TILE_BYTES = 2 << 20
TILE_MIN = 1 << 20
_BK_CAP = 2048


def _tpu_blocks(Bg: int, K: int, N: int, M: int, l: int) -> dict:
    lo = _FLOORS["pallas_tpu"]
    bb = row_block(Bg, "pallas_tpu")
    n_cols = -(-N // lo["bn"])  # N in lane tiles
    deep = -(-TILE_MIN // (n_cols * lo["bn"]))  # bk for the min tile
    bk = (_divisor_block(K, lo["bk"], max(_BK_CAP, deep))
          or _pow2_cover(K, lo["bk"], 256))
    if bk < deep:  # the smallest divisor of K deep enough, if any
        bk = next((b for b in range(bk, K + 1, lo["bk"])
                   if K % b == 0 and b >= deep), bk)
    per = max(1, min(TILE_BYTES // bk // lo["bn"], n_cols))

    def fits(per, bk):  # the worst call: int32 activations and container
        return vmem_bytes(bb, per * lo["bn"], bk, n=4, M=M, l=l,
                          packed=False) <= VMEM_BUDGET_BYTES

    while not fits(per, bk):
        if per > 1:
            per -= 1
        elif not (bk := _divisor_block(K, lo["bk"], bk - lo["bk"])):
            raise ValueError(f"no blocks of ({Bg}, {K}, {N}) at M={M} fit "
                             f"{VMEM_BUDGET_BYTES} B of VMEM")
    per = -(-n_cols // -(-n_cols // per))  # balance the column blocks
    return {"bb": bb, "bn": per * lo["bn"], "bk": bk}


def default_blocks(Bg: int, K: int, N: int,
                   backend: Optional[str] = None, *,
                   plan: Optional[EntanglePlan] = None,
                   grouped: bool = False) -> dict:
    """Shape-clamped block sizes for one (Bg, K, N) protected GEMM: the
    compiled dense kernel's weight tiles (see :data:`TILE_BYTES`), fitted
    to the VMEM working set of ``plan``'s streams (M=4, l=8 if none),
    else — and for ``grouped`` (per-expert) sites — powers of two capped
    at 256 (a compiled grouped ``bk`` divides K where a multiple of 128
    up to 256 does)."""
    if backend == "pallas_tpu" and not grouped:
        return _tpu_blocks(Bg, K, N, plan.M if plan else 4,
                           plan.l if plan else 8)
    lo = _FLOORS.get(backend, _FLOOR_DEFAULT)
    bk = _pow2_cover(K, lo["bk"], 256)
    if backend == "pallas_tpu" and K % bk:
        # a bk that does not divide K pads (copies) every expert's weights
        # on each call: take the largest dividing one (DeepSeek-V2-Lite's
        # expert down projection, K=1408, takes 128)
        bk = _divisor_block(K, lo["bk"], 256) or bk
    return {"bb": row_block(Bg, backend),
            "bn": _pow2_cover(N, lo["bn"], 256), "bk": bk}


@dataclasses.dataclass(frozen=True)
class ProtectionPlan:
    """Immutable protection parameters of one GEMM site at one call shape.

    Built ahead of time (engine startup census -> ``compile_plans``) or
    lazily at trace time (library use); either way every field is static:
    a :class:`~repro.ft.protected.ProtectedLinear` bound to a plan is a
    pure executor, and the traced program can never re-derive blocks,
    shapes or entanglement parameters mid-flight.
    """

    site: str
    shape: tuple  # (M, Bg, K, N) — or (M, E, Bg, K, N) when grouped
    backend: str
    plan: EntanglePlan
    blocks: object  # None | dict | "auto" — passed through to kernels.ops
    grouped: bool = False
    # the site's startup-quantized q8 copy is int8-packed 4-per-word along
    # K (kernels unpack on load); drives the autotune warm keys and the
    # prepare_params packing policy — the executor itself re-derives
    # packedness from the weight's contraction-axis length
    packed: bool = False


# pre-v2 name: registry entries used to be mutable-registry-only objects
PlanEntry = ProtectionPlan


class PlanRegistry:
    """(site, shape, M, backend) -> :class:`ProtectionPlan` map."""

    def __init__(self, plan: EntanglePlan, *, blocks: object = None,
                 packed: bool = False):
        self.plan = plan
        self.blocks_policy = blocks
        self.packed = packed
        self._entries: dict[tuple, ProtectionPlan] = {}
        # chainable site groups noted by the census-only traces: tuples of
        # sites that consume the SAME activations and are strictly linear,
        # so one entangle/quantize pass feeds all of them and the chain
        # executor keeps them in the entangled domain
        self._chains: set[tuple] = set()

    @staticmethod
    def key(site: str, shape: tuple, M: int, backend: str) -> tuple:
        return (site, shape, M, backend)

    def shape_for(self, rows: int, K: int, N: int,
                  groups: Optional[int] = None) -> tuple:
        """The kernel-call shape key of a site invocation: ``rows`` is the
        flattened sample count (per expert when ``groups`` is given)."""
        Bg = group_rows(rows, self.plan.M)
        if groups is None:
            return (self.plan.M, Bg, K, N)
        return (self.plan.M, groups, Bg, K, N)

    def entry(self, site: str, rows: int, K: int, N: int,
              backend: str, *, groups: Optional[int] = None) -> ProtectionPlan:
        """Resolve (creating on first use) the plan for one call site."""
        shape = self.shape_for(rows, K, N, groups)
        k = self.key(site, shape, self.plan.M, backend)
        e = self._entries.get(k)
        if e is None:
            blocks = self.blocks_policy
            if blocks is None:
                blocks = default_blocks(*shape[-3:], backend,
                                        plan=self.plan,
                                        grouped=groups is not None)
            e = ProtectionPlan(site=site, shape=shape, backend=backend,
                               plan=self.plan, blocks=blocks,
                               grouped=groups is not None,
                               packed=self.packed)
            self._entries[k] = e
        return e

    def note_chain(self, sites: tuple) -> None:
        """Record one chainable site group (census-only traces call this
        when a fanout/chain executor covers ``sites`` with one codec
        pass)."""
        if len(sites) >= 2:
            self._chains.add(tuple(sites))

    def chains(self) -> frozenset:
        """Chainable site groups noted during the census traces."""
        return frozenset(self._chains)

    def entries(self) -> list[ProtectionPlan]:
        return list(self._entries.values())

    def census(self) -> dict:
        """{(site, shape): blocks} — what warm_autotune iterates; grouped
        sites carry 5-tuple shapes."""
        return {(e.site, e.shape): e.blocks for e in self._entries.values()}

    def get(self, site: str, shape: tuple,
            backend: str) -> Optional[ProtectionPlan]:
        return self._entries.get(self.key(site, shape, self.plan.M, backend))
