"""Pallas TPU kernel: integer GEMM with the FULL entanglement codec fused.

The paper's throughput claim (1.8-2.8% overhead, Fig. 2) rests on the codec
never being a separate memory sweep: entanglement is applied "as data within
each input stream is being read" and extraction as results are written. This
kernel honors both halves in one ``pallas_call``:

  prologue  eps = (roll(c, 1) << l) + c      entangle-on-load, by linearity
  body      acc[m] += eps[m] @ g             MXU, int8 limbs, int32 acc
  epilogue  d = disentangle(acc)             Horner telescoping + bit-field
            (at the k == nk-1 flush)         split, incl. the dualword path

MXU contract and cost model
---------------------------
The TPU MXU multiplies int8 x int8 -> int32 (or bf16); an int32 x int32
dot does not lower. The entangled operand ``eps[m]`` needs up to ``w``
bits, so the kernel never forms it. Instead the wrapper splits ``c`` into
``n`` balanced int8 limbs (:func:`repro.kernels.codec.split_int8`), one per
byte of ``c``'s dtype (an int32 ``c`` takes 4 and stays exact for any
value), and stream m accumulates

  eps[m] @ g = sum_i (c_i[m-1] @ g << (l + 8i)) + (c_i[m] @ g << 8i)

in int32 — bit-identical, mod 2^32, to the int32 GEMM it replaces. Terms
shifted past bit 31 vanish and are skipped. Each stream computes both its
own products: sharing ``c[j] @ g`` between streams j and j+1 would let one
fail-stop corrupt two entangled outputs.

Each grid step pushes its weight tile through the MXU ONCE. The kernel
stacks, in VMEM, every limb of every stream's own rows and — when it
entangles — every limb of each stream's own copy of its predecessor's
rows into one int8 LHS of ``(2n - s) * M * bb`` rows (``s`` = skipped
terms; ``n * M * bb`` in the chain modes), runs one int8 x int8 -> int32
dot against the tile, and rebuilds each stream's accumulator from its
own row slices with the shifts above. Rows still belong to one stream
each, so one fail-stop corrupts one accumulator. A v5e MXU latches a
128 x 128 weight sub-tile and then streams LHS rows through it: pushed
once per stacked dot, the tile costs one latch per sub-tile plus the
stacked rows, where a dot per stream and term latched it ``2M`` times.
The MXU work is still ``M * (2n - s)`` int8 row passes against ``M * n``
for the same GEMM unprotected on the same limbs. On the serving path the
activations sit on the eq.-13 grid, ``|c| <= 127`` whenever ``K >= 516``
under ``make_plan(4)`` (every published width), and arrive as int8, so
``n = 1``: protection doubles the rows the MXU streams. The paper's
"overhead independent of the op" held on a CPU whose 32-bit integer SIMD
absorbs the shifted operand for free; on an int8 MXU the extra rows are
hidden only where the GEMM is bound by the weight read, not by the MXU.
The ``'chain'`` modes feed already-entangled int32 accumulators: 4 limbs,
no entangle term, so ``4M`` row passes.

Bytes: activations move ``n`` bytes per element (int8 limbs), weights 1
byte packed (``packed=True``) or 4 in the legacy int32 container, outputs
4. Entangle -> GEMM -> extract costs no intermediate HBM round trip.

Tiling: grid (B/bb, ceil(N/bn), K/bk), K innermost, with the small M
stream axis and the limb axis FULLY resident per tile — block (n, M, bb,
bk). With all M streams in one block the predecessor stream is a static
index, the stack is built from the one activation block in VMEM (never a
doubled operand in HBM), and the epilogue has every stream's accumulator
in VMEM to disentangle against. int8 blocks tile as (32, 128) on the
TPU, so the compiled backend needs bb % 32 == 0 and bk % 128 == 0 (and
bn % 128). Every grid step also costs a fixed ~0.24 us on a v5e, so the
compiled backend's calls take weight tiles of 1-2 MiB of int8 — a decode
step's few rows per stream group most of all — with ``bk`` a divisor of
K, so the wrapper never pads (copies) the weights, and ``bn`` shrunk
where the stacked rows of a packed prefill would overflow VMEM (the
policy is :func:`repro.ft.registry.default_blocks`). :func:`vmem_bytes`
reckons a step's working set; the kernel raises its scoped-VMEM limit to
:data:`VMEM_LIMIT_BYTES` and the policy keeps every call within
:data:`VMEM_BUDGET_BYTES`. The last column block may be
partial, so the weights are never padded along N: a vocabulary head split
over devices (128256 / 4 = 32064 columns each) would otherwise be copied
on every call.

``fuse_epilogue`` is a four-state switch selecting which codec halves run:

  ==============  =================  ===================
  fuse_epilogue   entangle prologue  extract at flush
  ==============  =================  ===================
  ``True``        yes                yes  (standalone fused GEMM)
  ``False``       yes                no   (raw entangled accumulators out)
  ``'chain'``     no                 no   (input ALREADY entangled)
  ``'chain_final'`` no               yes  (chain tail: extract only)
  ==============  =================  ===================

The chain modes exploit linearity of the codec over streams:
``(E c) @ g = E (c @ g)``, so feeding one call's entangled accumulators
straight into the next call's plain per-stream GEMM (no re-entangle, no
extract between) keeps the whole chain in the entangled domain — one
entangle, N GEMMs, one extract, and a fail-stopped stream's garbage stays
confined to its own stream until the final extraction statically skips it
(``failed=r``, same shifts/adds as the clean path).

``g`` holds int8 values. ``packed=True`` reads it with 4 int8 lanes per
int32 word (packed along K by :func:`repro.kernels.codec.pack_int8`): the
weight block shrinks to (bk/4, bn) in HBM/VMEM, and since byte j of word
w is row 4w + j — the order in which the TPU packs int8 rows into a
32-bit sublane — it becomes the (bk, bn) int8 MXU operand by a bitcast —
the q8 copies cost their true bytes end to end. Unpacked, the
int32-container block is narrowed to int8 in registers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.plan import EntanglePlan
from repro.kernels.codec import PACK_LANES, disentangle_block, split_int8

# fuse_epilogue values whose prologue entangles / whose flush extracts
ENTANGLE_MODES = (False, True)
EXTRACT_MODES = (True, "chain_final")
CHAIN_MODES = ("chain", "chain_final")


def stack_shifts(n: int, l: int, entangle: bool) -> tuple:
    """Accumulator shift of each row block of the kernel's stacked LHS.

    The stack holds, in order, limb i of every stream's own rows (shift
    ``8i``) for i < n, then — when ``entangle`` — limb i of every
    stream's copy of its predecessor's rows (shift ``l + 8i``) for the
    limbs whose term stays below bit 32 (terms shifted past bit 31 vanish
    mod 2^32 and are never stacked)."""
    shifts = [8 * i for i in range(n)]
    if entangle:
        shifts += [l + 8 * i for i in range(n) if l + 8 * i < 32]
    return tuple(shifts)


def vmem_bytes(bb: int, bn: int, bk: int, *, n: int = 1, M: int = 4,
               l: int = 8, entangle: bool = True,
               packed: bool = True) -> int:
    """Reckoned VMEM working set of one grid step: the double-buffered
    activation, weight and output blocks, the accumulator, the int8
    weight tile, the stacked LHS and the dot's int32 result."""
    rows = len(stack_shifts(n, l, entangle)) * M * bb
    act = 2 * n * M * bb * bk
    weight = 2 * bk * bn * (1 if packed else 4) + bk * bn
    out = 3 * M * bb * bn * 4  # output block x2 + accumulator
    return act + weight + out + rows * bk + rows * bn * 4


# the kernel's scoped-VMEM limit (v5e holds 128 MiB), and the working set
# the block policy (``repro.ft.registry.default_blocks``) keeps within it
VMEM_LIMIT_BYTES = 64 << 20
VMEM_BUDGET_BYTES = 40 << 20


def _emm_kernel(
    c_ref, g_ref, out_ref, acc_ref, *,
    plan: EntanglePlan, nk: int, fuse_epilogue, r: int, packed: bool,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = c_ref[...]  # [n, M, bb, bk] int8 limbs
    n, M, bb, bk = c.shape
    g = g_ref[...]
    # [bk/4, bn] words -> [bk, bn] int8: byte j of word w is row 4w + j,
    # which is how the TPU lays int8 rows into 32-bit sublanes, so the
    # unpack is a bitcast; or the int32 container narrowed to int8
    g = pltpu.bitcast(g, jnp.int8) if packed else g.astype(jnp.int8)
    shifts = stack_shifts(n, plan.l, fuse_epilogue in ENTANGLE_MODES)
    if len(shifts) > n:
        # stream m's own copy of stream m-1's limbs (a static roll over
        # the stream axis): each stream computes both its products from
        # its own rows, so one fail-stop corrupts one accumulator only
        prev = c[: len(shifts) - n]
        c = jnp.concatenate(
            [c, jnp.concatenate([prev[:, M - 1:], prev[:, :M - 1]], axis=1)],
            axis=0)
    # ONE dot per grid step: the weight tile enters the MXU once for every
    # limb, stream and predecessor term
    p = jnp.dot(c.reshape(len(shifts) * M * bb, bk), g,
                preferred_element_type=jnp.int32)
    p = p.reshape(len(shifts), M, bb, p.shape[-1])
    acc = acc_ref[...]
    for j, s in enumerate(shifts):
        acc = acc + (jnp.left_shift(p[j], s) if s else p[j])
    acc_ref[...] = acc

    @pl.when(k == nk - 1)
    def _flush():
        acc = acc_ref[...]
        if fuse_epilogue in EXTRACT_MODES:
            out_ref[...] = disentangle_block(acc, plan, r)
        else:
            out_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=("plan", "fuse_epilogue", "failed", "bb", "bn", "bk",
                     "packed", "interpret"),
)
def entangled_matmul_pallas(
    c: jax.Array,
    g: jax.Array,
    *,
    plan: EntanglePlan,
    fuse_epilogue=False,
    failed: int = 0,
    bb: int = 128,
    bn: int = 128,
    bk: int = 128,
    packed: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Fused entangle[-GEMM-extract] for c:[M, B, K] int8/int16/int32,
    g:[K, N] int8 values (int32 container or packed).

    Returns entangled products delta[m] = (E c)[m] @ g when
    ``fuse_epilogue=False``, or the recovered true products d[m] = c[m] @ g
    when ``fuse_epilogue=True`` (extraction never reads stream ``failed``).
    ``'chain'`` / ``'chain_final'`` skip the entangle prologue (c must
    already be entangled) and keep / extract the entangled accumulators —
    see module docstring. With ``packed=True``, ``g`` is [K/4, N] packed
    int8 lanes. ``c``'s dtype sets the int8 limb count (one per byte).
    B and K must be multiples of bb and bk (ops.py pads). N need not be
    a multiple of bn: output columns are independent, so the last column
    block reads unspecified weight lanes past N and its writes past N are
    dropped.
    """
    M, B, K = c.shape
    Kg, N = g.shape
    assert K == (Kg * PACK_LANES if packed else Kg), (K, Kg, packed)
    assert M == plan.M, (M, plan.M)
    grid = (B // bb, pl.cdiv(N, bn), K // bk)
    bkg = bk // PACK_LANES if packed else bk
    limbs = split_int8(c)
    n = limbs.shape[0]
    return pl.pallas_call(
        functools.partial(
            _emm_kernel, plan=plan, nk=grid[2],
            fuse_epilogue=fuse_epilogue, r=failed % M, packed=packed,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, M, bb, bk), lambda b, j, k: (0, 0, b, k)),
            pl.BlockSpec((bkg, bn), lambda b, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((M, bb, bn), lambda b, j, k: (0, b, j)),
        out_shape=jax.ShapeDtypeStruct((M, B, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((M, bb, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(limbs, g)
