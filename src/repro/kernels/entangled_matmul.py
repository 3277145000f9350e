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

Per (bb, bk) x (bk, bn) tile the MXU thus runs ``M * (2n - s)`` int8
passes (``s`` = skipped terms), against ``M * n`` for the same GEMM
unprotected on the same limbs. On the serving path the activations sit on
the eq.-13 grid, ``|c| <= 127`` whenever ``K >= 516`` under
``make_plan(4)`` (every published width), and arrive as int8, so
``n = 1``: protection costs exactly 2x the plain int8 MXU passes. The paper's
"overhead independent of the op" held on a CPU whose 32-bit integer SIMD
absorbs the shifted operand for free; on an int8 MXU it does not — the
price is one extra MXU pass per stream, hidden only where the GEMM is
bound by the weight read (small-batch decode), not by the MXU (prefill).
The ``'chain'`` modes feed already-entangled int32 accumulators: 4 limbs,
no entangle term, so ``4M`` passes.

Bytes: activations move ``n`` bytes per element (int8 limbs), weights 1
byte packed (``packed=True``) or 4 in the legacy int32 container, outputs
4. Entangle -> GEMM -> extract costs no intermediate HBM round trip.

Tiling: grid (B/bb, ceil(N/bn), K/bk), K innermost, with the small M
stream axis and the limb axis FULLY resident per tile — block (n, M, bb,
bk). With all M streams in one block the predecessor stream is a static
index, the operand is bound once, and the epilogue has every stream's
accumulator in VMEM to disentangle against. int8 blocks tile as (32, 128)
on the TPU, so the compiled backend needs bb % 32 == 0 and bk % 128 == 0
(and bn % 128). The last column block may be partial, so the weights are
never padded along N: a vocabulary head split over devices
(128256 / 4 = 32064 columns each) would otherwise be copied on every call.

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
weight block shrinks to (bk/4, bn) in HBM/VMEM and is sign-extend-unpacked
to int8 in registers before the MXU dot — the q8 copies cost their true
bytes end to end. Unpacked, the int32-container block is narrowed to int8
in registers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.plan import EntanglePlan
from repro.kernels.codec import (PACK_LANES, disentangle_block,
                                 entangled_limb_dot, split_int8, unpack_int8)

# fuse_epilogue values whose prologue entangles / whose flush extracts
ENTANGLE_MODES = (False, True)
EXTRACT_MODES = (True, "chain_final")
CHAIN_MODES = ("chain", "chain_final")


def _emm_kernel(
    c_ref, g_ref, out_ref, acc_ref, *,
    plan: EntanglePlan, nk: int, fuse_epilogue, r: int, packed: bool,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = c_ref[...]  # [n, M, bb, bk] int8 limbs
    g = g_ref[...]
    # [bk/4, bn] words -> [bk, bn] sign-extended int8 lanes, or the int32
    # container narrowed to its int8 values
    g = unpack_int8(g, axis=0) if packed else g.astype(jnp.int8)
    entangle = fuse_epilogue in ENTANGLE_MODES
    acc_ref[...] += jnp.stack(  # static unroll over streams; M is 3..8
        [entangled_limb_dot(c, g, m, plan.l, entangle)
         for m in range(plan.M)],
        axis=0,
    )

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
        interpret=interpret,
    )(limbs, g)
