"""Pallas TPU kernel: grouped (per-expert) integer GEMM with the fused
entanglement codec — the MoE counterpart of :mod:`entangled_matmul`.

A Mixture-of-Experts layer runs E independent GEMMs per call, one per
expert, each over that expert's capacity-bounded row bucket:

    out[m, e] = c[m, e] @ g[e]        c: [M, E, Cg, K], g: [E, K, N]

Ragged token->expert assignments are padded to the uniform capacity Cg by
the dispatcher (exactly how capacity-bounded MoE already materializes its
expert buffers), so the kernel sees a *uniformly grouped* batch: the grid
simply gains a leading expert axis and every expert's tile reuses the
fused schedule of :mod:`entangled_matmul` verbatim:

  prologue  eps = (roll(c, 1) << l) + c      entangle-on-load, by linearity
  body      acc[m] += eps[m, e] @ g[e]       MXU, int8 limbs, int32 acc
  epilogue  d = disentangle(acc)             at the k == nk-1 flush

Entanglement spans the M stream axis only — each expert's GEMM is linear,
so the codec commutes with it per expert and a fail-stopped stream's
outputs roll forward from the other M-1 accumulators inside the kernel
(``failed=r``), independently and identically for every expert. Zero pad
rows entangle to zeros and cannot perturb any live stream.

The MXU contract and cost model are those of :mod:`entangled_matmul`:
``c`` is split into int8 limbs in the wrapper, and stream m accumulates
``(c[m-1] @ g << l) + c[m] @ g`` limb by limb, computing both products
itself.

Tiling: grid (E, Cg/bb, ceil(N/bn), K/bk), K innermost; the expert axis is
blocked at 1 (each program owns one expert's (bb, bk)x(bk, bn) tile), the
limb and M stream axes are fully resident per block.
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


def _emmg_kernel(
    c_ref, g_ref, out_ref, acc_ref, *,
    plan: EntanglePlan, nk: int, fuse_epilogue: bool, r: int, packed: bool,
):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = c_ref[:, :, 0]  # [n, M, bb, bk] int8 limbs of this expert
    g = g_ref[0]  # [bk, bn] — this program's expert slice
    g = unpack_int8(g, axis=0) if packed else g.astype(jnp.int8)
    acc_ref[...] += jnp.stack(  # static unroll over streams; M is 3..8
        [entangled_limb_dot(c, g, m, plan.l, True)
         for m in range(plan.M)],
        axis=0,
    )

    @pl.when(k == nk - 1)
    def _flush():
        acc = acc_ref[...]
        if fuse_epilogue:
            out_ref[...] = disentangle_block(acc, plan, r)[:, None]
        else:
            out_ref[...] = acc[:, None]


@functools.partial(
    jax.jit,
    static_argnames=("plan", "fuse_epilogue", "failed", "bb", "bn", "bk",
                     "packed", "interpret"),
)
def entangled_matmul_grouped_pallas(
    c: jax.Array,
    g: jax.Array,
    *,
    plan: EntanglePlan,
    fuse_epilogue: bool = False,
    failed: int = 0,
    bb: int = 128,
    bn: int = 128,
    bk: int = 128,
    packed: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Fused grouped entangle[-GEMM-extract]: c [M, E, Cg, K] int8/16/32,
    g [E, K, N] int8 values.

    Returns entangled per-expert products when ``fuse_epilogue=False`` or
    the recovered true products when ``True`` (extraction never reads
    stream ``failed``). With ``packed=True``, ``g`` is [E, K/4, N] packed
    int8 lanes (4 per int32 word along K), sign-extend-unpacked in VMEM
    registers before the MXU dot; ``g`` holds int8 values either way.
    ``c``'s dtype sets the int8 limb count (one per byte). Cg and K must
    be multiples of bb and bk (ops.py pads/unpads); N may end in a partial
    column block, as in :func:`entangled_matmul_pallas`. The expert axis E
    is never padded — the grid walks it.
    """
    M, E, Cg, K = c.shape
    E2, Kg, N = g.shape
    assert E == E2, (E, E2)
    assert K == (Kg * PACK_LANES if packed else Kg), (K, Kg, packed)
    assert M == plan.M, (M, plan.M)
    grid = (E, Cg // bb, pl.cdiv(N, bn), K // bk)
    bkg = bk // PACK_LANES if packed else bk
    limbs = split_int8(c)
    n = limbs.shape[0]
    return pl.pallas_call(
        functools.partial(
            _emmg_kernel, plan=plan, nk=grid[3],
            fuse_epilogue=fuse_epilogue, r=failed % M, packed=packed,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, M, 1, bb, bk),
                         lambda e, b, j, k: (0, 0, e, b, k)),
            pl.BlockSpec((1, bkg, bn), lambda e, b, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((M, 1, bb, bn),
                               lambda e, b, j, k: (0, e, b, j)),
        out_shape=jax.ShapeDtypeStruct((M, E, Cg, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((M, bb, bn), jnp.int32)],
        interpret=interpret,
    )(limbs, g)
