"""Pallas TPU kernel: entangled depthwise causal conv1d, codec fully fused.

Convolution is the paper's experimental LSB op (Fig. 2): depthwise conv is
sesquilinear per stream, so ``conv(E c) = E conv(c)``. This kernel carries
that identity into the schedule — the M entangled streams share one weight
read and one fused pass:

  prologue  eps = (roll(x, 1) << l) + x      entangle-on-load (current tile
                                             AND its halo), in registers
  body      acc[m] = sum_j w[:, j] * win[m]  VPU taps, static unroll
  epilogue  d = disentangle(acc)             optional extract-at-flush

The M stream axis is fully resident per block (M is 3..8), so the cyclic
predecessor is a register roll — the operand is bound once per tile role.

Depthwise conv has no contraction, so it runs on the VPU (int32 lane
multiplies), not the MXU: the int8 MXU contract of the GEMM kernels does
not apply, and packed weights are unpacked straight to int8 lanes that
widen in the multiply.

Causality halo: each output tile of length ``bt`` needs ``K_f - 1``
trailing inputs of the previous tile. Pallas blocks are uniform, so the
input is bound a second time at index ``max(t-1, 0)`` for the halo; the
taps rotate lanes instead of slicing them
(:func:`repro.kernels.conv1d.causal_taps`). The second binding
fetches a full extra tile per grid step (~2x input traffic) to use only
its trailing K_f - 1 columns. Accepted: conv input bytes are a small share
of a step's total traffic; carrying the previous tile's tail across grid
steps in VMEM scratch is the follow-up if a profile ever flags it (see
conv1d.py for the same trade-off on the unentangled kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.plan import EntanglePlan
from repro.kernels.codec import (PACK_LANES, disentangle_block,
                                 entangle_block, unpack_int8)
from repro.kernels.conv1d import causal_taps


def _econv_kernel(
    x_cur_ref, x_prev_ref, w_ref, out_ref, *,
    plan: EntanglePlan, fuse_epilogue: bool, r: int, packed: bool,
):
    t = pl.program_id(2)
    l = plan.l

    eps_cur = entangle_block(x_cur_ref[:, 0], l)  # [M, bd, bt]
    eps_prev = entangle_block(x_prev_ref[:, 0], l)
    eps_prev = jnp.where(t == 0, jnp.zeros_like(eps_prev), eps_prev)

    w = w_ref[...]
    if packed:  # [bd/4, kf] words -> [bd, kf] sign-extended lanes
        w = unpack_int8(w, axis=0)
    acc = causal_taps(eps_cur, eps_prev, w.astype(jnp.int32))

    if fuse_epilogue:
        acc = disentangle_block(acc, plan, r)
    out_ref[:, 0] = acc


@functools.partial(
    jax.jit,
    static_argnames=("plan", "fuse_epilogue", "failed", "bd", "bt",
                     "packed", "interpret"),
)
def entangled_conv1d_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    plan: EntanglePlan,
    fuse_epilogue: bool = False,
    failed: int = 0,
    bd: int = 128,
    bt: int = 512,
    packed: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Entangled depthwise causal conv: x [M, B, D, T] int32, w [D, K_f].

    Returns entangled conv outputs delta[m] = conv(E x)[m] when
    ``fuse_epilogue=False``, or the recovered true outputs
    d[m, b, d, t] = sum_j w[d, j] * x[m, b, d, t-K_f+1+j] when
    ``fuse_epilogue=True`` (extraction never reads stream ``failed``).
    With ``packed=True``, ``w`` is [D/4, K_f] packed int8 lanes (4 per
    int32 word along D), sign-extend-unpacked in registers per tile.
    D % bd == 0, T % bt == 0, 2 <= K_f <= bt (ops.py pads/unpads).
    """
    M, B, D, T = x.shape
    Dg, kf = w.shape
    assert D == (Dg * PACK_LANES if packed else Dg), (D, Dg, packed)
    assert 2 <= kf <= bt, (kf, bt)
    assert M == plan.M, (M, plan.M)
    grid = (B, D // bd, T // bt)
    bdg = bd // PACK_LANES if packed else bd
    return pl.pallas_call(
        functools.partial(
            _econv_kernel, plan=plan,
            fuse_epilogue=fuse_epilogue, r=failed % M, packed=packed,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((M, 1, bd, bt), lambda b, d, t: (0, b, d, t)),
            # predecessor tile (halo); same block index at t=0, masked above
            pl.BlockSpec(
                (M, 1, bd, bt),
                lambda b, d, t: (0, b, d, jnp.maximum(t - 1, 0)),
            ),
            pl.BlockSpec((bdg, kf), lambda b, d, t: (d, 0)),
        ],
        out_specs=pl.BlockSpec((M, 1, bd, bt), lambda b, d, t: (0, b, d, t)),
        out_shape=jax.ShapeDtypeStruct((M, B, D, T), jnp.int32),
        interpret=interpret,
    )(x, x, w)
