"""Pallas TPU kernel: depthwise causal integer conv1d.

Convolution is the paper's experimental LSB op (Fig. 2) and also the conv
frontend of the assigned SSM/hybrid/audio architectures (Mamba conv1d,
Whisper/RecurrentGemma frontends use K_f in {3, 4}). This kernel covers the
short-filter depthwise case used inside models; long-kernel stream
convolution (paper Fig. 2, K up to 4500) goes through XLA's conv in
``benchmarks/`` where im2col/FFT strategies win.

Causality halo: each output tile of length ``bt`` needs ``K_f - 1`` trailing
inputs of the previous tile. Pallas blocks are uniform, so the input is bound
twice — current tile and predecessor tile — and the first tile's halo is
masked to zero (causal left padding). The taps never slice the time (lane)
axis at an unaligned offset, which the TPU cannot lower: tap ``s`` rotates
both tiles by ``s`` lanes and selects the predecessor's wrapped tail for
the first ``s`` lanes (:func:`causal_taps`). DMA cost of the second binding: each
grid step fetches a full extra (bd, bt) predecessor tile even though only
its trailing K_f - 1 columns are read, i.e. ~2x input traffic — only the
K_f-1 columns are *useful* (<1% at bt=512, K_f<=4), the rest is the price
of uniform blocks. Accepted for now because conv input bytes are a small
share of a model step's total traffic; the fix if it ever shows up on a
profile is carrying the previous tile's tail across grid steps in a VMEM
scratch instead of re-binding. (The GEMM kernel's former self/predecessor
double-binding is gone entirely: entangled_matmul.py now holds all M
streams in one block and rolls in registers.)

Works on entangled streams unchanged: depthwise conv is sesquilinear in the
stream, so ``conv(E c) = E conv(c)`` per the paper's Sec. III argument.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def causal_taps(cur: jax.Array, prev: jax.Array, w: jax.Array) -> jax.Array:
    """One output tile of the depthwise causal conv, by lane rotations.

    ``cur``/``prev`` are ``[..., bd, bt]`` int32 tiles (``prev`` the
    predecessor tile, zero for the first); ``w`` is ``[bd, K_f]``. Returns
    ``out[..., d, t] = sum_j w[d, j] * x[..., d, t - K_f + 1 + j]`` where
    ``x`` continues into ``prev`` for negative offsets. Tap ``j`` reads
    ``x`` shifted right by ``s = K_f - 1 - j`` lanes: rotate both tiles by
    ``s`` and take the predecessor's wrapped tail where ``t < s``.
    """
    kf = w.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, cur.shape, cur.ndim - 1)
    acc = jnp.zeros(cur.shape, jnp.int32)
    for j in range(kf):  # static unroll over taps
        s = kf - 1 - j
        if s == 0:
            shifted = cur
        else:
            ax = cur.ndim - 1
            shifted = jnp.where(lane >= s, pltpu.roll(cur, s, ax),
                                pltpu.roll(prev, s, ax))
        acc += w[:, j:j + 1] * shifted
    return acc


def _conv1d_kernel(x_cur_ref, x_prev_ref, w_ref, out_ref):
    t = pl.program_id(2)
    prev = x_prev_ref[0]
    prev = jnp.where(t == 0, jnp.zeros_like(prev), prev)  # causal zero pad
    out_ref[0, ...] = causal_taps(x_cur_ref[0], prev, w_ref[...])


@functools.partial(
    jax.jit, static_argnames=("bd", "bt", "interpret")
)
def conv1d_causal_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    bd: int = 128,
    bt: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Depthwise causal conv: x [B, D, T] int32, w [D, K_f] int32 ->
    out[b,d,t] = sum_j w[d,j] * x[b,d,t-K_f+1+j]. D % bd == 0, T % bt == 0,
    2 <= K_f <= bt (ops.py pads/unpads; K_f=1 is promoted there with a
    zero leading tap, so every kernel call has a halo)."""
    B, D, T = x.shape
    D2, kf = w.shape
    assert D == D2 and 2 <= kf <= bt
    grid = (B, D // bd, T // bt)
    return pl.pallas_call(
        _conv1d_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bd, bt), lambda b, d, t: (b, d, t)),
            # predecessor tile (halo); clamped at t=0 and masked in-kernel
            pl.BlockSpec(
                (1, bd, bt), lambda b, d, t: (b, d, jnp.maximum(t - 1, 0))
            ),
            pl.BlockSpec((bd, kf), lambda b, d, t: (d, 0)),
        ],
        out_specs=pl.BlockSpec((1, bd, bt), lambda b, d, t: (b, d, t)),
        out_shape=jax.ShapeDtypeStruct((B, D, T), jnp.int32),
        interpret=interpret,
    )(x, x, w)
