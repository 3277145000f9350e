"""Register-level codec math shared by every fused Pallas kernel.

One implementation of the paper's codec, written over *register values*
(jnp arrays already loaded from VMEM refs) so the same code runs

  * inside the standalone entangle/disentangle kernels,
  * as the load-prologue / flush-epilogue of the fused GEMM and conv1d
    kernels (entangle-on-load, extract-at-flush),
  * in the jnp oracles.

``entangle_block`` is eq. (14/15): one shift-add per element against the
cyclic predecessor row. ``disentangle_rows`` is eq. (16-19): the Horner
telescoping sum (int32 single-word or dual-word per paper Remark 1), the
sign-extended bit-field split of d_r / d_q, and the eq. (19) recovery
chain. All ops are shifts/adds on VPU integer lanes — no multiplies, no
HBM traffic.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import wideint
from repro.core.plan import EntanglePlan


def entangle_block(c: jax.Array, l: int) -> jax.Array:
    """eps_m = (c_{(m-1) mod M} << l) + c_m over leading axis of ``c``."""
    return jnp.left_shift(jnp.roll(c, 1, axis=0), l) + c


# ---------------------------------------------------------------------------
# int8 lane packing — 4 int8 values per int32 word
#
# The startup-quantized q8 weight copies are int8-valued but ride the
# kernels' int32 container, costing 4x their true bytes in HBM plus a
# 4x-wide sweep per protected GEMM. Packing stores 4 consecutive values
# along the contraction axis in one int32 word (lane j in bits
# [8j, 8j+8)); the fused kernels unpack on load in VMEM registers with
# two shifts per lane — arithmetic right-shift sign-extends, so the
# roundtrip is bit-exact over the full int8 range.
# ---------------------------------------------------------------------------

PACK_LANES = 4  # int8 lanes per int32 word


def pack_int8(x: jax.Array, axis: int = -2) -> jax.Array:
    """Pack int8-valued int32 ``x`` 4-to-1 along ``axis``.

    ``axis`` is zero-padded to a multiple of :data:`PACK_LANES` (zero packs
    and unpacks exactly, so padding never perturbs a GEMM). Values must be
    in [-128, 127]; out-of-range values are truncated mod 256.
    """
    axis = axis % x.ndim
    n = x.shape[axis]
    pad = (-n) % PACK_LANES
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    lanes = jnp.moveaxis(x, axis, -1).reshape(
        *[s for a, s in enumerate(x.shape) if a != axis],
        (n + pad) // PACK_LANES, PACK_LANES)
    word = jnp.zeros(lanes.shape[:-1], jnp.int32)
    for j in range(PACK_LANES):
        word = word + jnp.left_shift(
            jnp.bitwise_and(lanes[..., j].astype(jnp.int32), 0xFF), 8 * j)
    return jnp.moveaxis(word, -1, axis)


def unpack_int8(p: jax.Array, axis: int = -2, n: Optional[int] = None
                ) -> jax.Array:
    """Inverse of :func:`pack_int8`: expand ``axis`` 1-to-4 into int8 —
    the MXU's operand type, so the fused kernels feed the result straight
    to an int8 x int8 -> int32 dot.

    ``n`` truncates the unpacked axis back to its original length (the
    pack may have zero-padded it to a multiple of :data:`PACK_LANES`).
    """
    axis = axis % p.ndim
    lanes = [jnp.right_shift(jnp.left_shift(p, 24 - 8 * j), 24)
             for j in range(PACK_LANES)]
    out = jnp.stack(lanes, axis=axis + 1).astype(jnp.int8)
    shape = list(p.shape)
    shape[axis] = p.shape[axis] * PACK_LANES
    out = out.reshape(shape)
    if n is not None and n != out.shape[axis]:
        out = jax.lax.slice_in_dim(out, 0, n, axis=axis)
    return out


# ---------------------------------------------------------------------------
# int8 limbs — the MXU operand contract
#
# The TPU MXU multiplies int8 (or bf16) operands only; an int32 x int32 dot
# does not lower. A GEMM operand wider than int8 is therefore split into
# int8 limbs, x = sum_i limb_i << 8i, one limb per byte of its integer
# type, and each limb gets its own int8 dot, recombined by shifts in the
# int32 accumulator. Limbs are balanced ([-128, 127]): one holds an int8,
# two an int16 up to 32639 (the top 128 int16 values would need a top limb
# of 128, which wraps to -128 and leaves them off by 2^16), and four hold
# ANY int32 exactly mod 2^32 (the top limb wraps, and 2^24 * 256 == 2^32
# vanishes) — the int32 accumulator's own arithmetic, so a limb-split GEMM
# is bit-identical to the int32 GEMM it replaces. The operand's dtype is
# its bound: callers that know their values fit int8 (the eq.-13
# activation grid) pass int8 and pay one limb.
# ---------------------------------------------------------------------------


def split_int8(x: jax.Array) -> jax.Array:
    """Balanced int8 limbs of integer ``x``, one per byte of its dtype:
    ``[n, *x.shape]`` int8 with ``x == sum_i limbs[i] << 8i`` (mod 2^32)."""
    n = min(jnp.dtype(x.dtype).itemsize, 4)
    x = x.astype(jnp.int32)
    limbs = []
    for _ in range(n - 1):
        lo = jnp.bitwise_and(x + 128, 255) - 128
        limbs.append(lo.astype(jnp.int8))
        x = jnp.right_shift(x - lo, 8)  # exact: x - lo is a multiple of 256
    limbs.append(x.astype(jnp.int8))
    return jnp.stack(limbs)


def limb_dot(c_limbs, g: jax.Array, shift: int = 0) -> jax.Array:
    """``(sum_i c_limbs[i] << 8i) @ g << shift`` in int32, one int8 MXU
    dot per limb. Terms shifted past bit 31 vanish mod 2^32 and are
    skipped statically."""
    acc = None
    for i, limb in enumerate(c_limbs):
        s = shift + 8 * i
        if s >= 32:
            break
        p = jnp.left_shift(
            jnp.dot(limb, g, preferred_element_type=jnp.int32), s)
        acc = p if acc is None else acc + p
    return acc


def entangled_limb_dot(c_limbs, g: jax.Array, m: int, l: int,
                       entangle: bool) -> jax.Array:
    """Stream ``m``'s entangled product ``eps[m] @ g`` from int8 limbs
    ``c_limbs`` ([n, M, ...]), where ``eps[m] = (c[m-1] << l) + c[m]``
    (eq. 14/15) when ``entangle`` and ``c[m]`` otherwise. Linearity gives
    ``(c[m-1] @ g << l) + c[m] @ g``. Stream m computes BOTH products
    itself — sharing ``c[j] @ g`` between streams j and j+1 would let one
    fail-stop corrupt two entangled outputs and void the single-failure
    guarantee."""
    M = c_limbs.shape[1]
    acc = limb_dot([c_limbs[i, m] for i in range(c_limbs.shape[0])], g)
    if entangle:
        prev = (m - 1) % M
        acc = acc + limb_dot(
            [c_limbs[i, prev] for i in range(c_limbs.shape[0])], g, l)
    return acc


def disentangle_rows(
    delta_rows: Sequence[jax.Array],
    plan: EntanglePlan,
    r: int = 0,
) -> list[jax.Array]:
    """Recover all M outputs from the M entangled rows, never reading row r.

    ``delta_rows[m]`` is the entangled output of stream m (any common
    shape). The failed/excluded index ``r`` is static. Returns the M
    recovered outputs in original stream order.
    """
    M, l = plan.M, plan.l
    assert len(delta_rows) == M, (len(delta_rows), M)
    r = r % M
    B = (M - 1) * l
    sign = -1 if (M % 2) else 1  # (-1)^M
    q = (r + M - 1) % M

    deltas = [delta_rows[(r + 1 + m) % M] for m in range(M - 1)]

    if plan.temp == "dualword":
        t = wideint.widen(deltas[0])
        for j, d in enumerate(deltas[1:], start=2):
            t = wideint.shl(t, l)
            t = (
                wideint.sub(t, wideint.widen(d))
                if (j % 2 == 0)
                else wideint.add(t, wideint.widen(d))
            )
        t_lo = wideint.extract_low_signed(t, B)
        d_q = (sign * t_lo).astype(jnp.int32)
        d_r = wideint.shr_exact_to_i32(wideint.sub(t, wideint.widen(t_lo)), B)
    else:  # single int32 word (valid when plan.temp_bits <= 32)
        t = deltas[0]
        for j, d in enumerate(deltas[1:], start=2):
            t = jnp.left_shift(t, l)
            t = (t - d) if (j % 2 == 0) else (t + d)
        shift = 32 - B
        t_lo = jnp.right_shift(jnp.left_shift(t, shift), shift)
        d_q = (sign * t_lo).astype(jnp.int32)
        d_r = jnp.right_shift(t - t_lo, B)

    out: list[Optional[jax.Array]] = [None] * M
    out[r], out[q] = d_r, d_q
    for m in range(1, M - 1):  # eq. (19) chain
        idx = (r + m) % M
        out[idx] = delta_rows[idx] - jnp.left_shift(out[(r + m - 1) % M], l)
    return out  # type: ignore[return-value]


def disentangle_block(
    delta: jax.Array, plan: EntanglePlan, r: int = 0
) -> jax.Array:
    """:func:`disentangle_rows` over the leading axis of a stacked block."""
    rows = [delta[m] for m in range(plan.M)]
    return jnp.stack(disentangle_rows(rows, plan, r), axis=0)
