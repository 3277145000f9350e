"""Int8 quantization policy of the protected-GEMM subsystem.

One policy, two halves, shared by EVERY protected projection (the serving
head and the in-model QKV/MLP/router sites alike):

  * **weights** — symmetric per-tensor int8: ``scale = 127 / max|w|``,
    values clipped to [-127, 127] and carried in an int32 container (the
    entangled kernel's stream dtype).  This is exactly the policy the head
    GEMM shipped with (``repro.ft.heads.quantize_head`` re-exports
    :func:`quantize_weight`), applied per layer / per expert by the
    startup hoist via :func:`quantize_weight_stacked`.
  * **activations** — symmetric PER-ROW integer quantization into the
    plan's eq. (13) budget: a ``K``-deep integer dot of int8 weights
    satisfies ``K * |a|max * 127 <= plan.max_output_magnitude`` iff the
    activation grid is bounded by :func:`activation_budget`.  The budget
    therefore shrinks with the contraction depth — a d_ff-deep MLP down
    projection quantizes coarser than the d_model-deep QKV projections,
    and both stay exactly recoverable.  The scale is per ROW (one grid per
    sample), not per tensor: a request's integer stream — and therefore
    its tokens — is a function of its own activations only, never of
    whichever other requests happen to be co-resident in the batch.  This
    is what makes serving-side scheduling (continuous batching, mid-flight
    slot refill, chunked admission) token-transparent: admitting, evicting
    or refilling neighbours cannot move any other request's quantization
    grid, so the entangled roll-forward stays bit-identical no matter WHEN
    a slot was filled.

Quantization trades output precision for protection like any int8 serving
path; the *recovery* is bit-exact — a healthy protected run and a
fail-stop-injected protected run produce identical integers, hence
identical logits and identical tokens (tested).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.plan import EntanglePlan
from repro.kernels.codec import pack_int8


# observability: how often the eq.-13 weight policy actually runs. The v2
# plan-compile flow quantizes every protected site's weights ONCE at engine
# startup (repro.ft.plans.prepare_params), so a traced decode/prefill step
# must never bump this counter — tests assert exactly that (the hoisted-
# quantization contract). Plain dict so tests can reset it in place.
TRACE_STATS = {"weight_quantize_calls": 0}


def quantize_weight(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 weight quantization (int32 container)."""
    TRACE_STATS["weight_quantize_calls"] += 1
    amax = jnp.maximum(jnp.max(jnp.abs(w)), 1e-9)
    scale = 127.0 / amax
    return jnp.clip(jnp.round(w * scale), -127, 127).astype(jnp.int32), scale


@functools.partial(jax.jit, static_argnames=("packed",))
def quantize_weight_stacked(w: jax.Array, *, packed: bool = False) -> dict:
    """Per-matrix int8 quantization of a stacked weight ``[..., K, N]``.

    Every leading axis (layer-repeat, expert) gets its own scale: the
    quantization is vmapped over all but the last two dims, so a scanned
    stack of layers (or a stack of MoE experts) quantizes each matrix on
    its own grid — exactly what the per-call policy produced, now computed
    once at startup. Returns ``{"w": int32 [..., K, N], "scale": [...]}``,
    the ``q8`` pytree entry :func:`repro.ft.plans.prepare_params` installs
    next to the float master.

    ``packed=True`` additionally packs the int8 values 4-per-int32-word
    along the contraction axis (:func:`repro.kernels.codec.pack_int8`), so
    the stored copy is ``[..., ceil(K/4), N]`` — its true int8 bytes in
    HBM. The kernels unpack on load; consumers detect packedness from the
    contraction-axis length (``w.shape[-2] != K``).

    Jitted, so each weight quantizes in one program whose temporaries XLA
    frees within it. Run eagerly op by op, a full-width llama3.2-1b engine
    start-up left 15.6 GB in use on a 16 GB TPU v5e, 6.7 GB after the
    first wave; the suspected cause, unconfirmed, is that the
    asynchronously dispatched per-op intermediates outran their release.
    """
    fn = quantize_weight
    for _ in range(w.ndim - 2):
        fn = jax.vmap(fn)
    wq, scale = fn(w)
    if packed:
        wq = pack_int8(wq, axis=-2)
    return {"w": wq, "scale": scale}


def activation_budget(plan: EntanglePlan, depth: int) -> int:
    """Largest activation magnitude so a ``depth``-deep int8 dot stays
    within the plan's eq. (13) output range (floor 1 — a degenerate budget
    still round-trips, just coarsely)."""
    return max(plan.max_output_magnitude // (depth * 127), 1)


def chain_budget(plan: EntanglePlan, depths: Sequence[int]) -> int:
    """Activation budget for an entangled-domain GEMM *chain*.

    A chain of GEMMs with contraction depths ``K_1 .. K_n`` (each against
    int8 weights) amplifies the first hop's activations by at most
    ``prod(K_i * 127)`` before the single final extraction, so the first
    hop's integer grid must satisfy
    ``budget * prod(K_i * 127) <= plan.max_output_magnitude`` for the whole
    chain to stay within the plan's eq. (13) range at every hop. Returns 0
    when no such grid exists — the chain is infeasible under this plan and
    the executor must fall back to per-GEMM extraction (which it does; see
    :func:`repro.ft.protected.entangled_chain`).
    """
    amp = 1
    for K in depths:
        amp *= int(K) * 127
    return plan.max_output_magnitude // amp


def acts_dtype(plan: EntanglePlan, depth: int, budget: int = None):
    """Narrowest integer dtype holding the activation grid of a
    ``depth``-deep contraction. The entangled GEMM kernels split their
    activations into one int8 MXU limb per byte of this dtype, so a grid
    within [-127, 127] (every published width under ``make_plan(4)``)
    runs one limb."""
    if budget is None:
        budget = activation_budget(plan, depth)
    if budget <= 127:
        return jnp.int8
    return jnp.int16 if budget <= 32767 else jnp.int32


def quantize_acts(x: jax.Array, plan: EntanglePlan, depth: int, *,
                  budget: int = None) -> tuple[jax.Array, jax.Array]:
    """Quantize float activations ``x`` onto the eq. (13)-budgeted integer
    grid for a ``depth``-deep contraction. Returns (integer values in the
    grid's :func:`acts_dtype`, scale),
    where the scale is PER ROW — shaped like ``x`` with the contraction
    axis reduced to 1, so it broadcasts against the row's outputs.

    Per-row scales keep every sample's integer stream a function of its
    own values: batch composition (which slots are live, what garbage an
    inactive row holds, when admission refilled a slot) can never move
    another row's grid. Each row's entries are bounded by ``budget``, so
    the eq. (13) output bound holds row-wise exactly as it did for the
    old shared per-tensor grid. ``budget`` overrides the single-GEMM
    budget (the chain executor passes :func:`chain_budget`'s tighter
    grid)."""
    if budget is None:
        budget = activation_budget(plan, depth)
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-9)
    a_scale = budget / amax
    dtype = acts_dtype(plan, depth, budget)
    return jnp.round(x * a_scale).astype(dtype), a_scale
