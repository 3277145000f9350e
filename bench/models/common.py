"""Plain float32 building blocks shared by the reference models.

Nothing here imports the program. ``linear`` computes a projection in the
precision a reference mode asks for:

* ``None``: float32 at the highest matmul precision;
* ``"bf16"``: bfloat16 operands, float32 accumulation;
* ``(w_levels, a_levels)``: the integer GEMM that the configuration states
  for a protected site -- weights on a symmetric per-matrix grid of
  ``w_levels`` steps each side (scale ``w_levels / max|w|``), activations on
  a symmetric per-row grid of ``a_levels`` steps each side, products
  accumulated exactly in int32, then scaled back.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def act_budget(ent: dict, K: int) -> int:
    """Per-row activation grid of a ``K``-deep protected GEMM: the largest
    magnitude whose products with int8 weights stay within the
    entanglement's output range (paper eq. 13)."""
    return max(ent["max_output"] // (K * 127), 1)


def linear(x, w, levels=None):
    if levels is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    if levels == "bf16":
        return bf16_matmul(x, w)
    w_levels, a_levels = levels
    w_scale = w_levels / jnp.maximum(jnp.max(jnp.abs(w)), 1e-9)
    wq = jnp.clip(jnp.round(w * w_scale), -w_levels, w_levels)
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-9)
    a_scale = a_levels / amax
    xq = jnp.round(x * a_scale)
    dt = jnp.int8 if max(w_levels, a_levels) <= 127 else jnp.int32
    acc = jnp.matmul(xq.astype(dt), wq.astype(dt),
                     preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) / (a_scale * w_scale)


def site_levels(mode: str, ent: dict, K: int, protected: bool):
    """Precision of one projection under a reference mode.

    ``ft``: protected sites as the configuration states them (int8 weights,
    the eq. 13 activation grid), the rest bfloat16. ``ft_control``: one step
    lower everywhere -- protected sites on int4 grids (7 steps), the rest
    int8. ``float``: float32 everywhere. ``float_control``: int8 grids
    everywhere (127 steps)."""
    if mode == "ft":
        return (127, act_budget(ent, K)) if protected else "bf16"
    if mode == "ft_control":
        return (7, min(act_budget(ent, K), 7)) if protected else (127, 127)
    if mode == "float":
        return None
    if mode == "float_control":
        return (127, 127)
    raise ValueError(f"unknown reference mode {mode!r}")


def rounding(mode: str):
    """Where the configuration states bfloat16 activations (the protected
    modes), the reference rounds to bfloat16 wherever the served model
    holds its activations in bfloat16, so that the integer GEMMs see the
    values the configuration says they see; the float modes keep float32
    throughout."""
    if mode.startswith("ft"):
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    return lambda x: x


def bf16_matmul(x, w):
    """A bfloat16 GEMM: bfloat16 operands, float32 accumulation."""
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale


def he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(
        jnp.float32(fan_in))


class Keys:
    """Independent keys for the leaves of one init, by running index."""

    def __init__(self, key):
        self.key, self.i = key, 0

    def __call__(self):
        self.i += 1
        return jax.random.fold_in(self.key, self.i)


def check_fields(cfg, want: dict) -> None:
    """Raise unless every named attribute of the program's config has the
    value the configuration file states."""
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"program config {cfg.name} departs from the "
                         f"configuration file: {bad} (program, file)")
