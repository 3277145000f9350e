"""Attention-free Mamba-1 selective state-space decoder (Falcon-Mamba
widths).

Per layer: RMSNorm -> ``in_proj`` to ``2 * intermediate_size`` (x, z) ->
causal depthwise conv of width ``conv_kernel`` with bias -> SiLU = u ->
``x_proj`` to (dt_rank, B, C) -> dt = softplus(dt_proj(dt_rank) + bias) ->
selective scan h_t = exp(dt_t A) h_{t-1} + dt_t u_t B_t, y_t = h_t . C_t,
A = -exp(A_log) -> y + D u -> times SiLU(z) -> ``out_proj`` -> residual.
Final RMSNorm, untied head, readout temperature ``1/sqrt(hidden_size)``.

Falcon-Mamba-7B also RMS-normalizes B, C and dt inside the mixer
(``mixer_rms_eps``). The program's mixer does not; this reference follows
the program's Mamba-1 mixer, and the configuration file names that
departure.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench.models.common import (Keys, check_fields, he, linear, rms_norm,
                                 rounding, site_levels)


def dims(c: dict) -> dict:
    return {"D": c["hidden_size"], "di": c["intermediate_size"],
            "S": c["state_size"], "kc": c["conv_kernel"],
            "R": c["time_step_rank"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"]}


def program_config(base, c: dict):
    """The program's model config for this cut (see dense_gqa)."""
    d = dims(c)
    ssm = base.ssm
    dt_rank = (ssm.dt_rank or -(-base.d_model // 16)) if ssm else None
    want = {"d_model": d["D"], "family": "ssm",
            "norm_eps": c["norm_eps_served"],
            "tie_embeddings": c["tie_word_embeddings"]}
    check_fields(base, want)
    got = (ssm.d_state, ssm.d_conv, ssm.expand * base.d_model, dt_rank)
    if got != (d["S"], d["kc"], d["di"], d["R"]):
        raise ValueError(f"program SSM widths {got} differ from the "
                         f"configuration's {(d['S'], d['kc'], d['di'], d['R'])}")
    return dataclasses.replace(base, n_layers=d["L"], vocab_size=d["V"])


def gemm_sites(c: dict) -> list:
    d = dims(c)
    D, di, S, R, L = d["D"], d["di"], d["S"], d["R"], d["L"]
    return [
        {"site": "in_proj", "K": D, "N": 2 * di, "layers": L,
         "protected": True},
        {"site": "x_proj", "K": di, "N": R + 2 * S, "layers": L,
         "protected": False},
        {"site": "dt_proj", "K": R, "N": di, "layers": L,
         "protected": False},
        {"site": "out_proj", "K": di, "N": D, "layers": L,
         "protected": True},
        {"site": "head", "K": D, "N": d["V"], "layers": 1,
         "protected": True},
    ]


def context_ops(c: dict, pos: int) -> float:
    """Scan and conv operations of one token, every layer: the state
    update (3 per state entry), the readout (2 per state entry), the conv
    (2 per tap and channel); constant in the position."""
    d = dims(c)
    return float(d["L"] * d["di"] * (5 * d["S"] + 2 * d["kc"]))


def init_params(key, c: dict):
    d = dims(c)
    D, di, S, kc, R, L, V = (d["D"], d["di"], d["S"], d["kc"], d["R"],
                             d["L"], d["V"])
    k = Keys(key)
    # dt drawn log-uniformly in [1e-3, 1e-1] per channel, stored as the
    # inverse softplus (Mamba's initialisation); A from S4D-real
    dt = jnp.exp(jax.random.uniform(k(), (L, di), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    layer = {
        "norm": {"scale": 1.0 + 0.05 * jax.random.normal(k(), (L, D))},
        "in_proj": {"w": he(k(), (L, D, 2 * di), D)},
        "conv_w": he(k(), (L, di, kc), kc),
        "conv_b": 0.02 * jax.random.normal(k(), (L, di)),
        "x_proj": {"w": he(k(), (L, di, R + 2 * S), di)},
        "dt_proj": {"w": he(k(), (L, R, di), R),
                    "b": dt + jnp.log(-jnp.expm1(-dt))},
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, S + 1, dtype=jnp.float32), (L, di, S))),
        "D_skip": jnp.ones((L, di), jnp.float32),
        "out_proj": {"w": he(k(), (L, di, D), di)},
    }
    embed = {"tok": he(k(), (V, D), D),
             "final_norm": {"scale": 1.0 + 0.05 * jax.random.normal(
                 k(), (D,), jnp.float32)},
             "head": he(k(), (D, V), D)}
    return {"embed": embed, "stack": [(layer,)]}


def reference_logits(params, tokens, c: dict, mode: str):
    """Float32 logits [T, V] of one sequence, the scan run step by step.
    ``r`` rounds to the activation type the mode states (see dense_gqa);
    in the protected modes the float32 contractions that the served model
    runs at the chip's default precision (dt_proj, the scan's readout)
    take bfloat16 operands as it does."""
    d = dims(c)
    D, di, S, kc, R = d["D"], d["di"], d["S"], d["kc"], d["R"]
    ent, eps = c["entanglement"], c["norm_eps_served"]
    T = tokens.shape[0]
    r = rounding(mode)
    x = r(jnp.take(params["embed"]["tok"], tokens, axis=0))

    def layer(x, p):
        h = r(rms_norm(x, p["norm"]["scale"], eps))
        xz = r(linear(h, p["in_proj"]["w"], site_levels(mode, ent, D, True)))
        xs, z = xz[:, :di], xz[:, di:]
        xp = jnp.concatenate([jnp.zeros((kc - 1, di), xs.dtype), xs], 0)
        conv = sum(xp[j:j + T] * p["conv_w"][:, j] for j in range(kc))
        u = jax.nn.silu(conv + p["conv_b"])
        proj = r(linear(r(u), p["x_proj"]["w"],
                        site_levels(mode, ent, di, False)))
        dt = jax.nn.softplus(
            linear(proj[:, :R], r(p["dt_proj"]["w"]),
                   site_levels(mode, ent, R, False)) + p["dt_proj"]["b"])
        Bc, Cc = proj[:, R:R + S], proj[:, R + S:]
        A = -jnp.exp(p["A_log"])

        def step(hs, xt):
            dt_t, u_t, b_t, c_t = xt
            hs = jnp.exp(dt_t[:, None] * A) * hs + (dt_t * u_t)[:, None] \
                * b_t[None, :]
            return hs, jnp.sum(r(hs) * c_t[None, :], axis=-1)

        _, ys = jax.lax.scan(step, jnp.zeros((di, S), jnp.float32),
                             (dt, u, Bc, Cc), unroll=8)
        y = r(r(ys + u * p["D_skip"]) * r(jax.nn.silu(z)))
        x = r(x + r(linear(y, p["out_proj"]["w"],
                           site_levels(mode, ent, di, True))))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["stack"][0][0])
    hf = r(rms_norm(x, params["embed"]["final_norm"]["scale"], eps))
    return linear(hf, params["embed"]["head"],
                  site_levels(mode, ent, D, True)) / math.sqrt(D)
