"""Latent-attention decoder with routed and shared experts (DeepSeek-V2
family), holding one chip's share of the routed experts.

Per layer: RMSNorm -> ``wq`` to 16 heads of (128 nope + 64 rope) and
``wkv_a`` to the 512-wide latent plus one 64-wide rope key shared by every
head -> RMSNorm of the latent (``kv_norm``) -> ``wkv_b`` up-projects the
latent to per-head keys (nope part) and values -> YaRN rotary embedding of
the rope dims -> causal attention at softmax scale (nope + rope)^-1/2 times
YaRN's mscale(factor, mscale_all_dim)^2 -> ``wo`` -> residual; RMSNorm ->
the first ``first_k_dense_replace`` layers a SiLU-gated MLP, the rest a
router (softmax over ``router_experts``, top ``num_experts_per_tok``, the
weights left un-normalized when ``norm_topk_prob`` is false) whose picks
among the held experts run SiLU-gated expert MLPs scaled by their routing
weight, plus ``n_shared_experts`` shared experts as one gated MLP ->
residual. Final RMSNorm, untied head, readout temperature
``1/sqrt(hidden_size)`` (argmax-neutral; the program's).

Expert share: the chip holds experts ``first_held_expert`` onward,
``n_routed_experts`` of them; the router scores all ``router_experts``,
and picks of experts held elsewhere add nothing here, exactly as the
program's share computes it. Protected sites (``wq``, ``wkv_a``, ``wo``,
router, dense and shared MLPs, each routed expert matrix on its own grid,
head) follow ``common.site_levels``; ``wkv_b`` is unprotected (bfloat16).

The weights are made here from the seed, in the layout the program's
``ServeEngine`` takes (float32 masters), and the reference reads the same
arrays. Attention runs in query blocks so that a 5,120-token check fits.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench.models.common import (HIGHEST, Keys, check_fields, he, linear,
                                 rms_norm, rounding, site_levels)

QB = 512  # query block of the reference attention


def dims(c: dict) -> dict:
    L = c["num_hidden_layers"]
    Ld = c["first_k_dense_replace"]
    return {"D": c["hidden_size"], "H": c["num_attention_heads"],
            "r": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"],
            "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "F": c["intermediate_size"], "Fe": c["moe_intermediate_size"],
            "Fs": c["n_shared_experts"] * c["moe_intermediate_size"],
            "E": c["router_experts"], "Eh": c["n_routed_experts"],
            "e0": c["first_held_expert"], "k": c["num_experts_per_tok"],
            "L": L, "Ld": Ld, "Lm": L - Ld, "V": c["vocab_size"]}


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(c: dict) -> float:
    d = dims(c)
    rs = c["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (d["dn"] + d["dr"]) ** -0.5 * m * m


def yarn_inv_freq(c: dict):
    """DeepSeek-V2's YaRN inverse frequencies of the rope dims, [dr/2]."""
    rs, theta, dim = c["rope_scaling"], c["rope_theta"], c["qk_rope_head_dim"]
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    expo = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / theta ** expo
    inter = 1.0 / (rs["factor"] * theta ** expo)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp  # 1 where the original frequency is kept
    return inter * (1.0 - mask) + extra * mask


def program_config(base, c: dict):
    """The program's model config for this cut: ``base`` (the program's
    published-width config) with the depth, vocabulary and expert share of
    the cut, dropless, serving latent-attention decode as the file states,
    after checking that every width and setting matches the file."""
    d = dims(c)
    rs = c["rope_scaling"]
    want = {"d_model": d["D"], "n_heads": d["H"],
            "n_kv_heads": c["num_key_value_heads"], "d_ff": d["F"],
            "rope_theta": c["rope_theta"], "norm_eps": c["rms_norm_eps"],
            "tie_embeddings": c["tie_word_embeddings"], "family": "moe",
            "qkv_bias": c["attention_bias"]}
    check_fields(base, want)
    _check_group(base.mla, "mla", {
        "kv_lora_rank": d["r"], "q_lora_rank": c["q_lora_rank"] or 0,
        "qk_nope_head_dim": d["dn"], "qk_rope_head_dim": d["dr"],
        "v_head_dim": d["dv"]})
    _check_group(base.moe, "moe", {
        "n_experts": d["E"], "top_k": d["k"],
        "n_shared": c["n_shared_experts"], "d_ff_expert": d["Fe"],
        "gating": c["scoring_func"], "first_dense_layers": d["Ld"],
        "norm_topk_prob": c["norm_topk_prob"]})
    _check_group(base.rope_scaling, "rope_scaling", {
        "factor": rs["factor"],
        "original_max_position_embeddings":
            rs["original_max_position_embeddings"],
        "beta_fast": rs["beta_fast"], "beta_slow": rs["beta_slow"],
        "mscale": rs["mscale"], "mscale_all_dim": rs["mscale_all_dim"]})
    # capacity factor E / k: every held expert's buffer holds all the
    # call's tokens, so no pick is dropped at any skew (dropless)
    moe = dataclasses.replace(base.moe, n_held=d["Eh"], first_held=d["e0"],
                              capacity_factor=d["E"] / d["k"])
    return dataclasses.replace(base, n_layers=d["L"], vocab_size=d["V"],
                               moe=moe,
                               mla_absorb=c["mla_decode"] == "absorbed")


def _check_group(group, name: str, want: dict) -> None:
    """Raise unless the program config's ``name`` group holds every value
    the configuration file states."""
    bad = {k: (getattr(group, k, None), v) for k, v in want.items()
           if getattr(group, k, None) != v}
    if bad:
        raise ValueError(f"program config's {name} departs from the "
                         f"configuration file: {bad} (program, file)")


def gemm_sites(c: dict) -> list:
    """Every projection a token passes through. The routed experts count
    the work this chip does per token: ``k * Eh / E`` expert MLPs a MoE
    layer (0.75 at 6 of 64 picks over 8 held), the share of picks that
    land here on average. ``kv_b`` (unprotected) counts the latent up- or
    absorbed projection, the same 2 * r * H * (dn + dv) either way."""
    d = dims(c)
    D, H, r, dn, dr, dv = d["D"], d["H"], d["r"], d["dn"], d["dr"], d["dv"]
    L, Ld, Lm = d["L"], d["Ld"], d["Lm"]
    routed = Lm * d["k"] * d["Eh"] / d["E"]
    rows = [("q", D, H * (dn + dr), L, True), ("kv_a", D, r + dr, L, True),
            ("kv_b", r, H * (dn + dv), L, False), ("o", H * dv, D, L, True),
            ("gate", D, d["F"], Ld, True), ("up", D, d["F"], Ld, True),
            ("down", d["F"], D, Ld, True),
            ("router", D, d["E"], Lm, True),
            ("shared_gate", D, d["Fs"], Lm, True),
            ("shared_up", D, d["Fs"], Lm, True),
            ("shared_down", d["Fs"], D, Lm, True)]
    rows += [(s, K, N, routed, True) for s, K, N in grouped_sites(c)]
    sites = [{"site": s, "K": K, "N": N, "layers": n, "protected": p}
             for s, K, N, n, p in rows]
    sites.append({"site": "head", "K": D, "N": d["V"], "layers": 1,
                  "protected": True})
    return sites


def grouped_sites(c: dict) -> list:
    """(site, K, N) of the routed experts' GEMMs, one grouped kernel call
    each per MoE layer."""
    d = dims(c)
    return [("expert_gate", d["D"], d["Fe"]), ("expert_up", d["D"], d["Fe"]),
            ("expert_down", d["Fe"], d["D"])]


def grouped_least_time(c: dict, rows: int, peak_ops: float,
                       hbm_bytes_per_s: float) -> float:
    """Least time of the grouped expert kernels of one program call over
    ``rows`` token rows: every MoE layer reads the held experts' int8
    weights once, and computes the routed rows' plain GEMMs, taken at the
    expected share ``rows * k * Eh / E`` (uniform routing; the random
    router of the benchmark's weights spreads its picks about evenly)
    with int8 activations read and int32 outputs written."""
    d = dims(c)
    routed = rows * d["k"] * d["Eh"] / d["E"]
    t = 0.0
    for _, K, N in grouped_sites(c):
        ops = 2.0 * routed * K * N
        nbytes = d["Eh"] * K * N + routed * K + 4 * routed * N
        t += max(ops / peak_ops, nbytes / hbm_bytes_per_s)
    return d["Lm"] * t


def context_ops(c: dict, pos: int) -> float:
    """Absorbed latent attention of the token at position ``pos``: scores
    over ``pos + 1`` cached latents and rope keys, and the weighted latent
    sum, every head, every layer."""
    d = dims(c)
    return 2.0 * d["H"] * (2 * d["r"] + d["dr"]) * (pos + 1) * d["L"]


def init_params(key, c: dict):
    d = dims(c)
    D, H, r, dn, dr, dv = d["D"], d["H"], d["r"], d["dn"], d["dr"], d["dv"]
    k = Keys(key)

    def scale(n, lead):
        return 1.0 + 0.05 * jax.random.normal(k(), (lead, n), jnp.float32)

    def attn(n):
        return {"norm": {"scale": scale(D, n)},
                "wq": {"w": he(k(), (n, D, H * (dn + dr)), D)},
                "wkv_a": {"w": he(k(), (n, D, r + dr), D)},
                "kv_norm": {"scale": scale(r, n)},
                "wkv_b": {"w": he(k(), (n, r, H * (dn + dv)), r)},
                "wo": {"w": he(k(), (n, H * dv, D), H * dv)}}

    def mlp(n, F):
        return {"gate": {"w": he(k(), (n, D, F), D)},
                "up": {"w": he(k(), (n, D, F), D)},
                "down": {"w": he(k(), (n, F, D), F)}}

    Ld, Lm, Eh, Fe = d["Ld"], d["Lm"], d["Eh"], d["Fe"]
    dense = {"attn": attn(Ld),
             "mlp": {"norm": {"scale": scale(D, Ld)}, **mlp(Ld, d["F"])}}
    moe = {"attn": attn(Lm),
           "moe": {"norm": {"scale": scale(D, Lm)},
                   "router": he(k(), (Lm, D, d["E"]), D),
                   "we_gate": he(k(), (Lm, Eh, D, Fe), D),
                   "we_up": he(k(), (Lm, Eh, D, Fe), D),
                   "we_down": he(k(), (Lm, Eh, Fe, D), Fe),
                   "shared": mlp(Lm, d["Fs"])}}
    embed = {"tok": he(k(), (d["V"], D), D),
             "final_norm": {"scale": 1.0 + 0.05 * jax.random.normal(
                 k(), (D,), jnp.float32)},
             "head": he(k(), (D, d["V"]), D)}
    return {"embed": embed, "stack": [(dense,), (moe,)]}


def _rope(x, inv_freq):
    """Rotate-half rotary embedding of x [T, heads, dr] at positions
    0..T-1 (the program's pairing; the published model interleaves, a
    fixed permutation of the rope columns)."""
    T, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, scale):
    """Causal softmax attention, q/k [T, H, dk], v [T, H, dv], in query
    blocks of ``QB``."""
    T = q.shape[0]
    nb = -(-T // QB)
    pad = nb * QB - T
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(nb, QB, *q.shape[1:])
    kpos = jnp.arange(T)

    def block(args):
        i, qb = args
        s = jnp.einsum("thd,shd->hts", qb, k, precision=HIGHEST) * scale
        qpos = i * QB + jnp.arange(QB)
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(nb), qp))
    return o.reshape(nb * QB, *o.shape[2:])[:T]


def reference_logits(params, tokens, c: dict, mode: str):
    """Float32 logits [T, V] of one sequence (teacher-forced, causal).
    ``r`` rounds to the activation type the mode states
    (``common.rounding``): bfloat16 where the served model holds
    bfloat16, identity in the float modes."""
    d = dims(c)
    D, H, r_, dn, dr, dv = d["D"], d["H"], d["r"], d["dn"], d["dr"], d["dv"]
    ent, eps = c["entanglement"], c["rms_norm_eps"]
    T = tokens.shape[0]
    r = rounding(mode)
    inv_freq = yarn_inv_freq(c)
    scale = softmax_scale(c)

    def lin(x, w, K, protected=True):
        return r(linear(x, w, site_levels(mode, ent, K, protected)))

    def attention(x, a):
        h = r(rms_norm(x, a["norm"]["scale"], eps))
        q = lin(h, a["wq"]["w"], D).reshape(T, H, dn + dr)
        kv = lin(h, a["wkv_a"]["w"], D)
        ckv = r(rms_norm(kv[:, :r_], a["kv_norm"]["scale"], eps))
        kr = r(_rope(kv[:, None, r_:], inv_freq))  # [T, 1, dr]
        qr = r(_rope(q[..., dn:], inv_freq))
        kvb = lin(ckv, a["wkv_b"]["w"], r_, False).reshape(T, H, dn + dv)
        kf = jnp.concatenate([kvb[..., :dn],
                              jnp.broadcast_to(kr, (T, H, dr))], -1)
        qf = jnp.concatenate([q[..., :dn], qr], -1)
        o = _attend(qf, kf, kvb[..., dn:], scale).reshape(T, H * dv)
        return r(x + lin(r(o), a["wo"]["w"], H * dv))

    def gated(h, m, F):
        g = lin(h, m["gate"]["w"], D)
        u = lin(h, m["up"]["w"], D)
        return lin(r(r(jax.nn.silu(g)) * u), m["down"]["w"], F)

    def dense_layer(x, p):
        x = attention(x, p["attn"])
        m = p["mlp"]
        h = r(rms_norm(x, m["norm"]["scale"], eps))
        return r(x + gated(h, m, d["F"])), None

    def expert(h, wg, wu, wd):
        # the program's grouped GEMMs hand back float32: SiLU before the
        # rounding, unlike the dense sites
        g = linear(h, wg, site_levels(mode, ent, D, True))
        u = lin(h, wu, D)
        return lin(r(r(jax.nn.silu(g)) * u), wd, d["Fe"])

    def moe_layer(x, p):
        x = attention(x, p["attn"])
        m = p["moe"]
        h = r(rms_norm(x, m["norm"]["scale"], eps))
        logits = linear(h, m["router"],
                        site_levels(mode, ent, D, True))  # float32
        probs = jax.nn.softmax(logits, axis=-1)
        vals, idx = jax.lax.top_k(probs, d["k"])  # [T, k]
        if c["norm_topk_prob"]:
            vals = vals / jnp.sum(vals, -1, keepdims=True)
        held = d["e0"] + jnp.arange(d["Eh"])
        # routing weight of each held expert for each token (0 if unpicked)
        wts = jnp.sum(jnp.where(idx[:, :, None] == held[None, None],
                                vals[:, :, None], 0.0), axis=1)  # [T, Eh]
        ys = jax.vmap(expert, in_axes=(None, 0, 0, 0))(
            h, m["we_gate"], m["we_up"], m["we_down"])  # [Eh, T, D]
        routed = jnp.sum(r(r(wts.T[:, :, None]) * ys), axis=0)
        shared = gated(h, m["shared"], d["Fs"])
        return r(x + r(r(routed) + shared)), None

    x = r(jnp.take(params["embed"]["tok"], tokens, axis=0))
    x, _ = jax.lax.scan(dense_layer, x, params["stack"][0][0])
    x, _ = jax.lax.scan(moe_layer, x, params["stack"][1][0])
    hf = r(rms_norm(x, params["embed"]["final_norm"]["scale"], eps))
    return linear(hf, params["embed"]["head"],
                  site_levels(mode, ent, D, True)) / math.sqrt(D)
