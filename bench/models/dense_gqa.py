"""Dense decoder with grouped-query attention (Qwen2 family).

Per layer: RMSNorm -> Q/K/V projections with bias -> rotary embedding
(rotate-half, ``rope_theta``) -> causal grouped-query attention -> output
projection -> residual; RMSNorm -> SiLU-gated MLP -> residual. Final RMSNorm,
untied head. The served logits carry the program's readout temperature
``1/sqrt(hidden_size)`` (argmax-neutral); the reference applies it too.

The weights are made here from the seed, in the layout the program's
``ServeEngine`` takes (float32 masters), and the reference reads the same
arrays.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench.models.common import (HIGHEST, Keys, check_fields, he, linear,
                                 rms_norm, rounding, site_levels)

PROTECTED = ("q", "k", "v", "o", "gate", "up", "down", "head")


def dims(c: dict) -> dict:
    D = c["hidden_size"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return {"D": D, "H": H, "Hkv": Hkv, "hd": D // H,
            "F": c["intermediate_size"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"]}


def program_config(base, c: dict):
    """The program's model config for this cut: ``base`` (the program's
    published-width config) with the depth and vocabulary of the cut,
    after checking that every width matches the published one."""
    d = dims(c)
    want = {"d_model": d["D"], "n_heads": d["H"], "n_kv_heads": d["Hkv"],
            "d_ff": d["F"], "resolved_head_dim": d["hd"], "qkv_bias": True,
            "rope_theta": c["rope_theta"], "norm_eps": c["rms_norm_eps"],
            "tie_embeddings": c["tie_word_embeddings"], "family": "dense"}
    check_fields(base, want)
    return dataclasses.replace(base, n_layers=d["L"], vocab_size=d["V"])


def gemm_sites(c: dict) -> list:
    d = dims(c)
    D, H, Hkv, hd, F, L = d["D"], d["H"], d["Hkv"], d["hd"], d["F"], d["L"]
    rows = [("q", D, H * hd), ("k", D, Hkv * hd), ("v", D, Hkv * hd),
            ("o", H * hd, D), ("gate", D, F), ("up", D, F), ("down", F, D)]
    sites = [{"site": s, "K": K, "N": N, "layers": L, "protected": True}
             for s, K, N in rows]
    sites.append({"site": "head", "K": D, "N": d["V"], "layers": 1,
                  "protected": True})
    return sites


def context_ops(c: dict, pos: int) -> float:
    """Attention operations of the token at position ``pos``: scores and
    weighted values over ``pos + 1`` keys, every head, every layer."""
    d = dims(c)
    return 4.0 * d["H"] * d["hd"] * (pos + 1) * d["L"]


def init_params(key, c: dict):
    d = dims(c)
    D, H, Hkv, hd, F, L, V = (d["D"], d["H"], d["Hkv"], d["hd"], d["F"],
                              d["L"], d["V"])
    k = Keys(key)

    def scale(n):
        return 1.0 + 0.05 * jax.random.normal(k(), (L, n), jnp.float32)

    def bias(n):
        return 0.02 * jax.random.normal(k(), (L, n), jnp.float32)

    layer = {
        "attn": {"norm": {"scale": scale(D)},
                 "wq": {"w": he(k(), (L, D, H * hd), D), "b": bias(H * hd)},
                 "wk": {"w": he(k(), (L, D, Hkv * hd), D),
                        "b": bias(Hkv * hd)},
                 "wv": {"w": he(k(), (L, D, Hkv * hd), D),
                        "b": bias(Hkv * hd)},
                 "wo": {"w": he(k(), (L, H * hd, D), H * hd)}},
        "mlp": {"norm": {"scale": scale(D)},
                "gate": {"w": he(k(), (L, D, F), D)},
                "up": {"w": he(k(), (L, D, F), D)},
                "down": {"w": he(k(), (L, F, D), F)}},
    }
    embed = {"tok": he(k(), (V, D), D),
             "final_norm": {"scale": 1.0 + 0.05 * jax.random.normal(
                 k(), (D,), jnp.float32)},
             "head": he(k(), (D, V), D)}
    return {"embed": embed, "stack": [(layer,)]}


def _rope(x, theta):
    """Rotate-half rotary embedding of x [T, heads, hd] at positions 0..T-1."""
    T, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def reference_logits(params, tokens, c: dict, mode: str):
    """Float32 logits [T, V] of one sequence (teacher-forced, causal).
    ``r`` rounds to the activation type the mode states
    (``common.rounding``): bfloat16 where the served model holds
    bfloat16, identity in the float modes."""
    d = dims(c)
    D, H, Hkv, hd, F = d["D"], d["H"], d["Hkv"], d["hd"], d["F"]
    ent, eps = c["entanglement"], c["rms_norm_eps"]
    T = tokens.shape[0]
    G = H // Hkv
    r = rounding(mode)

    def lin(x, w, K):
        return r(linear(x, w, site_levels(mode, ent, K, True)))

    x = r(jnp.take(params["embed"]["tok"], tokens, axis=0))
    mask = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        h = r(rms_norm(x, a["norm"]["scale"], eps))
        q = r(lin(h, a["wq"]["w"], D) + r(a["wq"]["b"]))
        k = r(lin(h, a["wk"]["w"], D) + r(a["wk"]["b"]))
        v = r(lin(h, a["wv"]["w"], D) + r(a["wv"]["b"]))
        q = r(_rope(q.reshape(T, H, hd), c["rope_theta"]))
        k = r(_rope(k.reshape(T, Hkv, hd), c["rope_theta"]))
        v = v.reshape(T, Hkv, hd)
        k = jnp.repeat(k, G, axis=1)  # head h reads kv head h // G
        v = jnp.repeat(v, G, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST)
        s = jnp.where(mask[None], s / math.sqrt(hd), -jnp.inf)
        o = jnp.einsum("hts,shd->thd", r(jax.nn.softmax(s, axis=-1)), v,
                       precision=HIGHEST).reshape(T, H * hd)
        x = r(x + lin(r(o), a["wo"]["w"], H * hd))
        h = r(rms_norm(x, m["norm"]["scale"], eps))
        g = lin(h, m["gate"]["w"], D)
        u = lin(h, m["up"]["w"], D)
        x = r(x + lin(r(r(jax.nn.silu(g)) * u), m["down"]["w"], F))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["stack"][0][0])
    hf = r(rms_norm(x, params["embed"]["final_norm"]["scale"], eps))
    return linear(hf, params["embed"]["head"],
                  site_levels(mode, ent, D, True)) / math.sqrt(D)
