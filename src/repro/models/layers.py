"""Model-zoo building blocks, pure-functional JAX.

Conventions:
  * params are nested dicts of jnp arrays (f32 masters; matmuls run bf16),
  * every block has ``init(key, cfg, max_seq) -> params`` and
    ``apply(params, x, *, cfg, cache, pos, mode) -> (y, new_cache)``,
  * ``mode`` in {train, prefill, decode}; decode processes T=1 with a cache,
  * activations carry logical sharding annotations (repro.dist.sharding),
  * blocks are scanned over layers by the assemblers (models/transformer.py),
    so shapes/dtypes must be layer-invariant within a pattern unit.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, RopeScaling
from repro.dist.sharding import constrain

ACT_DTYPE = jnp.bfloat16


def _he(key, shape, fan_in=None, dtype=jnp.float32):
    fan_in = fan_in or shape[0]
    return (jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _split(key, n):
    return list(jax.random.split(key, n))


# ----------------------------------------------------------------- norms ----

def init_norm(cfg: ModelConfig, d: int):
    if cfg.norm_kind == "layernorm":
        return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}
    return {"scale": jnp.ones((d,))}


def apply_norm(p, x, cfg: ModelConfig):
    x32 = x.astype(jnp.float32)
    if cfg.norm_kind == "layernorm":
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.var(x32, -1, keepdims=True)
        y = (x32 - mu) * lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"] + p["bias"]
        return y.astype(ACT_DTYPE)
    ms = jnp.mean(jnp.square(x32), -1, keepdims=True)  # f32 reduce (fused)
    if cfg.norm_f32:
        y = x32 * lax.rsqrt(ms + cfg.norm_eps) * p["scale"]
        return y.astype(ACT_DTYPE)
    # bf16 elementwise apply: no f32 [B,T,D] materialization (§Perf)
    inv = lax.rsqrt(ms + cfg.norm_eps).astype(ACT_DTYPE)
    return x.astype(ACT_DTYPE) * inv * p["scale"].astype(ACT_DTYPE)


# ------------------------------------------------------------------ rope ----

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor 0.1 * mscale * ln(factor) + 1
    (1 for factor <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_range(sc: RopeScaling, dim: int, theta: float):
    """(low, high) rotary dims between which YaRN ramps from extrapolated
    to interpolated frequencies: the dims that turn ``beta_fast`` and
    ``beta_slow`` times over the original context, floored / ceiled and
    clipped to [0, dim - 1]."""
    def dim_of(rot):
        return (dim * math.log(sc.original_max_position_embeddings
                               / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = math.floor(dim_of(sc.beta_fast))
    high = math.ceil(dim_of(sc.beta_slow))
    return max(low, 0), min(high, dim - 1)


def rope_freqs(half: int, theta: float,
               scaling: Optional[RopeScaling] = None) -> jax.Array:
    """Rotary inverse frequencies [half]; with YaRN ``scaling`` each is
    blended between f / factor (interpolated) and f (extrapolated) by a
    linear ramp over the correction range."""
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    if scaling is None:
        return freqs
    low, high = _yarn_correction_range(scaling, 2 * half, theta)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         scaling: Optional[RopeScaling] = None) -> jax.Array:
    """NeoX-style rotary embedding. x: [B, T, H, hd], positions: [B, T].
    ``scaling`` (YaRN) blends the frequencies (:func:`rope_freqs`) and
    scales cos/sin by mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = rope_freqs(half, theta, scaling)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# ----------------------------------------------------------------- dense ----

def init_dense(key, d_in, d_out, bias=False):
    p = {"w": _he(key, (d_in, d_out))}
    if bias:
        p["b"] = jnp.zeros((d_out,))
    return p


def _dense_w(p):
    """The weight :func:`dense` would hand the protected path: the startup
    pre-quantized (wq, scale) pair when installed, else the float master."""
    return (p["q8"]["w"], p["q8"]["scale"]) if "q8" in p else p["w"]


def dense(p, x, *, ft=None, site=None):
    """Dense projection — THE protected-GEMM chokepoint.

    When an :class:`repro.ft.FTContext` is threaded down (serving, with
    ``ft_scope`` covering ``site``'s category) the matmul runs as the fused
    entangled int8 GEMM with in-kernel fail-stop roll-forward instead of
    the bf16 einsum; the bias stays in float either way. ``ft=None`` (train
    and every pre-existing caller) is the unprotected fast path.

    A ``q8`` entry (installed by :func:`repro.ft.prepare_params` at engine
    startup) carries the site's pre-quantized int8 weights + scale; when
    present the protected path uses it directly, so the traced step holds
    no eq.-13 weight-quantization ops — the float master ``w`` stays the
    source of truth for every unprotected caller.
    """
    if ft is not None and site is not None and ft.protects(site):
        y = ft.matmul(site, x, _dense_w(p)).astype(ACT_DTYPE)
    else:
        y = jnp.einsum("...d,df->...f", x.astype(ACT_DTYPE),
                       p["w"].astype(ACT_DTYPE))
    if "b" in p:
        y = y + p["b"].astype(ACT_DTYPE)
    return y


def dense_fanout(ps, x, *, ft, sites):
    """Fanout form of :func:`dense`: every site in ``sites`` projects the
    SAME activations ``x`` — attention Q/K/V, MLP gate/up, RG-LRU
    in_gate/in_x, MLA's two ``h`` projections, the MoE shared expert.

    When all sites are protected the group runs through
    :meth:`repro.ft.FTContext.matmul_fanout`: one quantize + group-permute
    codec pass feeds every member's fused entangled kernel call
    (bit-identical to per-site :func:`dense` calls, tested), and the
    engine's census-only traces mark the group as chainable at
    plan-compile time. Any other case — no ``ft``, a site out of scope —
    degrades to the per-site path. Returns one output per site, in order.
    """
    if ft is None or not all(ft.protects(s) for s in sites):
        return [dense(p, x, ft=ft, site=s) for p, s in zip(ps, sites)]
    ys = ft.matmul_fanout(tuple(sites), x,
                          tuple(_dense_w(p) for p in ps))
    outs = []
    for p, y in zip(ps, ys):
        y = y.astype(ACT_DTYPE)
        if "b" in p:
            y = y + p["b"].astype(ACT_DTYPE)
        outs.append(y)
    return outs


# ---------------------------------------------------------- GQA attention ----

def init_attention(key, cfg: ModelConfig, max_seq: int):
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = _split(key, 4)
    return {
        "norm": init_norm(cfg, cfg.d_model),
        "wq": init_dense(k1, cfg.d_model, cfg.n_heads * hd, cfg.qkv_bias),
        "wk": init_dense(k2, cfg.d_model, cfg.n_kv_heads * hd, cfg.qkv_bias),
        "wv": init_dense(k3, cfg.d_model, cfg.n_kv_heads * hd, cfg.qkv_bias),
        "wo": init_dense(k4, cfg.n_heads * hd, cfg.d_model),
    }


def init_attn_cache(cfg: ModelConfig, batch: int, max_seq: int, window: int = 0):
    hd = cfg.resolved_head_dim
    s = min(max_seq, window) if window else max_seq
    shape = (batch, s, cfg.n_kv_heads, hd)
    return {
        "k": jnp.zeros(shape, ACT_DTYPE),
        "v": jnp.zeros(shape, ACT_DTYPE),
    }


def _is_pos_vector(pos) -> bool:
    """True when ``pos`` is a per-row position vector [B] (batched serving
    decode) rather than a scalar shared across the batch."""
    return pos is not None and jnp.ndim(pos) == 1


def _decode_positions(pos, B: int, T: int) -> jax.Array:
    """[B, T] absolute positions of the decode step (T == 1 tokens)."""
    if _is_pos_vector(pos):
        return jnp.broadcast_to(jnp.asarray(pos, jnp.int32)[:, None], (B, T))
    return jnp.full((B, T), pos, dtype=jnp.int32)


def _cache_write(buf: jax.Array, new: jax.Array, write):
    """Write the decode-step entry ``new`` [B, 1, ...] into cache ``buf``
    [B, S, ...] at slot ``write`` — shared scalar (dynamic_update_slice) or
    per-row vector [B] (one scatter row per batch element)."""
    if _is_pos_vector(write):
        B = buf.shape[0]
        return buf.at[jnp.arange(B), write].set(new[:, 0])
    idx = (0, write) + (0,) * (buf.ndim - 2)
    return lax.dynamic_update_slice(buf, new, idx)


def _prefill_off(pos, mode: str):
    """Chunk offset of a prefill call: the engine's chunked prefill processes
    tokens [B, C] at absolute positions off..off+C-1. Two forms:

      * a Python int (bucketed per-batch chunking — every row of the batch
        shares one offset, each (bucket, chunk) shape traces once),
      * a TRACED int32 vector [B] (token-packed prefill — every row is a
        DIFFERENT request at its own offset, so ONE compiled shape serves
        every packing mix).

    Classic whole-prompt prefill passes pos=None -> offset 0."""
    if mode != "prefill" or pos is None:
        return 0
    if _is_pos_vector(pos):
        return jnp.asarray(pos, jnp.int32)
    return int(pos)


def _off_any(off) -> bool:
    """True when any row of this prefill call may start past position 0
    (an earlier chunk's cache/conv tail can exist). Always True for a
    per-row offset vector — rows at offset 0 read a zeroed cache row, which
    is bitwise identical to the fresh-state branch."""
    return _is_pos_vector(off) or bool(off)


def _conv_tail_state(xp: jax.Array, off, T: int, lengths,
                     d_conv: int) -> jax.Array:
    """Per-row depthwise-conv tail state of a bucketed prefill chunk:
    the last ``d_conv - 1`` REAL inputs per row, gathered from
    ``xp = [prev_tail (d_conv-1), inputs (T)]``. Index e..e+d_conv-2 ends
    at the row's last real position of this chunk; rows with no real
    positions (e = 0) keep the prior tail. Shared by Mamba and RG-LRU so
    the tail-index math can never diverge between them."""
    B = xp.shape[0]
    e = (jnp.clip(jnp.asarray(lengths, jnp.int32) - off, 0, T)
         if lengths is not None else jnp.full((B,), T, jnp.int32))
    gidx = e[:, None] + jnp.arange(d_conv - 1)[None]
    return jnp.take_along_axis(xp, gidx[..., None], axis=1).astype(ACT_DTYPE)


def _prefill_valid(off, T: int, lengths, *, time_major: bool = False):
    """[B, T] (or [T, B]) mask of REAL positions in a bucketed prefill
    chunk: global position off+t belongs to row b iff off+t < lengths_b.
    ``off`` is a shared int or a per-row vector [B] (token-packed prefill).
    None when lengths is None (whole batch real) — the single source of
    the bucket-padding validity invariant for every block type."""
    if lengths is None:
        return None
    L = jnp.asarray(lengths, jnp.int32)
    if _is_pos_vector(off):
        g = jnp.asarray(off, jnp.int32)[:, None] + jnp.arange(T)[None]
        m = g < L[:, None]  # [B, T]
        return m.T if time_major else m
    g = off + jnp.arange(T)
    if time_major:
        return g[:, None] < L[None, :]
    return g[None, :] < L[:, None]


def _window_prefill_write(cache: dict, k: jax.Array, v: jax.Array, *,
                          off, lengths, window: int):
    """Masked rolling-buffer write for a bucketed/chunked prefill step.

    Writes, per row, the last ``min(T, window)`` REAL positions before
    ``end_b = min(lengths_b, off + T)`` at slot p % window. Pad positions
    (>= lengths_b) and positions from earlier chunks (< off) leave the
    buffer untouched, so padding a prompt to its bucket can never clobber a
    previously written real key. Slot indices within a row are a contiguous
    position range of length <= window, hence collision-free. ``off`` is a
    shared int or a per-row offset vector [B] (token-packed prefill)."""
    B, T = k.shape[0], k.shape[1]
    off_b = jnp.asarray(off, jnp.int32) if _is_pos_vector(off) else off
    off_col = off_b[:, None] if _is_pos_vector(off) else off_b
    if lengths is None:
        end = jnp.full((B,), T, jnp.int32) + off_b
    else:
        end = jnp.clip(jnp.asarray(lengths, jnp.int32), off_b, off_b + T)
    keep = min(T, window)
    idx = end[:, None] - keep + jnp.arange(keep)[None]  # [B, keep] abs pos
    valid = idx >= off_col
    local = jnp.clip(idx - off_col, 0, T - 1)
    slots = idx % window
    bidx = jnp.arange(B)[:, None]

    def write(buf, new):
        sel = jnp.take_along_axis(new, local[..., None, None], axis=1)
        cur = buf[bidx, slots]
        return buf.at[bidx, slots].set(
            jnp.where(valid[..., None, None], sel, cur))

    return {"k": write(cache["k"], k), "v": write(cache["v"], v)}


def _cache_abs_pos(S: int, pos, window: int):
    """Absolute position of each cache slot during decode (-1 = not valid).

    Linear cache: slot s holds position s, valid while s <= pos.
    Rolling window cache: slot s holds the latest position congruent to s
    (mod window) that is <= pos.

    ``pos`` may be a scalar (-> [S]) or a per-row vector [B] (-> [B, S],
    the batched serving engine's per-slot positions)."""
    slot = jnp.arange(S)
    if _is_pos_vector(pos):
        slot = slot[None, :]
        pos = jnp.asarray(pos)[:, None]
    if not window:
        return jnp.where(slot <= pos, slot, -1)
    base = (pos // window) * window
    abs_pos = jnp.where(slot <= pos % window, base + slot, base - window + slot)
    ok = (abs_pos >= 0) & (abs_pos <= pos) & (abs_pos > pos - window)
    return jnp.where(ok, abs_pos, -1)


def apply_attention(
    p,
    x,
    *,
    cfg: ModelConfig,
    cache=None,
    pos=None,
    mode="train",
    window: int = 0,
    rope_theta: Optional[float] = None,
    cross_kv=None,
    lengths=None,
    ft=None,
):
    """GQA/MQA attention with optional sliding window and KV cache.

    cross_kv: precomputed (k, v) for cross-attention (whisper decoder);
    bypasses self-KV entirely (no mask, no rope).

    ``ft`` (serving): protection context — scope ``qkv`` runs the Q/K/V
    projections as entangled int8 GEMMs with fail-stop roll-forward.

    Batched/chunked prefill: ``pos`` (a static int) is the chunk offset and
    ``lengths`` [B] the per-row true prompt lengths of a bucket-padded
    batch — cache writes are offset (linear) or length-masked (rolling
    window), and chunk queries attend to all earlier cached positions.

    Token-packed prefill: ``pos`` is a TRACED int32 vector [B] of per-row
    chunk offsets (each row a different request). Linear cache writes become
    per-row scatters and queries attend over the FULL cache with a per-row
    causal mask — masked tail keys contribute exact 0.0 to the softmax
    reductions, so packed output is bitwise identical to per-batch chunking.
    """
    B, T, D = x.shape
    hd = cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    off = _prefill_off(pos, mode)
    vec_off = _is_pos_vector(off)
    h = apply_norm(p["norm"], x, cfg)

    win_kabs = None  # set on the bucketed/chunked rolling-window path
    win_qpos = None
    packed_qpos = None  # set on the token-packed linear-cache path
    if cross_kv is None:
        # Q/K/V consume the same normed activations: one fanout group
        # (a protected run shares a single quantize+group codec pass)
        q, k, v = dense_fanout((p["wq"], p["wk"], p["wv"]), h, ft=ft,
                               sites=("qkv.q", "qkv.k", "qkv.v"))
        q = q.reshape(B, T, H, hd)
        k = k.reshape(B, T, Hkv, hd)
        v = v.reshape(B, T, Hkv, hd)
        if rope_theta:
            if mode == "decode":
                positions = _decode_positions(pos, B, T)
            elif vec_off:
                positions = off[:, None] + jnp.arange(T)[None]
            else:
                positions = jnp.broadcast_to(jnp.arange(T) + off, (B, T))
            q = rope(q, positions, rope_theta, cfg.rope_scaling)
            k = rope(k, positions, rope_theta, cfg.rope_scaling)
        q = constrain(q, "batch", "seq", "heads", None)
        k = constrain(k, "batch", "seq", "kv_heads", None)
        v = constrain(v, "batch", "seq", "kv_heads", None)
        new_cache = cache
        if mode == "decode":
            assert cache is not None
            S = cache["k"].shape[1]
            write = (pos % window) if window else pos
            k_all = _cache_write(cache["k"], k, write)
            v_all = _cache_write(cache["v"], v, write)
            new_cache = {"k": k_all, "v": v_all}
            k, v = k_all, v_all
            Tk = S
        elif mode == "prefill":
            assert cache is not None
            batched = lengths is not None or vec_off or off > 0
            if window:
                if batched:
                    new_cache = _window_prefill_write(
                        cache, k, v, off=off, lengths=lengths, window=window)
                    # attend against OLD cache (earlier chunks) + own keys,
                    # masked on per-row absolute positions: a row's pad tail
                    # and other rows' lengths can't leak into its window
                    S_c = cache["k"].shape[1]
                    prev_end = (jnp.clip(jnp.asarray(lengths, jnp.int32),
                                         0, off)
                                if lengths is not None
                                else jnp.full((B,), off, jnp.int32))
                    kabs_cache = _cache_abs_pos(S_c, prev_end - 1, window)
                    g = (off[:, None] + jnp.arange(T)[None] if vec_off
                         else off + jnp.arange(T))
                    valid_new = _prefill_valid(off, T, lengths)
                    if valid_new is None:
                        valid_new = jnp.ones((B, T), bool)
                    kabs_new = jnp.where(valid_new,
                                         g if vec_off else g[None, :], -1)
                    win_kabs = jnp.concatenate([kabs_cache, kabs_new], axis=1)
                    win_qpos = g
                    k = jnp.concatenate([cache["k"], k], axis=1)
                    v = jnp.concatenate([cache["v"], v], axis=1)
                    Tk = S_c + T
                else:
                    # rolling buffer: absolute pos p lives at slot p % window
                    keep = min(T, window)
                    slots = jnp.arange(T - keep, T) % window
                    new_cache = {
                        "k": cache["k"].at[:, slots].set(k[:, T - keep :]),
                        "v": cache["v"].at[:, slots].set(v[:, T - keep :]),
                    }
                    Tk = T
            elif vec_off:
                # token-packed: per-row scatter write, then attend over the
                # FULL cache with a per-row causal mask (masked tail keys
                # contribute exact zeros — bitwise-equal to the slice path)
                bidx = jnp.arange(B)[:, None]
                idx = off[:, None] + jnp.arange(T)[None]  # [B, T] abs pos
                new_cache = {
                    "k": cache["k"].at[bidx, idx].set(k),
                    "v": cache["v"].at[bidx, idx].set(v),
                }
                k, v = new_cache["k"], new_cache["v"]
                Tk = cache["k"].shape[1]
                packed_qpos = idx
            else:
                new_cache = {
                    "k": lax.dynamic_update_slice(cache["k"], k,
                                                  (0, off, 0, 0)),
                    "v": lax.dynamic_update_slice(cache["v"], v,
                                                  (0, off, 0, 0)),
                }
                if off:
                    # chunked: queries attend to every position cached so far
                    k = new_cache["k"][:, : off + T]
                    v = new_cache["v"][:, : off + T]
                    Tk = off + T
                else:
                    Tk = T
        else:
            Tk = T
    else:
        q = dense(p["wq"], h, ft=ft, site="qkv.q").reshape(B, T, H, hd)
        k, v = cross_kv
        Tk = k.shape[1]
        new_cache = cache

    # grouped heads: q [B, Hkv, G, T, hd]; k/v [B, Hkv, S, hd]
    from repro.models.attention_core import (
        attend, attend_decode, attend_prefill_packed, attend_prefill_window)

    G = H // Hkv
    qg = q.reshape(B, T, Hkv, G, hd).transpose(0, 2, 3, 1, 4)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if cross_kv is not None:
        o = attend(qg, kt, vt, kind="full")
    elif mode == "decode":
        abs_pos = _cache_abs_pos(Tk, pos, window)
        o = attend_decode(qg, kt, vt, abs_pos=abs_pos)
    elif mode == "encode":
        o = attend(qg, kt, vt, kind="full")
    elif win_kabs is not None:
        o = attend_prefill_window(qg, kt, vt, qpos=win_qpos,
                                  kabs=win_kabs, window=window)
    elif packed_qpos is not None:
        o = attend_prefill_packed(qg, kt, vt, qpos=packed_qpos)
    else:
        o = attend(qg, kt, vt, kind="window" if window else "causal",
                   window=window, q_off=off)
    out = o.transpose(0, 3, 1, 2, 4).reshape(B, T, H * hd)
    out = dense(p["wo"], out.astype(ACT_DTYPE), ft=ft, site="out.o")
    return constrain(out, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------- MLA attention ----

def init_mla(key, cfg: ModelConfig, max_seq: int):
    m = cfg.mla
    ks = _split(key, 6)
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    p = {
        "norm": init_norm(cfg, cfg.d_model),
        "wkv_a": init_dense(ks[0], cfg.d_model, m.kv_lora_rank + m.qk_rope_head_dim),
        "kv_norm": init_norm(cfg, m.kv_lora_rank),
        "wkv_b": init_dense(
            ks[1], m.kv_lora_rank, cfg.n_heads * (m.qk_nope_head_dim + m.v_head_dim)
        ),
        "wo": init_dense(ks[2], cfg.n_heads * m.v_head_dim, cfg.d_model),
    }
    if m.q_lora_rank:
        p["wq_a"] = init_dense(ks[3], cfg.d_model, m.q_lora_rank)
        p["q_norm"] = init_norm(cfg, m.q_lora_rank)
        p["wq_b"] = init_dense(ks[4], m.q_lora_rank, cfg.n_heads * qk_dim)
    else:
        p["wq"] = init_dense(ks[5], cfg.d_model, cfg.n_heads * qk_dim)
    return p


def init_mla_cache(cfg: ModelConfig, batch: int, max_seq: int):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_seq, m.kv_lora_rank), ACT_DTYPE),
        "krope": jnp.zeros((batch, max_seq, m.qk_rope_head_dim), ACT_DTYPE),
    }


def apply_mla(p, x, *, cfg: ModelConfig, cache=None, pos=None, mode="train",
              lengths=None, ft=None):
    """Multi-head latent attention (DeepSeek). The cache stores ONLY the
    compressed latent c_kv [B, S, r] + shared k_rope — the paper-faithful
    KV-compression; decode up-projects cached latents, or with
    ``cfg.mla_absorb`` scores the latents directly (absorbed W_uk / W_uv).
    YaRN (``cfg.rope_scaling``) scales the rope dims' frequencies and the
    softmax scale by mscale(factor, mscale_all_dim)^2.

    Chunked prefill: ``pos`` (static int) offsets rope positions and the
    latent-cache write; chunk queries attend over all cached latents so
    far. Bucket padding needs no masking here (linear cache + causal mask:
    garbage latents past a row's length are never read by real queries and
    are decode-overwritten before they become visible).

    Token-packed prefill: ``pos`` is a traced per-row offset vector [B];
    cache writes become per-row scatters and queries attend over the full
    latent cache under a per-row causal mask (exact-zero masked terms)."""
    m = cfg.mla
    B, T, D = x.shape
    H = cfg.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    off = _prefill_off(pos, mode)
    vec_off = _is_pos_vector(off)
    h = apply_norm(p["norm"], x, cfg)

    # wq_a (or wq) and wkv_a both project the normed residual stream:
    # one fanout group per step
    if m.q_lora_rank:
        qa, kv = dense_fanout((p["wq_a"], p["wkv_a"]), h, ft=ft,
                              sites=("qkv.q_a", "qkv.kv"))
        q = dense(p["wq_b"], apply_norm(p["q_norm"], qa, cfg),
                  ft=ft, site="qkv.q")
    else:
        q, kv = dense_fanout((p["wq"], p["wkv_a"]), h, ft=ft,
                             sites=("qkv.q", "qkv.kv"))
    q = q.reshape(B, T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    # kv: [B, T, r + dr]
    ckv = apply_norm(p["kv_norm"], kv[..., : m.kv_lora_rank], cfg)
    k_rope_new = kv[..., m.kv_lora_rank :]  # [B, T, dr] shared across heads

    if mode == "decode":
        positions = _decode_positions(pos, B, T)
    elif vec_off:
        positions = off[:, None] + jnp.arange(T)[None]
    else:
        positions = jnp.broadcast_to(jnp.arange(T) + off, (B, T))
    q_rope = rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    k_rope_new = rope(k_rope_new[:, :, None, :], positions, cfg.rope_theta,
                      cfg.rope_scaling)[:, :, 0]

    new_cache = cache
    packed_qpos = None  # set on the token-packed path
    if mode == "decode":
        assert cache is not None
        ckv_all = _cache_write(cache["ckv"], ckv, pos)
        kr_all = _cache_write(cache["krope"], k_rope_new, pos)
        new_cache = {"ckv": ckv_all, "krope": kr_all}
        ckv_s, kr_s = ckv_all, kr_all
        Tk = ckv_all.shape[1]
    elif mode == "prefill" and vec_off:
        assert cache is not None
        bidx = jnp.arange(B)[:, None]
        idx = off[:, None] + jnp.arange(T)[None]
        new_cache = {
            "ckv": cache["ckv"].at[bidx, idx].set(ckv),
            "krope": cache["krope"].at[bidx, idx].set(k_rope_new),
        }
        ckv_s, kr_s = new_cache["ckv"], new_cache["krope"]
        Tk = cache["ckv"].shape[1]
        packed_qpos = idx
    else:
        if mode == "prefill":
            assert cache is not None
            new_cache = {
                "ckv": lax.dynamic_update_slice(cache["ckv"], ckv,
                                                (0, off, 0)),
                "krope": lax.dynamic_update_slice(cache["krope"], k_rope_new,
                                                  (0, off, 0)),
            }
        if mode == "prefill" and off:
            ckv_s = new_cache["ckv"][:, : off + T]
            kr_s = new_cache["krope"][:, : off + T]
            Tk = off + T
        else:
            ckv_s, kr_s = ckv, k_rope_new
            Tk = T

    from repro.models.attention_core import (attend, attend_decode,
                                             attend_prefill_packed)

    scale = 1.0 / math.sqrt(dn + dr)
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2

    if mode == "decode" and cfg.mla_absorb:
        # absorbed projections: fold W_uk into q and W_uv out of the value
        # sum, so attention runs over the r-dim latents themselves and the
        # up-projection happens ONCE per step, not per cached position.
        with jax.named_scope("mla.decode"):
            wb = p["wkv_b"]["w"].reshape(m.kv_lora_rank, H, dn + dv)
            w_uk, w_uv = wb[..., :dn], wb[..., dn:]
            q_eff = jnp.einsum("bthd,rhd->bthr", q_nope.astype(jnp.float32),
                               w_uk.astype(jnp.float32))
            s_nope = jnp.einsum("bthr,bsr->bhts", q_eff,
                                ckv_s.astype(jnp.float32))
            s_rope = jnp.einsum("bthd,bsd->bhts", q_rope.astype(jnp.float32),
                                kr_s.astype(jnp.float32))
            s = (s_nope + s_rope) * scale
            slot = jnp.arange(Tk)
            if _is_pos_vector(pos):  # per-row positions: mask [B, S]
                ok = slot[None, :] <= jnp.asarray(pos)[:, None]
                s = jnp.where(ok[:, None, None, :], s, -1e30)
            else:
                s = jnp.where((slot <= pos)[None, None, None], s, -1e30)
            probs = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bhts,bsr->bthr", probs,
                             ckv_s.astype(jnp.float32))
            o = jnp.einsum("bthr,rhd->bthd", ctx, w_uv.astype(jnp.float32))
        out = dense(p["wo"], o.reshape(B, T, H * dv).astype(ACT_DTYPE),
                    ft=ft, site="out.o")
        return constrain(out, "batch", "seq", "embed"), new_cache

    # up-project latents to per-head K_nope and V (paper-faithful/naive path)
    kvb = dense(p["wkv_b"], ckv_s).reshape(B, Tk, H, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    k_nope = constrain(k_nope, "batch", "seq", "heads", None)

    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_s[:, :, None, :], (B, Tk, H, dr))], axis=-1
    )
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    qg = q_full.transpose(0, 2, 1, 3)[:, :, None]  # [B, H, 1, T, dk]
    kt = k_full.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if mode == "decode":
        o = attend_decode(qg, kt, vt, abs_pos=_cache_abs_pos(Tk, pos, 0),
                          scale=scale)
    elif packed_qpos is not None:
        o = attend_prefill_packed(qg, kt, vt, qpos=packed_qpos, scale=scale)
    else:
        o = attend(qg, kt, vt, kind="causal", scale=scale, q_off=off)
    out = o[:, :, 0].transpose(0, 2, 1, 3).reshape(B, T, H * dv)
    out = dense(p["wo"], out.astype(ACT_DTYPE), ft=ft, site="out.o")
    return constrain(out, "batch", "seq", "embed"), new_cache


# ------------------------------------------------------------------- MLP ----

def _mlp_gated(cfg: ModelConfig, gated_default: bool) -> bool:
    return gated_default if cfg.mlp_gated is None else cfg.mlp_gated


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None, gated=True):
    d_ff = d_ff or cfg.d_ff
    ks = _split(key, 3)
    if _mlp_gated(cfg, gated):
        return {
            "norm": init_norm(cfg, cfg.d_model),
            "gate": init_dense(ks[0], cfg.d_model, d_ff),
            "up": init_dense(ks[1], cfg.d_model, d_ff),
            "down": init_dense(ks[2], d_ff, cfg.d_model),
        }
    return {
        "norm": init_norm(cfg, cfg.d_model),
        "up": init_dense(ks[0], cfg.d_model, d_ff),
        "down": init_dense(ks[1], d_ff, cfg.d_model),
    }


def _mlp_act(cfg: ModelConfig, a):
    if cfg.mlp_act == "relu2":
        return jnp.square(jax.nn.relu(a))
    if cfg.mlp_act == "gelu":
        return jax.nn.gelu(a)
    return jax.nn.silu(a)


def apply_mlp(p, x, *, cfg: ModelConfig, ft=None):
    h = apply_norm(p["norm"], x, cfg)
    if "gate" in p:
        # gate/up share the normed input: one fanout group
        gate, up = dense_fanout((p["gate"], p["up"]), h, ft=ft,
                                sites=("mlp.gate", "mlp.up"))
        a = _mlp_act(cfg, gate) * up
    else:
        up = dense(p["up"], h, ft=ft, site="mlp.up")
        a = _mlp_act(cfg, up) if cfg.norm_kind != "layernorm" \
            else jax.nn.gelu(up)
    a = constrain(a, "batch", "seq", "mlp")
    return constrain(dense(p["down"], a, ft=ft, site="mlp.down"),
                     "batch", "seq", "embed")


# ------------------------------------------------------------------- MoE ----

def moe_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    """Rows of each held expert's buffer for a call of ``n_tokens`` tokens
    (per dispatch group): the capacity-factor share, at most ``n_tokens``
    — a token picks an expert at most once, so that ceiling (reached at
    ``capacity_factor >= n_experts / top_k``) drops nothing at any skew."""
    mc = cfg.moe
    c = int(math.ceil(n_tokens * mc.top_k / mc.n_experts * mc.capacity_factor))
    c = min(c, n_tokens)  # dropless ceiling
    return max(8, -(-c // 8) * 8)  # round up to 8


def init_moe(key, cfg: ModelConfig):
    """Router over all ``n_experts``; expert weights only for the held
    share (``MoEConfig.held`` of them)."""
    mc = cfg.moe
    ks = _split(key, 5)
    Eh = mc.held
    p = {
        "norm": init_norm(cfg, cfg.d_model),
        "router": _he(ks[0], (cfg.d_model, mc.n_experts)),
        "we_gate": _he(ks[1], (Eh, cfg.d_model, mc.d_ff_expert), cfg.d_model),
        "we_up": _he(ks[2], (Eh, cfg.d_model, mc.d_ff_expert), cfg.d_model),
        "we_down": _he(ks[3], (Eh, mc.d_ff_expert, cfg.d_model), mc.d_ff_expert),
    }
    if mc.n_shared:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=mc.n_shared * mc.d_ff_expert)
        del p["shared"]["norm"]  # shares the block's norm
    return p


def apply_moe(p, x, *, cfg: ModelConfig, valid=None, ft=None):
    """Grouped sort-based dispatch (EP): tokens are routed SHARD-LOCALLY per
    data-parallel group (leading G axis = number of 'batch' shards), so the
    argsort/scatter never crosses devices; the only cross-device movement is
    the capacity-bounded [G, E, C, D] buffer resharding (data <-> expert
    owners) — GSPMD lowers it to the canonical EP all-to-all. §Perf iteration
    1: replaces a global argsort whose GSPMD lowering all-gathered the full
    [N, D] activations.

    dispatch='global_sort' keeps the pre-iteration path for A/B.

    Expert share (``MoEConfig.n_held``): the router scores all
    ``n_experts`` and picks the top-k among them; only assignments to the
    held experts ``first_held ..`` are dispatched and computed here, the
    rest go to a virtual out-of-range expert and contribute nothing. The
    shared experts are computed in full. A ``capacity_factor`` of at least
    ``n_experts / top_k`` sizes every held expert's buffer to the tokens
    of the call, so no assignment is ever dropped whatever the skew.

    ``valid`` [B, T] (bucketed batched prefill) routes pad tokens to the
    same virtual expert so they can never STEAL capacity slots from real
    prompt tokens; their own outputs are garbage and discarded by the
    caller. (Capacity-factor dropping itself still depends on the batch
    composition, so MoE batched serving is exact only modulo drops unless
    dropless — the same caveat as any capacity-bounded MoE engine.)"""
    from repro.dist.sharding import axis_extent

    mc = cfg.moe
    B, T, D = x.shape
    N, K = B * T, mc.top_k
    E = mc.held  # dispatch buffers cover the held experts; E = virtual
    h = apply_norm(p["norm"], x, cfg)
    hf = h.reshape(N, D)

    G = axis_extent("batch") if getattr(mc, "dispatch", "grouped") == "grouped" else 1
    if N % G:
        G = 1
    n_loc = N // G
    hg = constrain(hf.reshape(G, n_loc, D), "batch", None, None)

    with jax.named_scope("moe.route"):
        # router in bf16 with f32 accumulation: avoids materializing an
        # f32 copy of the full [N, D] activations (§Perf iteration 4)
        if ft is not None and ft.protects("mlp.router"):
            # MoE routing decisions steer EVERY expert GEMM downstream —
            # protecting this small projection makes routing itself
            # fail-stop recoverable, so a failed group cannot silently
            # reroute tokens
            rw = ((p["router_q8"]["w"], p["router_q8"]["scale"])
                  if "router_q8" in p else p["router"])
            logits = ft.matmul("mlp.router", hg, rw)
        else:
            logits = jnp.einsum("gnd,de->gne", hg,
                                p["router"].astype(ACT_DTYPE),
                                preferred_element_type=jnp.float32)
        if mc.gating == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
        vals, idx = lax.top_k(probs, K)  # [G, n_loc, K]
        if mc.norm_topk_prob:
            weights = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-9)
        else:
            weights = vals
        if mc.n_held:  # experts held elsewhere -> virtual expert
            idx = idx - mc.first_held
            idx = jnp.where((idx >= 0) & (idx < E), idx, E)
        if valid is not None:
            vg = valid.reshape(G, n_loc)
            idx = jnp.where(vg[..., None], idx, E)  # pad tokens -> virtual

    with jax.named_scope("moe.dispatch"):
        C = moe_capacity(n_loc, cfg)
        A = n_loc * K  # assignments per group
        e_flat = idx.reshape(G, A)
        w_flat = weights.reshape(G, A)
        order = jnp.argsort(e_flat, axis=-1)  # stable: within-expert order = token order
        e_s = jnp.take_along_axis(e_flat, order, axis=-1)
        starts = jax.vmap(
            lambda es: jnp.searchsorted(es, jnp.arange(E + 1), side="left"))(e_s)

        # GATHER-based capacity dispatch (§Perf iteration 3): buffer slot
        # p = e*C + r pulls sorted-assignment starts[e]+r — no forward
        # scatter (XLA's scatter expander materializes target-shaped
        # index grids).
        eidx = jnp.arange(E * C) // C
        ridx = jnp.arange(E * C) % C
        src = jnp.take_along_axis(starts, eidx[None].repeat(G, 0), axis=1) + ridx
        # slot occupancy mask — deliberately NOT named `valid`: that's the
        # [B, T] token-validity parameter, still live below
        slot_ok = src < jnp.take_along_axis(starts, eidx[None].repeat(G, 0) + 1,
                                            axis=1)
        src = jnp.minimum(src, A - 1)
        src_assign = jnp.take_along_axis(order, src, axis=1)  # [G, E*C] assignment id
        src_tok = src_assign // K
        rows = jnp.take_along_axis(hg, src_tok[..., None], axis=1)  # [G, E*C, D]
        rows = constrain(rows, "batch", None, None)
        expert_in = jnp.where(slot_ok[..., None], rows, 0).reshape(G, E, C, D)
        # the EP boundary: data-sharded groups -> expert-sharded buffers
        expert_in = constrain(expert_in, "batch", "experts", None, None)
    if ft is not None and ft.protects("moe.gate"):
        # the per-expert batched GEMMs — the last big unprotected FLOPs of
        # the MoE block — run through the GROUPED entangled kernel: one
        # call per projection covers all E experts, rows round-robin onto
        # the M streams within each expert, fail-stop rolled forward
        # per-expert in-kernel. Startup-quantized q8 stacks (per-expert
        # grids) are used when prepare_params installed them.
        def _we(name):
            q = p.get(name + "_q8")
            return (q["w"], q["scale"]) if q is not None else p[name]

        a = jax.nn.silu(
            ft.matmul_grouped("moe.gate", expert_in, _we("we_gate"))
        ).astype(ACT_DTYPE) * ft.matmul_grouped(
            "moe.up", expert_in, _we("we_up")).astype(ACT_DTYPE)
        out_e = ft.matmul_grouped("moe.down", a,
                                  _we("we_down")).astype(ACT_DTYPE)
    else:
        a = jax.nn.silu(
            jnp.einsum("gecd,edf->gecf", expert_in,
                       p["we_gate"].astype(ACT_DTYPE))
        ) * jnp.einsum("gecd,edf->gecf", expert_in,
                       p["we_up"].astype(ACT_DTYPE))
        out_e = jnp.einsum("gecf,efd->gecd", a,
                           p["we_down"].astype(ACT_DTYPE))

    with jax.named_scope("moe.combine"):
        out_e = constrain(out_e, "batch", "experts", None, None)
        h_flat = constrain(out_e.reshape(G, E * C, D), "batch", None, None)
        # combine, also gather-based: assignment (t, k) sits at sorted
        # position inv_order, rank within its expert = pos - starts[e],
        # slot = e*C + rank
        inv_order = jnp.argsort(order, axis=-1)  # [G, A]
        rank = inv_order - jnp.take_along_axis(starts, e_flat, axis=1)
        keep = rank < C
        if valid is not None or mc.n_held:
            keep &= e_flat < E  # virtual-expert assignments contribute 0
        slot = jnp.minimum(e_flat * C + rank, E * C - 1)
        hsel = jnp.take_along_axis(h_flat, slot[..., None], axis=1)  # [G, A, D]
        hsel = constrain(hsel, "batch", None, None)
        contrib = jnp.where(keep[..., None],
                            w_flat[..., None].astype(ACT_DTYPE) * hsel, 0)
        out = contrib.reshape(G, n_loc, K, D).sum(axis=2)
        out = constrain(out, "batch", None, None).reshape(N, D)

    if mc.n_shared:
        sp = dict(p["shared"])
        g_s, u_s = dense_fanout((sp["gate"], sp["up"]), hf, ft=ft,
                                sites=("mlp.gate", "mlp.up"))
        a = jax.nn.silu(g_s) * u_s
        out = out + dense(sp["down"], a, ft=ft, site="mlp.down")
    return constrain(out.reshape(B, T, D), "batch", "seq", "embed")


# ----------------------------------------------------------------- Mamba ----

def _mamba_dims(cfg: ModelConfig):
    sc = cfg.ssm
    d_inner = sc.expand * cfg.d_model
    dt_rank = sc.dt_rank or -(-cfg.d_model // 16)
    return d_inner, dt_rank


def init_mamba(key, cfg: ModelConfig, max_seq: int):
    sc = cfg.ssm
    di, dtr = _mamba_dims(cfg)
    ks = _split(key, 6)
    return {
        "norm": init_norm(cfg, cfg.d_model),
        "in_proj": init_dense(ks[0], cfg.d_model, 2 * di),
        "conv_w": _he(ks[1], (di, sc.d_conv), sc.d_conv),
        "conv_b": jnp.zeros((di,)),
        "x_proj": init_dense(ks[2], di, dtr + 2 * sc.d_state),
        "dt_proj": {
            "w": _he(ks[3], (dtr, di)),
            "b": jnp.zeros((di,)) + jnp.log(jnp.expm1(jnp.float32(0.01))),
        },
        "A_log": jnp.log(
            jnp.broadcast_to(jnp.arange(1, sc.d_state + 1, dtype=jnp.float32), (di, sc.d_state))
        ),
        "D_skip": jnp.ones((di,)),
        "out_proj": init_dense(ks[4], di, cfg.d_model),
    }


def init_mamba_cache(cfg: ModelConfig, batch: int, max_seq: int):
    sc = cfg.ssm
    di, _ = _mamba_dims(cfg)
    return {
        "conv": jnp.zeros((batch, sc.d_conv - 1, di), ACT_DTYPE),
        "h": jnp.zeros((batch, di, sc.d_state), jnp.float32),
    }


def apply_mamba(p, x, *, cfg: ModelConfig, cache=None, pos=None, mode="train",
                lengths=None, ft=None):
    """Mamba-1: GEMMs hoisted out of the recurrence; the selective scan runs
    as lax.scan over time (compile-compact; per-step work is elementwise).

    Bucketed/chunked prefill: ``pos`` (static int) is the chunk offset —
    the depthwise conv is seeded from the cached tail of the previous chunk
    — and ``lengths`` [B] gates the recurrence per row, so a bucket-padded
    prompt's pad tail can NEVER leak into the carried state (recurrent
    state, unlike a causally masked KV cache, would otherwise absorb every
    pad token)."""
    sc = cfg.ssm
    B, T, D = x.shape
    di, dtr = _mamba_dims(cfg)
    off = _prefill_off(pos, mode)
    h_in = apply_norm(p["norm"], x, cfg)
    # in_proj is Mamba's QKV analog (the block's big input projection)
    xz = dense(p["in_proj"], h_in, ft=ft, site="qkv.in")
    xs, z = xz[..., :di], xz[..., di:]
    xs = constrain(xs, "batch", "seq", "mlp")

    # depthwise causal conv over time
    new_conv_state = None
    if mode == "decode":
        window = jnp.concatenate([cache["conv"], xs], axis=1)  # [B, d_conv, di]
        new_conv_state = window[:, 1:]
        conv = jnp.einsum("bkd,dk->bd", window.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32))[:, None]
    else:
        # chunk > 0: the conv context is the previous chunk's cached tail
        # (token-packed rows at offset 0 read a zeroed cache row — bitwise
        # identical to the fresh-state branch)
        pad = (cache["conv"].astype(xs.dtype) if _off_any(off)
               else jnp.zeros((B, sc.d_conv - 1, di), xs.dtype))
        xp = jnp.concatenate([pad, xs], axis=1)
        conv = sum(
            xp[:, j : j + T].astype(jnp.float32)
            * p["conv_w"][:, j].astype(jnp.float32)
            for j in range(sc.d_conv)
        )
        if mode == "prefill":
            if lengths is not None or _off_any(off):
                new_conv_state = _conv_tail_state(xp, off, T, lengths,
                                                  sc.d_conv)
            else:
                new_conv_state = xp[:, -(sc.d_conv - 1) :].astype(ACT_DTYPE)
    u = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))  # [B, T, di] f32

    proj = dense(p["x_proj"], u.astype(ACT_DTYPE)).astype(jnp.float32)
    dt = jax.nn.softplus(
        jnp.einsum("btr,rd->btd", proj[..., :dtr], p["dt_proj"]["w"].astype(jnp.float32))
        + p["dt_proj"]["b"]
    )
    Bc = proj[..., dtr : dtr + sc.d_state]  # [B, T, S]
    Cc = proj[..., dtr + sc.d_state :]
    A = -jnp.exp(p["A_log"])  # [di, S]

    h0 = cache["h"] if cache is not None else jnp.zeros((B, di, sc.d_state), jnp.float32)

    # Selective scan, chunked: the [B, T, di, S] discretized operands are
    # NEVER materialized over full T (17 TB/device at train_4k for 7B) —
    # da/db are formed per step inside the scan; chunk bodies are
    # checkpointed so backward stores only T/Q chunk-boundary states.
    # Bucketed prefill gates the state update per row/step (pad steps are
    # identities on h), keeping padded rows' carried state exact.
    valid_tb = (_prefill_valid(off, T, lengths, time_major=True)
                if mode == "prefill" else None)

    def step(h, inputs):
        if valid_tb is None:
            dt_t, b_t, c_t, u_t = inputs  # [B, di], [B, S], [B, S], [B, di]
        else:
            dt_t, b_t, c_t, u_t, v_t = inputs
        da_t = jnp.exp(dt_t[..., None] * A)  # [B, di, S]
        db_t = (dt_t * u_t)[..., None] * b_t[:, None, :]
        h_new = da_t * h + db_t
        h = h_new if valid_tb is None else jnp.where(v_t[:, None, None],
                                                     h_new, h)
        y = jnp.einsum("bds,bs->bd", h, c_t)
        return h, y

    xs = (
        dt.swapaxes(0, 1),  # [T, B, di]
        Bc.swapaxes(0, 1),  # [T, B, S]
        Cc.swapaxes(0, 1),
        u.swapaxes(0, 1),  # [T, B, di]
    )
    if valid_tb is not None:
        xs = xs + (valid_tb,)
    Q = 64  # chunk length
    if T % Q == 0 and T > Q:
        chunked = jax.tree.map(lambda a: a.reshape(T // Q, Q, *a.shape[1:]), xs)

        def chunk_body(h, chunk_xs):
            return lax.scan(step, h, chunk_xs)

        if mode == "train":
            chunk_body = jax.checkpoint(chunk_body)
        hT, ys = lax.scan(chunk_body, h0, chunked)
        ys = ys.reshape(T, B, di)
    else:
        hT, ys = lax.scan(step, h0, xs)
    y = ys.swapaxes(0, 1) + u * p["D_skip"].astype(jnp.float32)  # [B, T, di]
    y = y.astype(ACT_DTYPE) * jax.nn.silu(z)
    out = dense(p["out_proj"], y, ft=ft, site="out.o")
    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"conv": new_conv_state.astype(ACT_DTYPE), "h": hT}
    return constrain(out, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------------- RG-LRU ----

def init_rglru(key, cfg: ModelConfig, max_seq: int):
    rc = cfg.rglru
    w = rc.lru_width or cfg.d_model
    ks = _split(key, 7)
    return {
        "norm": init_norm(cfg, cfg.d_model),
        "in_x": init_dense(ks[0], cfg.d_model, w),
        "in_gate": init_dense(ks[1], cfg.d_model, w),
        "conv_w": _he(ks[2], (w, rc.d_conv), rc.d_conv),
        "conv_b": jnp.zeros((w,)),
        "w_a": init_dense(ks[3], w, w, bias=True),
        "w_i": init_dense(ks[4], w, w, bias=True),
        "lam": jnp.full((w,), 4.0),  # a = sigmoid(lam)^(c*r): init near 0.98^c
        "out": init_dense(ks[5], w, cfg.d_model),
    }


def init_rglru_cache(cfg: ModelConfig, batch: int, max_seq: int):
    rc = cfg.rglru
    w = rc.lru_width or cfg.d_model
    return {
        "conv": jnp.zeros((batch, rc.d_conv - 1, w), ACT_DTYPE),
        "h": jnp.zeros((batch, w), jnp.float32),
    }


def apply_rglru(p, x, *, cfg: ModelConfig, cache=None, pos=None, mode="train",
                lengths=None, ft=None):
    """RG-LRU block. Bucketed/chunked prefill mirrors :func:`apply_mamba`:
    ``pos`` (static int) seeds the conv from the previous chunk's cached
    tail, ``lengths`` gates the recurrence so pad steps hold the state."""
    rc = cfg.rglru
    B, T, D = x.shape
    w = rc.lru_width or cfg.d_model
    off = _prefill_off(pos, mode)
    h_in = apply_norm(p["norm"], x, cfg)
    # in_x / in_gate are the RG-LRU block's QKV-analog input projections;
    # they share h_in, so a protected run fans them out as one group
    gate_p, u = dense_fanout((p["in_gate"], p["in_x"]), h_in, ft=ft,
                             sites=("qkv.gate", "qkv.in"))
    gate = jax.nn.gelu(gate_p)

    new_conv_state = None
    if mode == "decode":
        windowv = jnp.concatenate([cache["conv"], u], axis=1)
        new_conv_state = windowv[:, 1:]
        u = jnp.einsum(
            "bkd,dk->bd", windowv.astype(jnp.float32), p["conv_w"].astype(jnp.float32)
        )[:, None] + p["conv_b"].astype(jnp.float32)
    else:
        pad = (cache["conv"].astype(u.dtype) if _off_any(off)
               else jnp.zeros((B, rc.d_conv - 1, w), u.dtype))
        up = jnp.concatenate([pad, u], axis=1)
        if mode == "prefill":
            if lengths is not None or _off_any(off):
                new_conv_state = _conv_tail_state(up, off, T, lengths,
                                                  rc.d_conv)
            else:
                new_conv_state = up[:, -(rc.d_conv - 1) :].astype(ACT_DTYPE)
        u = sum(
            up[:, j : j + T].astype(jnp.float32) * p["conv_w"][:, j].astype(jnp.float32)
            for j in range(rc.d_conv)
        ) + p["conv_b"].astype(jnp.float32)
    u = u.astype(ACT_DTYPE)

    r = jax.nn.sigmoid(dense(p["w_a"], u).astype(jnp.float32))
    i = jax.nn.sigmoid(dense(p["w_i"], u).astype(jnp.float32))
    log_a_base = jax.nn.log_sigmoid(p["lam"].astype(jnp.float32))  # [w]
    log_a = rc.c * r * log_a_base  # [B, T, w]
    a = jnp.exp(log_a)
    gated_x = i * u.astype(jnp.float32)
    inp = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * gated_x

    h0 = cache["h"] if cache is not None else jnp.zeros((B, w), jnp.float32)

    valid_tb = (_prefill_valid(off, T, lengths, time_major=True)
                if mode == "prefill" else None)

    def step(h, ab):
        if valid_tb is None:
            a_t, x_t = ab
            h = a_t * h + x_t
        else:
            a_t, x_t, v_t = ab
            h = jnp.where(v_t[:, None], a_t * h + x_t, h)
        return h, h

    scan_xs = (a.swapaxes(0, 1), inp.swapaxes(0, 1))
    if valid_tb is not None:
        scan_xs = scan_xs + (valid_tb,)
    hT, hs = lax.scan(step, h0, scan_xs)
    rec = hs.swapaxes(0, 1).astype(ACT_DTYPE)  # [B, T, w]
    out = dense(p["out"], rec * gate, ft=ft, site="out.o")
    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"conv": new_conv_state, "h": hT}
    return constrain(out, "batch", "seq", "embed"), new_cache
