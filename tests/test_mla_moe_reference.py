"""DeepSeek-V2-Lite's program path against the benchmark's plain reference
(``bench/models/mla_moe.py``) at a small size on the CPU, with seeded
random weights: latent attention with YaRN rope, a router over all experts
of which this chip holds a share, shared experts, and the dense first
layer.

* token-packed prefill, then decode through the latent cache (absorbed),
  gives the reference's logits, unprotected and with every GEMM entangled;
* the served engine's tokens lie at the reference's best logit;
* the expert shares add up to the uncut layer, and a skewed router drops
  nothing;
* YaRN's frequencies follow the closed form, and an unscaled ``rope`` is
  the plain one bit for bit.
"""
import dataclasses
import functools
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.models import mla_moe  # noqa: E402
from repro.configs import RopeScaling, get_smoke_config  # noqa: E402
from repro.core.plan import make_plan  # noqa: E402
from repro.ft import FTContext, PlanRegistry  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.serve.engine import Request, ServeConfig, ServeEngine  # noqa: E402

KEY = jax.random.PRNGKey(11)

# the benchmark configuration's keys at the program's smoke widths: 16
# routed experts of which experts 4-7 are held, top-4, 2 shared, YaRN over
# an original context of 16 positions (so the ramp spans the rope dims)
SMALL = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_size": 64, "intermediate_size": 160, "kv_lora_rank": 32,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "router_experts": 16, "first_held_expert": 4, "n_shared_experts": 2,
    "norm_topk_prob": False, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "rope_theta": 10000, "scoring_func": "softmax",
    "tie_word_embeddings": False, "vocab_size": 256,
    "mla_decode": "absorbed",
    "entanglement": {"M": 4, "w": 32, "l": 8, "k": 8,
                     "max_output": 8323072},
}


def _base():
    """The program's smoke DeepSeek-V2-Lite at SMALL's settings."""
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    rs = SMALL["rope_scaling"]
    return dataclasses.replace(
        cfg, rope_scaling=RopeScaling(
            factor=rs["factor"],
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        moe=dataclasses.replace(cfg.moe, n_experts=16, top_k=4, n_shared=2,
                                norm_topk_prob=False))


@functools.lru_cache(maxsize=None)
def _setup():
    cfg = mla_moe.program_config(_base(), SMALL)
    params = jax.jit(functools.partial(mla_moe.init_params, c=SMALL))(KEY)
    return cfg, params


@functools.lru_cache(maxsize=None)
def _reference(mode):
    cfg, params = _setup()
    return jax.jit(lambda t: mla_moe.reference_logits(params, t, SMALL,
                                                      mode))


def test_program_config_and_layout():
    """The cut config holds 4 of 16 experts from expert 4, dropless, serves
    absorbed decode; the reference's weights have the program's layout."""
    cfg, params = _setup()
    assert (cfg.moe.n_experts, cfg.moe.held, cfg.moe.first_held) == (16, 4, 4)
    assert cfg.mla_absorb
    assert L.moe_capacity(64, cfg) == 64  # dropless: a call's tokens
    assert not cfg.moe.norm_topk_prob
    want = jax.eval_shape(lambda k: get_model(cfg).init(k, cfg, 64), KEY)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all(a.shape == b.shape for a, b in
               zip(jax.tree.leaves(want), jax.tree.leaves(got)))
    with pytest.raises(ValueError, match="norm_topk_prob"):
        mla_moe.program_config(
            dataclasses.replace(_base(), moe=dataclasses.replace(
                _base().moe, norm_topk_prob=True)), SMALL)


def _packed_then_decode(ft):
    """Logits of two requests served through the token-packed prefill
    (chunks of 16 at per-row offsets, the second request one chunk long)
    and then 6 decode steps each through the latent cache at per-row
    positions, teacher-forced with the sequences' own tokens."""
    cfg, params = _setup()
    model = get_model(cfg)
    rng = np.random.default_rng(5)
    lens, C, steps = (20, 11), 16, 6
    seqs = [rng.integers(0, cfg.vocab_size, n + steps).astype(np.int32)
            for n in lens]
    cache = model.init_cache(cfg, 2, 64)
    pre = jax.jit(lambda p, t, c, o: model.prefill_packed(
        p, t, cfg, c, pos0=o, lengths=jnp.asarray(lens), ft=ft))
    last = [None, None]
    for off in (0, C):
        tok = np.zeros((2, C), np.int32)
        for r, n in enumerate(lens):
            chunk = seqs[r][off:min(off + C, n)]
            tok[r, :len(chunk)] = chunk
        h, cache = pre(params, jnp.asarray(tok), cache,
                       jnp.asarray([off, off], np.int32))
        for r, n in enumerate(lens):
            if off <= n - 1 < off + C:
                last[r] = h[r, n - 1 - off]
    got = [model.head_project(params, jnp.stack(last), cfg)]
    dec = jax.jit(lambda p, t, c, pos: model.decode_hidden(p, t, c, pos, cfg,
                                                           ft=ft))
    for j in range(steps - 1):
        tok = jnp.asarray([[s[n + j]] for s, n in zip(seqs, lens)])
        pos = jnp.asarray([n + j for n in lens], np.int32)
        h, cache = dec(params, tok, cache, pos)
        got.append(model.head_project(params, h, cfg))
    return seqs, lens, np.stack([np.asarray(g) for g in got], axis=1)


@pytest.mark.parametrize("ft_mode", ["none", "entangle"])
def test_packed_prefill_then_decode_matches_reference(ft_mode):
    """Prefill then decode through the cache gives the reference's full
    forward pass. The program holds activations in bfloat16; the
    reference in ``ft`` mode rounds where it does (widest logit error
    read 0.0094), in ``float`` mode it does not (0.0154, bfloat16 rounding
    through three layers). The limit 0.022 lies between those and the
    controls one precision step lower (0.032 with int8 grids everywhere,
    0.38 with int4 protected grids), which must miss it."""
    ft = None
    mode = "float"
    if ft_mode == "entangle":
        ft = FTContext(registry=PlanRegistry(make_plan(4)), scope="all")
        mode = "ft"
    seqs, lens, logits = _packed_then_decode(ft)
    worst = {}
    for m in (mode, f"{mode}_control"):
        ref = _reference(m)
        worst[m] = max(
            float(np.max(np.abs(
                logits[r] - np.asarray(ref(jnp.asarray(s)))
                [n - 1:n - 1 + logits.shape[1]])))
            for r, (s, n) in enumerate(zip(seqs, lens)))
    assert worst[mode] < 0.022, worst
    assert worst[f"{mode}_control"] > 0.022, worst


@pytest.mark.parametrize("ft_mode", ["none", "entangle"])
def test_served_tokens_at_reference_best(ft_mode):
    """The engine (token-packed admission, absorbed decode, slot reuse)
    serves tokens whose reference logit lies within 0.022 of the
    reference's best at every position, the logits' own limit above (read:
    0 unprotected, 0.0007 entangled): random weights put near-ties within
    bfloat16 rounding of each other, so the argmax itself may differ."""
    cfg, params = _setup()
    scfg = ServeConfig(max_batch=4, max_seq=64, ft_mode=ft_mode,
                       ft_scope="all", prefill_chunk=16, token_budget=32)
    eng = ServeEngine(cfg, scfg, params)
    rng = np.random.default_rng(9)
    reqs = [Request(rid=i, max_new=6 + i % 3, prompt=rng.integers(
        0, cfg.vocab_size, n).astype(np.int32))
        for i, n in enumerate((5, 20, 33, 17, 9, 40))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_steps=500)
    ref = _reference("ft" if ft_mode == "entangle" else "float")
    gaps = []
    for r in reqs:
        assert r.status == "done"
        seq = np.zeros(64, np.int32)
        full = np.concatenate([r.prompt, r.out[:-1]])
        seq[:len(full)] = full
        lg = np.asarray(ref(jnp.asarray(seq)))
        pos = len(r.prompt) - 1 + np.arange(len(r.out))
        gaps.append(lg[pos].max(-1) - lg[pos, r.out])
    assert float(np.max(np.concatenate(gaps))) < 0.022
    m = eng.metrics
    assert m["moe_experts_held"] == 4
    assert m["moe_grouped_sites"] == (6 if ft_mode == "entangle" else 0)


def _moe_layer(n_held=0, first_held=0, capacity_factor=4.0, shared=True):
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    moe = dataclasses.replace(cfg.moe, n_experts=16, top_k=4, n_shared=2,
                              norm_topk_prob=False, n_held=n_held,
                              first_held=first_held,
                              capacity_factor=capacity_factor)
    if not shared:
        moe = dataclasses.replace(moe, n_shared=0)
    return dataclasses.replace(cfg, moe=moe)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of 4 experts each, each routing over all 16 and
    computing its own experts' part, summed with the shared experts
    counted once, give the uncut layer (bfloat16 outputs: 2e-2)."""
    full_cfg = _moe_layer()
    p = L.init_moe(jax.random.PRNGKey(3), full_cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 64))
    full = L.apply_moe(p, x, cfg=full_cfg)
    zero = dict(p, we_down=jnp.zeros_like(p["we_down"]))
    shared = L.apply_moe(zero, x, cfg=full_cfg)  # routed part exactly 0
    total = shared.astype(jnp.float32)
    for s in range(4):
        cfg_s = _moe_layer(n_held=4, first_held=4 * s, shared=False)
        p_s = {k: (v[4 * s:4 * s + 4] if k.startswith("we_") else v)
               for k, v in p.items() if k != "shared"}
        total = total + L.apply_moe(p_s, x, cfg=cfg_s).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(full, np.float32), atol=2e-2)
    # and a share is not the whole: dropping one share moves the result
    assert float(jnp.max(jnp.abs(total - full))) < float(
        jnp.max(jnp.abs(shared - full)))


def _dense_routed(p, x, cfg):
    """Every token through every held expert it picked, weighted: the
    layer with no capacity at all (no shared experts)."""
    mc = cfg.moe
    h = L.apply_norm(p["norm"], x, cfg).astype(jnp.float32)
    probs = jax.nn.softmax(h @ p["router"], axis=-1)
    vals, idx = jax.lax.top_k(probs, mc.top_k)
    held = mc.first_held + jnp.arange(mc.held)
    w = jnp.sum(jnp.where(idx[..., None] == held, vals[..., None], 0.0), -2)
    g = jnp.einsum("btd,edf->btef", h, p["we_gate"])
    u = jnp.einsum("btd,edf->btef", h, p["we_up"])
    y = jnp.einsum("btef,efd->bted", jax.nn.silu(g) * u, p["we_down"])
    return jnp.einsum("bte,bted->btd", w, y)


def test_skewed_router_drops_nothing():
    """A router biased so that every token picks experts 4-7 (all held
    here): 24 tokens each land on the same 4 experts, and the dropless
    layer computes every assignment, where the capacity-factor layer of
    the same share (capacity 8 a expert) drops most of them."""
    cfg = _moe_layer(n_held=4, first_held=4, shared=False)
    p = L.init_moe(jax.random.PRNGKey(3), cfg)
    # 24 tokens near one direction, all routed alike
    x = (jax.random.normal(jax.random.PRNGKey(4), (64,))
         + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (2, 12, 64)))
    h = L.apply_norm(p["norm"], x, cfg).astype(jnp.float32)
    d = jnp.mean(h, axis=(0, 1))
    d = d / jnp.linalg.norm(d)
    bias = jnp.zeros((64, 16)).at[:, 4:8].set(
        d[:, None] * jnp.asarray([8.0, 7.0, 6.0, 5.0]))
    p = dict(p, router=0.01 * p["router"] + bias)
    probs = jax.nn.softmax(h @ p["router"], axis=-1)
    picks = jax.lax.top_k(probs, 4)[1]
    assert set(np.unique(np.asarray(picks))) == {4, 5, 6, 7}
    want = _dense_routed(p, x, cfg)
    got = L.apply_moe(p, x, cfg=cfg)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2, rtol=3e-2)
    drop = L.apply_moe(p, x, cfg=_moe_layer(n_held=4, first_held=4,
                                            capacity_factor=1.25,
                                            shared=False))
    assert float(jnp.max(jnp.abs(drop.astype(jnp.float32) - want))) > 0.1


def test_yarn_frequencies_follow_the_closed_form():
    """DeepSeek-V2's YaRN at its published settings (64 rope dims, factor
    40 over 4,096 positions): correction range dims 10 to 23, the first
    ten dims keep their frequency, the last eight are divided by 40, and
    the softmax scale gains mscale(40, 0.707)^2 = 1.590."""
    rs = RopeScaling(factor=40.0, original_max_position_embeddings=4096,
                     beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                     mscale_all_dim=0.707)
    assert L._yarn_correction_range(rs, 64, 1e4) == (10, 23)
    got = np.asarray(L.rope_freqs(32, 1e4, rs))
    i = np.arange(32)
    base = 1.0 / 1e4 ** (2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    assert abs(L.yarn_mscale(40, 0.707) ** 2 - 1.590) < 1e-3
    c = dict(SMALL, qk_nope_head_dim=128, qk_rope_head_dim=64,
             rope_scaling=dict(SMALL["rope_scaling"],
                               original_max_position_embeddings=4096))
    np.testing.assert_allclose(np.asarray(mla_moe.yarn_inv_freq(c)), got,
                               rtol=1e-6)
    assert abs(mla_moe.softmax_scale(c)
               - 1.590 / math.sqrt(192)) < 1e-3 / math.sqrt(192)


def _plain_rope(x, positions, theta):
    """The program's rotary embedding as it was before YaRN."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32)
                    / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def test_rope_unscaled_is_bitwise_unchanged():
    """With no ``rope_scaling``, ``rope`` is the plain NeoX rotary
    embedding bit for bit."""
    x = jax.random.normal(KEY, (2, 7, 3, 16)).astype(jnp.bfloat16)
    pos = jnp.arange(14).reshape(2, 7) * 37
    got = jax.jit(lambda x, p: L.rope(x, p, 1e6))(x, pos)
    want = jax.jit(lambda x, p: _plain_rope(x, p, 1e6))(x, pos)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_grouped_roofline_reader():
    """The benchmark's grouped-kernel roofline share: least time of the
    traced decode and prefill calls' grouped GEMMs over the grouped
    kernel's device time. At the cell's decode rows (48 routed rows of 64
    tokens) the least time is the held experts' int8 weights read once,
    every MoE layer's 8 experts x 3 matrices of 2048 x 1408 bytes at 819
    GB/s; it
    reads nothing from a trace without the grouped kernel, or for an
    architecture without routed experts."""
    import json
    import types

    from bench import spec
    from bench.models import dense_gqa

    read = spec.metric_reader("entangled_matmul_grouped_roofline.batch")
    c = json.loads((ROOT / "bench/configs/deepseek-v2-lite-16b.json")
                   .read_text())
    peaks = spec.load_peaks()["devices"]["TPU v5 lite"]
    red = {"kernels": {"entangled_matmul_grouped_pallas":
                       {"count": 108, "device_s": 0.06},
                       "entangled_matmul_pallas": {"count": 9,
                                                   "device_s": 1.0}},
           "modules": {"jit__decode_impl": {"count": 5, "device_s": 0.1},
                       "jit__prefill_packed_impl": {"count": 1,
                                                    "device_s": 0.2}}}
    rec = {"trace": red, "cell": types.SimpleNamespace(arch=mla_moe),
           "config": c, "serve": c["serve"], "peaks": peaks}
    bw, peak = peaks["hbm_bytes_per_s"], peaks["int8_ops_per_s"]
    dec = mla_moe.grouped_least_time(c, 64, peak, bw)
    moe_layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    weights = moe_layers * 8 * 3 * 2048 * 1408
    assert weights / bw < dec < 1.02 * weights / bw
    pre = mla_moe.grouped_least_time(c, 2048, peak, bw)
    assert read(rec) == pytest.approx(100 * (5 * dec + pre) / 0.06)
    assert read(dict(rec, trace=dict(red, kernels={}))) is None
    assert read(dict(rec, trace=None)) is None
    assert read(dict(rec, cell=types.SimpleNamespace(arch=dense_gqa))) \
        is None


def test_dense_roofline_reader():
    """The benchmark's dense-kernel roofline share in the MoE cell: least
    time of the protected sites other than the routed experts over the
    device time of kernels named exactly ``entangled_matmul_pallas``, so
    the grouped kernel's time and work stay out of it; it reads nothing
    from a trace without the dense kernel, or for an architecture without
    routed experts."""
    import json
    import types

    from bench import opcount, spec
    from bench.models import dense_gqa

    read = spec.metric_reader("entangled_matmul_pallas_roofline.batch")
    c = json.loads((ROOT / "bench/configs/deepseek-v2-lite-16b.json")
                   .read_text())
    peaks = spec.load_peaks()["devices"]["TPU v5 lite"]
    red = {"kernels": {"entangled_matmul_grouped_pallas":
                       {"count": 108, "device_s": 0.06},
                       "entangled_matmul_pallas": {"count": 9,
                                                   "device_s": 1.0}},
           "modules": {"jit__decode_impl": {"count": 5, "device_s": 0.1},
                       "jit__prefill_packed_impl": {"count": 1,
                                                    "device_s": 0.2}}}
    rec = {"trace": red, "cell": types.SimpleNamespace(arch=mla_moe,
                                                       config=c),
           "config": c, "serve": c["serve"], "peaks": peaks}
    bw, peak = peaks["hbm_bytes_per_s"], peaks["int8_ops_per_s"]
    experts = {s for s, _, _ in mla_moe.grouped_sites(c)}
    dense = [s for s in mla_moe.gemm_sites(c)
             if s["protected"] and s["site"] not in experts]
    assert len(dense) == len([s for s in mla_moe.gemm_sites(c)
                              if s["protected"]]) - 3
    dec = opcount.kernel_least_time(dense, 64, peak, bw)
    pre = opcount.kernel_least_time(
        [s for s in dense if s["site"] != "head"], 2048, peak, bw)
    assert read(rec) == pytest.approx(100 * (5 * dec + pre) / 1.0)
    only_grouped = {"entangled_matmul_grouped_pallas":
                    red["kernels"]["entangled_matmul_grouped_pallas"]}
    assert read(dict(rec, trace=dict(red, kernels=only_grouped))) is None
    assert read(dict(rec, trace=None)) is None
    assert read(dict(rec, cell=types.SimpleNamespace(arch=dense_gqa,
                                                     config=c))) is None
