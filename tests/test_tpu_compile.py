"""Compile the entangled Pallas kernels for a TPU v5e at the widths of the
serving path, with no chip attached.

The TPU compiler is installed with JAX and compiles for a described
topology, so these tests catch what interpret mode cannot: operand types
the MXU refuses, block shapes off the int8 (32, 128) tiling, slices the
lowering cannot express. Nothing runs, so they say nothing of results or
times — the interpret-mode oracle tests cover results.

Widths are ``llama3.2-1b``'s: q/o K=N=2048, k/v K=2048 N=512, gate/up
K=2048 N=8192, down K=8192 N=2048, head K=2048 N=128256; and the
benchmark's Qwen2-7B and Falcon-Mamba-7B sites at decode and packed-prefill
rows with the blocks the serving policy gives them. The topology is
described only inside the module fixture: the TPU library may be loaded
by one process at a time, so it is never touched while modules import.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.plan import make_plan
from repro.ft.registry import default_blocks
from repro.kernels.codec import PACK_LANES
from repro.kernels.conv1d import conv1d_causal_pallas
from repro.kernels.entangled_conv1d import entangled_conv1d_pallas
from repro.kernels.entangled_matmul import entangled_matmul_pallas
from repro.kernels.entangled_matmul_grouped import (
    entangled_matmul_grouped_pallas)

PLAN = make_plan(4)
M = PLAN.M
BB = 32  # rows per stream: one int8 row tile

# (site, K, N) of llama3.2-1b's protected GEMMs; head/4 is one device's
# column slice of the head on a 4-chip mesh, whose 32064 columns end in a
# partial column block
SITES = [("q", 2048, 2048), ("k", 2048, 512), ("gate", 2048, 8192),
         ("down", 8192, 2048), ("head", 2048, 128256),
         ("head/4", 2048, 128256 // 4)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _blocks(K, N, grouped=False):
    """The protected sites' default blocks on the compiled backend (a
    decode step of 8 slots: 2 rows per stream)."""
    return default_blocks(8 // M, K, N, "pallas_tpu", grouped=grouped)


@pytest.mark.parametrize("site,K,N", SITES, ids=[s[0] for s in SITES])
@pytest.mark.parametrize("packed", [True, False], ids=["packed", "int32"])
def test_entangled_matmul_compiles(one_chip, no_persistent_cache,
                                   site, K, N, packed):
    """The fused dense kernel at each protected site's width, int8
    activations (the serving path's one limb), healthy."""
    bl = _blocks(K, N)
    c = jax.ShapeDtypeStruct((M, BB, K), jnp.int8, sharding=one_chip)
    g = jax.ShapeDtypeStruct((K // PACK_LANES if packed else K, N),
                             jnp.int32, sharding=one_chip)
    compiled = _compile(lambda c, g: entangled_matmul_pallas(
        c, g, plan=PLAN, fuse_epilogue=True, packed=packed, **bl), c, g)
    assert "tpu_custom_call" in compiled.as_text()


# (config, site, rows per stream group, K, N) of the benchmark's protected
# GEMMs: decode rows (max_batch / M) and packed-prefill rows (2048 / M);
# the head runs on decode rows only
BENCH_SITES = [
    (cfg, site, rows, K, N)
    for cfg, dec, sites in (
        ("qwen2-7b", 8, [("q", 3584, 3584), ("k", 3584, 512),
                         ("gate", 3584, 18944), ("down", 18944, 3584),
                         ("head", 3584, 38016)]),
        ("falcon", 16, [("in_proj", 4096, 16384), ("out_proj", 8192, 4096),
                        ("head", 4096, 16256)]))
    for site, K, N in sites
    for rows in ((dec,) if site == "head" else (dec, 512))]


@pytest.mark.parametrize(
    "cfg,site,rows,K,N", BENCH_SITES,
    ids=[f"{c}-{s}-{r}" for c, s, r, _, _ in BENCH_SITES])
def test_bench_sites_compile_without_weight_copy(
        one_chip, no_persistent_cache, cfg, site, rows, K, N):
    """The fused kernel through ``ops`` at a benchmark site's width with
    the policy's blocks (decode-sized weight tiles at decode rows): it
    compiles for the chip, and no pad or copy as large as the packed
    weights is made per call — ``bk`` divides K, and N is never padded."""
    from repro.kernels import ops

    c = jax.ShapeDtypeStruct((M, rows, K), jnp.int8, sharding=one_chip)
    g = jax.ShapeDtypeStruct((K // PACK_LANES, N), jnp.int32,
                             sharding=one_chip)
    bl = default_blocks(rows, K, N, "pallas_tpu")
    text = _compile(lambda c, g: ops.entangled_matmul(
        c, g, PLAN, fuse_epilogue=True, failed=1, packed=True, blocks=bl,
        backend="pallas_tpu"), c, g).as_text()
    assert "tpu_custom_call" in text
    # the int8 activations' rows may pad; no int32 array the size of the
    # packed weights is made
    _assert_no_copy_over(text, K * N // PACK_LANES)


def _assert_no_copy_over(text, words):
    """No int32 pad or copy in the compiled ``text`` holds ``words``
    elements or more."""
    for dims, op in re.findall(r"= s32\[([0-9,]*)\]\S* (pad|copy)\(",
                               text):
        size = 1
        for d in dims.split(","):
            size *= int(d or 1)
        assert size < words, (op, dims)


def test_sharded_head_compiles_without_weight_copy(topo, no_persistent_cache):
    """The head GEMM through ``ops`` under the serving engine's 2x2 mesh:
    each device runs the kernel in a ``shard_map`` on its column slice of
    the packed head, and no padded copy of the head is made per call."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dist.sharding import axis_rules, serve_mesh
    from repro.kernels import ops

    mesh = serve_mesh(topo.devices)
    K, V = 2048, 128256
    rep = NamedSharding(mesh, P())
    c = jax.ShapeDtypeStruct((M, 2, K), jnp.int8, sharding=rep)
    g = jax.ShapeDtypeStruct((K // PACK_LANES, V), jnp.int32, sharding=rep)
    with axis_rules(mesh):
        compiled = _compile(lambda c, g: ops.entangled_matmul(
            c, g, PLAN, fuse_epilogue=True, failed=1, packed=True,
            blocks={"bb": BB}, backend="pallas_tpu"), c, g)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"s32[{K // PACK_LANES},{V // 4}]" in text  # one column slice
    # only the int8 activations' rows are padded, never the int32 weights
    assert not re.search(r"= s32\[[0-9,]*\]\S* pad\(", text)


@pytest.mark.parametrize("mode", [True, False, "chain", "chain_final"],
                         ids=["fused", "entangled", "chain", "chain_final"])
@pytest.mark.parametrize("dtype", [jnp.int8, jnp.int32],
                         ids=["int8", "int32"])
def test_entangled_matmul_modes_compile_failed(one_chip, no_persistent_cache,
                                               mode, dtype):
    """Every fuse mode with stream 1 fail-stopped, at the gate/up width;
    int32 activations take four int8 limbs."""
    K, N = 2048, 8192
    c = jax.ShapeDtypeStruct((M, BB, K), dtype, sharding=one_chip)
    g = jax.ShapeDtypeStruct((K // PACK_LANES, N), jnp.int32,
                             sharding=one_chip)
    _compile(lambda c, g: entangled_matmul_pallas(
        c, g, plan=PLAN, fuse_epilogue=mode, failed=1, packed=True,
        **_blocks(K, N)), c, g)


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "int32"])
def test_entangled_matmul_grouped_compiles(one_chip, no_persistent_cache,
                                           packed):
    """The grouped (per-expert) kernel, 8 experts at the k/v width, with
    stream 1 fail-stopped."""
    E, K, N = 8, 2048, 512
    c = jax.ShapeDtypeStruct((M, E, BB, K), jnp.int8, sharding=one_chip)
    g = jax.ShapeDtypeStruct((E, K // PACK_LANES if packed else K, N),
                             jnp.int32, sharding=one_chip)
    _compile(lambda c, g: entangled_matmul_grouped_pallas(
        c, g, plan=PLAN, fuse_epilogue=True, failed=1, packed=packed,
        **_blocks(K, N, grouped=True)), c, g)


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "int32"])
def test_grouped_moe_width_compiles_without_weight_copy(
        one_chip, no_persistent_cache, packed):
    """The grouped kernel through ``ops`` at an MoE expert's width (K=2048,
    N=1408: the last column block is partial) with the registry's grouped
    blocks, 8 experts, 16 rows each per stream: it compiles for the chip
    under the default scoped VMEM limit, and no pad or copy as large as
    the expert weights is made per call."""
    from repro.kernels import ops

    E, K, N = 8, 2048, 1408
    bl = default_blocks(16, K, N, "pallas_tpu", grouped=True)
    c = jax.ShapeDtypeStruct((M, E, 16, K), jnp.int8, sharding=one_chip)
    g = jax.ShapeDtypeStruct((E, K // PACK_LANES if packed else K, N),
                             jnp.int32, sharding=one_chip)
    text = _compile(lambda c, g: ops.entangled_matmul_grouped(
        c, g, PLAN, fuse_epilogue=True, failed=1, packed=packed, blocks=bl,
        backend="pallas_tpu"), c, g).as_text()
    assert "tpu_custom_call" in text
    _assert_no_copy_over(text, E * K * N // (PACK_LANES if packed else 1))


@pytest.mark.parametrize("rows,dtype", [(2, jnp.int8), (256, jnp.int32)],
                         ids=["decode-int8", "prefill-int32"])
def test_eight_streams_compile_without_weight_copy(
        one_chip, no_persistent_cache, rows, dtype):
    """The dense kernel through ``ops`` with M=8 streams (l=4: every
    predecessor limb term is stacked) at the Qwen2-7B gate width, with the
    blocks the policy fits to that plan; int32 activations at prefill rows
    are the largest working set the policy reckons."""
    from repro.kernels import ops

    plan8 = make_plan(8)
    K, N = 3584, 18944
    bl = default_blocks(rows, K, N, "pallas_tpu", plan=plan8)
    c = jax.ShapeDtypeStruct((8, rows, K), dtype, sharding=one_chip)
    g = jax.ShapeDtypeStruct((K, N), jnp.int32, sharding=one_chip)
    text = _compile(lambda c, g: ops.entangled_matmul(
        c, g, plan8, fuse_epilogue=True, failed=1, packed=False, blocks=bl,
        backend="pallas_tpu"), c, g).as_text()
    assert "tpu_custom_call" in text
    _assert_no_copy_over(text, K * N)


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "int32"])
@pytest.mark.parametrize("failed", [0, 1])
def test_entangled_conv1d_compiles(one_chip, no_persistent_cache,
                                   packed, failed):
    """The entangled depthwise causal conv (the paper's validation op),
    D=256 channels, T=1024 steps, K_f=4 taps."""
    D, T, kf = 256, 1024, 4
    x = jax.ShapeDtypeStruct((M, 1, D, T), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((D // PACK_LANES if packed else D, kf),
                             jnp.int32, sharding=one_chip)
    _compile(lambda x, w: entangled_conv1d_pallas(
        x, w, plan=PLAN, fuse_epilogue=True, failed=failed, packed=packed,
        bd=128, bt=512), x, w)


def test_conv1d_compiles(one_chip, no_persistent_cache):
    """The unentangled causal conv shares the lane-rotation taps."""
    x = jax.ShapeDtypeStruct((1, 256, 1024), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((256, 4), jnp.int32, sharding=one_chip)
    _compile(lambda x, w: conv1d_causal_pallas(x, w, bd=128, bt=512), x, w)


# DeepSeek-V2-Lite's routed-expert GEMMs at the benchmark cell's capacity
# rows (dropless: every held expert's buffer holds the call's tokens, 64 a
# decode step and 2048 a packed prefill, i.e. 16 and 512 a stream)
EXPERT_SITES = [(site, rows, K, N)
                for site, K, N in (("gate", 2048, 1408), ("down", 1408, 2048))
                for rows in (64, 2048)]


@pytest.mark.parametrize("site,rows,K,N", EXPERT_SITES,
                         ids=[f"{s}-{r}" for s, r, _, _ in EXPERT_SITES])
def test_expert_sites_compile_without_weight_copy(
        one_chip, no_persistent_cache, site, rows, K, N):
    """The grouped kernel through ``ops`` for 8 held experts at the
    published expert width with the registry's grouped blocks: it compiles
    for the chip, and ``bk`` divides K (1408 takes 128), so no pad or copy
    of the packed expert weights is made per call."""
    from repro.kernels import ops

    E, Bg = 8, rows // M
    bl = default_blocks(Bg, K, N, "pallas_tpu", grouped=True)
    assert K % bl["bk"] == 0
    c = jax.ShapeDtypeStruct((M, E, Bg, K), jnp.int8, sharding=one_chip)
    g = jax.ShapeDtypeStruct((E, K // PACK_LANES, N), jnp.int32,
                             sharding=one_chip)
    text = _compile(lambda c, g: ops.entangled_matmul_grouped(
        c, g, PLAN, fuse_epilogue=True, failed=1, packed=True, blocks=bl,
        backend="pallas_tpu"), c, g).as_text()
    assert "tpu_custom_call" in text
    _assert_no_copy_over(text, E * K * N // PACK_LANES)


def test_absorbed_mla_decode_compiles(one_chip, no_persistent_cache):
    """DeepSeek-V2-Lite's latent-attention decode at the benchmark cell's
    64 slots x 5,120 positions, absorbed, with per-row positions and the
    latent cache donated: it compiles for the chip, the cache is updated
    in place (aliased), and the program's temporaries fit beside it."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import layers as L

    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"),
                              mla_absorb=True)
    B, S = 64, 5120

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    p = jax.tree.map(sds, jax.eval_shape(
        lambda k: L.init_mla(k, cfg, S), jax.random.PRNGKey(0)))
    cache = jax.tree.map(sds, jax.eval_shape(
        lambda: L.init_mla_cache(cfg, B, S)))
    x = jax.ShapeDtypeStruct((B, 1, cfg.d_model), L.ACT_DTYPE,
                             sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, x, c, pos: L.apply_mla(p, x, cfg=cfg, cache=c, pos=pos,
                                         mode="decode"),
        donate_argnums=(2,)).lower(p, x, cache, pos).compile()
    mem = compiled.memory_analysis()
    cache_bytes = B * S * (512 + 64) * 2
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < 4 * cache_bytes
