"""Compile the entangled Pallas kernels for a TPU v5e at the widths of the
serving path, with no chip attached.

The TPU compiler is installed with JAX and compiles for a described
topology, so these tests catch what interpret mode cannot: operand types
the MXU refuses, block shapes off the int8 (32, 128) tiling, slices the
lowering cannot express. Nothing runs, so they say nothing of results or
times — the interpret-mode oracle tests cover results.

Widths are ``llama3.2-1b``'s: q/o K=N=2048, k/v K=2048 N=512, gate/up
K=2048 N=8192, down K=8192 N=2048, head K=2048 N=128256. The topology is
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


def _blocks(K, N):
    """The protected sites' default blocks on the compiled backend (a
    decode step of 8 slots: 2 rows per stream)."""
    return default_blocks(8 // M, K, N, "pallas_tpu")


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
        **_blocks(K, N)), c, g)


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
