"""Operation and byte counts against hand counts."""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import opcount  # noqa: E402
from bench.models import dense_gqa, mamba1  # noqa: E402


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def site(sites, name):
    return next(s for s in sites if s["site"] == name)


def test_qwen2_down_projection_at_decode():
    s = site(dense_gqa.gemm_sites(config("qwen2-7b")), "down")
    assert (s["K"], s["N"], s["layers"]) == (18944, 3584, 6)
    rows = 32
    assert opcount.gemm_ops(rows, s["K"], s["N"]) == 2 * 32 * 18944 * 3584
    # int8 weights 67,895,296 B + int8 activations 606,208 B + int32
    # outputs 458,752 B
    assert opcount.kernel_bytes(rows, s["K"], s["N"]) == \
        67_895_296 + 606_208 + 458_752
    # memory-bound at 32 rows on v5e: bytes / 819 GB/s
    t = opcount.least_time(opcount.gemm_ops(rows, 18944, 3584),
                           opcount.kernel_bytes(rows, 18944, 3584),
                           393e12, 819e9)
    assert t == pytest.approx(68_960_256 / 819e9)


def test_qwen2_layer_is_233m_weights():
    sites = dense_gqa.gemm_sites(config("qwen2-7b"))
    layer = sum(s["K"] * s["N"] for s in sites if s["site"] != "head")
    assert layer == 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert opcount.gemm_ops_per_token(
        [s for s in sites if s["site"] != "head"]) == 2 * 6 * layer


def test_falcon_mamba_in_proj_at_prefill():
    s = site(mamba1.gemm_sites(config("falcon-mamba-7b")), "in_proj")
    assert (s["K"], s["N"], s["layers"]) == (4096, 16384, 16)
    rows = 2048
    ops = opcount.gemm_ops(rows, 4096, 16384)
    assert ops == 274_877_906_944
    nbytes = opcount.kernel_bytes(rows, 4096, 16384)
    assert nbytes == 67_108_864 + 8_388_608 + 134_217_728
    # compute-bound at 2048 rows: ops / 393 TOP/s
    assert opcount.least_time(ops, nbytes, 393e12, 819e9) == \
        pytest.approx(ops / 393e12)
    assert opcount.kernel_least_time([s], rows, 393e12, 819e9) == \
        pytest.approx(16 * ops / 393e12)


def test_param_counts_split_embedding():
    tree = {"embed": {"tok": jax.ShapeDtypeStruct((10, 4), jnp.float32),
                      "head": jax.ShapeDtypeStruct((4, 10), jnp.float32)},
            "stack": [{"w": jax.ShapeDtypeStruct((3, 4, 5), jnp.float32)}]}
    c = opcount.param_counts(tree)
    assert c == {"total": 140, "embedding": 80, "nonemb": 60}
