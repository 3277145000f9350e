"""Per-kernel shape/dtype sweeps, exact-equality vs the pure-jnp oracles
(interpret=True executes kernel bodies on CPU — the task-mandated mode)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.plan import make_plan
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("M,w", [(3, 32), (4, 32), (8, 32), (4, 16)])
@pytest.mark.parametrize("n", [64, 1000, 1024, 4097])
def test_entangle_kernel_sweep(M, w, n):
    plan = make_plan(M, w)
    lim = min(plan.max_output_magnitude, 2**20) or 100
    c = jnp.asarray(RNG.integers(-lim, lim, size=(M, n)).astype(np.int32))
    out = ops.entangle(c, plan)
    expect = ref.entangle_ref(c, plan.l)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("M,w", [(3, 32), (4, 32), (8, 32), (4, 16)])
@pytest.mark.parametrize("failed", [None, 0, 1, -1])
def test_disentangle_kernel_sweep(M, w, failed):
    plan = make_plan(M, w)
    D = plan.max_output_magnitude
    d = RNG.integers(-D, D + 1, size=(M, 2048)).astype(np.int64)
    delta = jnp.asarray(((np.roll(d, 1, 0) << plan.l) + d).astype(np.int32))
    f = (failed % M) if failed is not None else None
    out = ops.disentangle(delta, plan, failed=f)
    np.testing.assert_array_equal(np.asarray(out), d)
    expect = ref.disentangle_ref(delta, plan, r=f or 0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("shape", [(4, 8, 32), (3, 128, 128), (4, 130, 300)])
@pytest.mark.parametrize("n_out", [16, 128, 257])
def test_entangled_matmul_sweep(shape, n_out):
    plan = make_plan(shape[0], 32)
    c = jnp.asarray(RNG.integers(-15, 15, size=shape).astype(np.int32))
    g = jnp.asarray(RNG.integers(-15, 15, size=(shape[2], n_out)).astype(np.int32))
    out = ops.entangled_matmul(c, g, plan, bb=32, bn=64, bk=32)
    expect = ref.entangled_matmul_ref(c, g, plan.l)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    # and the entangled product disentangles to the true integer GEMM
    true = np.einsum("mbk,kn->mbn", np.asarray(c, np.int64), np.asarray(g, np.int64))
    rec = ops.disentangle(out, plan, failed=shape[0] - 1)
    np.testing.assert_array_equal(np.asarray(rec), true)


@pytest.mark.parametrize("B,D,T,kf", [(1, 16, 64, 4), (2, 130, 513, 4), (1, 64, 128, 3)])
def test_conv1d_kernel_sweep(B, D, T, kf):
    x = jnp.asarray(RNG.integers(-30, 30, size=(B, D, T)).astype(np.int32))
    w = jnp.asarray(RNG.integers(-10, 10, size=(D, kf)).astype(np.int32))
    out = ops.conv1d_causal(x, w, bd=16, bt=64)
    expect = ref.conv1d_causal_ref(x, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("M,n", [(3, 100), (8, 4096)])
def test_checksum_kernel(M, n):
    c = jnp.asarray(RNG.integers(-1000, 1000, size=(M, n)).astype(np.int32))
    out = ops.checksum(c)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref.checksum_ref(c))[0])


def test_entangle_kernel_nd_shapes():
    """ops wrappers flatten arbitrary trailing shapes."""
    plan = make_plan(4, 32)
    c = jnp.asarray(RNG.integers(-100, 100, size=(4, 3, 5, 7)).astype(np.int32))
    out = ops.entangle(c, plan)
    assert out.shape == c.shape
    rec = ops.disentangle(ref_delta(c, plan), plan, failed=2)
    np.testing.assert_array_equal(np.asarray(rec), np.asarray(c))


def ref_delta(c, plan):
    d = np.asarray(c, dtype=np.int64)
    return jnp.asarray(((np.roll(d, 1, 0) << plan.l) + d).astype(np.int32))


# (activation dtype -> int8 limbs n, fuse mode, packed weights, rows per
# stream): every n meets every mode; packed / int32 weights and 8 / 16 rows
# (both below the 32-row block) alternate across them
ONE_DOT_CASES = [
    (dt, mode, (i + j) % 2 == 0, (8, 16)[(i + j // 2) % 2])
    for i, dt in enumerate((jnp.int8, jnp.int16, jnp.int32))
    for j, mode in enumerate((True, False, "chain", "chain_final"))]


@pytest.mark.parametrize(
    "dtype,mode,packed,rows", ONE_DOT_CASES,
    ids=[f"{jnp.dtype(d).name}-{m}-{'packed' if p else 'int32'}-{r}"
         for d, m, p, r in ONE_DOT_CASES])
def test_one_dot_kernel_matches_oracle(dtype, mode, packed, rows):
    """The fused GEMM's single stacked dot per grid step is bit-identical to
    the jnp oracle for every limb count, fuse mode and failed stream, with
    rows below the row block and a partial last column block (N = 320 in
    128-column blocks, K = 256 in two 128-deep steps)."""
    from repro.kernels.codec import pack_int8

    plan = make_plan(4, 32)
    K, N = 256, 320
    info = np.iinfo(dtype)
    # the standalone fused GEMM extracts through the eq.-13 budget, so its
    # inputs stay inside it; every other mode is compared in full int32
    # ring arithmetic, over all the values the limbs hold (two balanced
    # int8 limbs stop at 32639: see the limb contract in kernels/codec.py)
    lo, hi = ((-60, 60) if mode is True
              else (info.min, 32639 if dtype == jnp.int16 else info.max))
    c = jnp.asarray(RNG.integers(lo, hi, size=(plan.M, rows, K),
                                 endpoint=True).astype(dtype))
    g = jnp.asarray(RNG.integers(-128, 127, size=(K, N), endpoint=True)
                    .astype(np.int32))
    gk = pack_int8(g, axis=0) if packed else g
    extracts = mode in (True, "chain_final")
    for failed in range(plan.M) if extracts else (0,):
        out = ops.entangled_matmul(
            c, gk, plan, fuse_epilogue=mode, failed=failed, packed=packed,
            blocks={"bb": 32, "bn": 128, "bk": 128}, backend="interpret_cpu")
        expect = ops.entangled_matmul(
            c, gk, plan, fuse_epilogue=mode, failed=failed, packed=packed,
            backend="reference")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    if mode is True:
        true = np.einsum("mbk,kn->mbn", np.asarray(c, np.int64),
                         np.asarray(g, np.int64))
        np.testing.assert_array_equal(np.asarray(out), true)
