"""Unified protected-GEMM subsystem (repro.ft) invariants.

  * protected_matmul recovery is EXACT: for every failed group r, the
    fail-stop-injected output equals the healthy output bitwise — fused
    Pallas, unfused Pallas and XLA paths, contiguous and round-robin
    grouping, row counts that do and do not divide into M groups;
  * the integer path is faithful: dequantized outputs approximate the
    float GEMM within the quantization step;
  * the activation budget honors the plan's eq. (13) output bound for the
    full contraction depth;
  * the PlanRegistry keys entries by (site, shape, M, backend), clamps
    default blocks to the call shape, and its census lists every site;
  * the shipped pre-tuned seed cache (kernels/pretuned/interpret_cpu.json)
    makes a COLD engine startup with blocks='auto' a pure cache hit — no
    sweep runs even with an empty user cache file.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.plan import make_plan
from repro.ft import (FTContext, PlanRegistry, activation_budget,
                      default_blocks, group_order, protected_matmul,
                      quantize_acts, quantize_weight)

RNG = np.random.default_rng(23)


def _xw(R=10, K=24, N=16):
    x = jnp.asarray(RNG.normal(size=(R, K)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(K, N)).astype(np.float32))
    return x, w


@pytest.mark.parametrize("use_pallas,fuse", [(True, True), (True, False),
                                             (False, False)])
@pytest.mark.parametrize("R", [8, 10])  # 10: pads 2 zero rows to M=4 groups
def test_protected_matmul_failstop_exact(use_pallas, fuse, R):
    plan = make_plan(4, 32)
    x, w = _xw(R=R)
    healthy = protected_matmul(x, w, plan=plan, use_pallas=use_pallas,
                               fuse_epilogue=fuse)
    assert healthy.shape == (R, w.shape[1])
    for r in range(plan.M):
        injected = protected_matmul(x, w, plan=plan, failed_group=r,
                                    use_pallas=use_pallas, fuse_epilogue=fuse)
        np.testing.assert_array_equal(np.asarray(healthy),
                                      np.asarray(injected),
                                      err_msg=f"failed_group={r}")


def test_protected_matmul_faithful_and_grouping_invariant():
    """Quantize-dequantize stays within one quantization step of the float
    GEMM, and contiguous vs round-robin grouping produce identical values
    (grouping permutes streams, never the math)."""
    plan = make_plan(4, 32)
    x, w = _xw(R=8, K=24, N=16)
    ref = np.asarray(x) @ np.asarray(w)
    got = np.asarray(protected_matmul(x, w, plan=plan))
    _, w_scale = quantize_weight(w)
    _, a_scale = quantize_acts(x, plan, x.shape[1])
    # worst-case rounding: K terms, each off by <= half a grid step per
    # operand (cross term negligible and covered by the 0.25 slack).
    # a_scale is PER ROW ([R, 1]); the coarsest row's grid bounds them all.
    K = x.shape[1]
    a_min = float(np.min(np.asarray(a_scale)))
    bound = K * (0.5 * np.max(np.abs(w)) / a_min
                 + 0.5 * np.max(np.abs(x)) / float(w_scale)
                 + 0.25 / (a_min * float(w_scale)))
    assert np.max(np.abs(got - ref)) <= bound
    rr = np.asarray(protected_matmul(x, w, plan=plan, contiguous=False))
    cont = np.asarray(protected_matmul(x, w, plan=plan, contiguous=True))
    # recovery is exact in BOTH layouts, so outputs match row-for-row —
    # grouping only re-buckets rows onto streams, never changes the math
    np.testing.assert_array_equal(rr, cont)


def test_activation_budget_honors_eq13():
    plan = make_plan(4, 32)
    for K in (7, 64, 4096):
        b = activation_budget(plan, K)
        assert b >= 1 and K * b * 127 <= max(plan.max_output_magnitude,
                                             K * 127)
        if b > 1:  # non-degenerate budgets must fit exactly
            assert K * b * 127 <= plan.max_output_magnitude


def test_group_order_roundtrip():
    order, inv = group_order(12, 4)
    assert (order[inv] == np.arange(12)).all()
    # row -> group = row % M: group g holds rows g, g+M, g+2M, ...
    assert list(order[:3]) == [0, 4, 8]


def test_registry_keys_blocks_and_census():
    plan = make_plan(4, 32)
    reg = PlanRegistry(plan)
    e = reg.entry("qkv.q", rows=4, K=64, N=48, backend="interpret")
    assert e.shape == (4, 1, 64, 48)  # rows pad to M groups -> 1 row/group
    assert e.blocks == {"bb": 8, "bn": 64, "bk": 64}  # shape-clamped pow2
    assert reg.get("qkv.q", e.shape, "interpret") is e
    # same site, other shape -> distinct entry; census lists both
    e2 = reg.entry("qkv.q", rows=128, K=64, N=48, backend="interpret")
    assert e2 is not e and e2.shape == (4, 32, 64, 48)
    assert set(reg.census()) == {("qkv.q", e.shape), ("qkv.q", e2.shape)}
    assert default_blocks(1, 2048, 300) == {"bb": 8, "bn": 256, "bk": 256}


def test_ftcontext_scopes():
    reg = PlanRegistry(make_plan(4, 32))
    for scope, protected in [("head", ["head"]),
                             ("qkv", ["head", "qkv.q", "qkv.in"]),
                             ("mlp", ["head", "mlp.down", "mlp.router"]),
                             ("all", ["head", "qkv.k", "mlp.up"])]:
        ctx = FTContext(registry=reg, scope=scope)
        for site in protected:
            assert ctx.protects(site), (scope, site)
    ctx = FTContext(registry=reg, scope="qkv")
    assert not ctx.protects("mlp.up")
    with pytest.raises(ValueError, match="ft_scope"):
        FTContext(registry=reg, scope="everything")


def test_pretuned_seed_cache_cold_hit(tmp_path, monkeypatch):
    """A cold process (empty user cache file) whose serving shapes are
    covered by the shipped interpret_cpu.json must warm WITHOUT a single
    sweep — the ROADMAP 'ship a pre-tuned cache' contract."""
    from repro.configs import get_smoke_config
    from repro.kernels import autotune
    from repro.models import get_model
    from repro.serve import ServeConfig, ServeEngine

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    cache = autotune.reset_cache(str(tmp_path / "at.json"))
    try:
        cfg = get_smoke_config("llama3.2-1b")
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0), cfg, max_seq=48)
        eng = ServeEngine(
            cfg, ServeConfig(max_batch=4, max_seq=48, ft_mode="entangle",
                             ft_M=4, ft_scope="all", blocks="auto"), params)
        assert cache.sweeps == 0, "cold warm swept despite pretuned cache"
        assert cache.hits > 0
        # warm covered head AND every in-model protected site (incl. the
        # v2 output-projection category)
        assert eng.census["head_gemm"]
        sites = {s for s, _ in eng.census["protected"]}
        assert {"qkv.q", "qkv.k", "qkv.v",
                "mlp.gate", "mlp.up", "mlp.down", "out.o"} <= sites
        # steady-state refill path: a chunked refill engine only ever
        # replays census'd [Bp, chunk] shapes, so its cold start must be
        # sweep-free off the same shipped cache too
        eng2 = ServeEngine(
            cfg, ServeConfig(max_batch=4, max_seq=48, prefill_chunk=8,
                             refill=True, ft_mode="entangle", ft_M=4,
                             ft_scope="all", blocks="auto"), params)
        assert cache.sweeps == 0, \
            "refill-path chunk shapes missing from pretuned seed cache"
        assert eng2.plans.misses == 0
        # token-packed admission runs ONE [Rp, Cp] program whatever the
        # packing mix — its protected shapes (rows = token_budget) must
        # cold-hit off the same shipped cache too
        eng3 = ServeEngine(
            cfg, ServeConfig(max_batch=4, max_seq=48, prefill_chunk=8,
                             token_budget=16, refill=True,
                             ft_mode="entangle", ft_M=4,
                             ft_scope="all", blocks="auto"), params)
        assert cache.sweeps == 0, \
            "packed-engine shapes missing from pretuned seed cache"
        assert eng3.plans.misses == 0
    finally:
        autotune.reset_cache(None)


BENCH_CELLS = ["qwen2-7b.chat.ft-all", "falcon-mamba-7b.batch.ft-all"]


def _bench_engine(cell_name: str):
    """The serving engine of a benchmark cell at its full widths, built on
    abstract weights (nothing is allocated) with the compiled TPU backend
    pinned, so its plans are the ones the chip runs."""
    from bench import harness
    from repro.kernels import ops
    from repro.serve.engine import ServeEngine

    cell = harness.load_cell(cell_name)
    cfg, scfg = harness.program_objects(cell)
    shapes = jax.eval_shape(
        lambda k: cell.arch.init_params(k, c=cell.config),
        jax.random.PRNGKey(0))
    box = {}

    def build(params):
        box["eng"] = ServeEngine(cfg, scfg, params)
        return 0

    ops.set_default_backend("pallas_tpu")
    try:
        jax.eval_shape(build, shapes)
    finally:
        ops.set_default_backend(None)
    return box["eng"]


@pytest.mark.parametrize("cell", BENCH_CELLS)
def test_tpu_tile_policy_at_bench_sites(cell):
    """Every protected GEMM of a benchmark configuration, at decode rows
    and packed-prefill rows, gets compiled-backend blocks that tile int8
    as (32, 128), a ``bk`` dividing K (no per-call weight pad), a working
    set within the kernel's VMEM budget and, at decode rows, a weight tile
    of at least 1 MiB. The head's blocks come from the same policy, and
    the engine's tile counter puts every decode site in the decode-tile
    class."""
    from repro.ft.registry import TILE_MIN, group_rows
    from repro.kernels.entangled_matmul import VMEM_BUDGET_BYTES, vmem_bytes

    eng = _bench_engine(cell)
    Bg = group_rows(eng.scfg.max_batch, eng.scfg.ft_M)
    D, V = eng._head_dims
    assert eng._head_blocks == default_blocks(Bg, D, V, "pallas_tpu",
                                              plan=eng.plan)
    calls = [(p.site, p.shape[-3], p.shape[-2], p.shape[-1], p.blocks)
             for p in eng.plans] + [("head", Bg, D, V, eng._head_blocks)]
    assert {rows for _, rows, _, _, _ in calls} == {
        Bg, group_rows(eng.scfg.token_budget, eng.scfg.ft_M)}
    for site, rows, K, N, b in calls:
        assert K % b["bk"] == 0, (site, rows, b)
        assert b["bb"] % 32 == b["bn"] % 128 == b["bk"] % 128 == 0, b
        assert vmem_bytes(b["bb"], b["bn"], b["bk"], n=4,
                          packed=False) <= VMEM_BUDGET_BYTES, (site, b)
        if rows == Bg:
            assert b["bn"] * b["bk"] >= TILE_MIN, (site, b)
    m = eng.metrics
    decode = sum(rows == Bg for _, rows, _, _, _ in calls)
    assert m["ft_sites"] == len(calls)
    assert m["ft_decode_sites"] == m["ft_decode_tile_sites"] == decode
    assert m["ft_decode_tile_min_bytes"] >= TILE_MIN


@pytest.mark.parametrize("M", [3, 4, 8])
def test_tpu_tile_policy_fits_the_plan(M):
    """The registry's compiled-backend blocks for dense sites fit the
    kernel's VMEM budget at the plan's stream count and shift, at decode
    and packed-prefill rows of the benchmark's widest sites, with ``bk``
    dividing K; grouped (per-expert) sites keep the shape-clamped powers
    of two the grouped kernel is built for."""
    from repro.kernels.entangled_matmul import VMEM_BUDGET_BYTES, vmem_bytes

    plan = make_plan(M, 32)
    reg = PlanRegistry(plan)
    for rows in (8, 2048):
        for K, N in ((3584, 18944), (18944, 3584), (4096, 16384),
                     (8192, 4096)):
            b = reg.entry("mlp.up", rows=rows, K=K, N=N,
                          backend="pallas_tpu").blocks
            assert b == default_blocks(-(-rows // M), K, N, "pallas_tpu",
                                       plan=plan)
            assert K % b["bk"] == 0, (rows, K, N, b)
            assert vmem_bytes(b["bb"], b["bn"], b["bk"], n=4, M=M, l=plan.l,
                              packed=False) <= VMEM_BUDGET_BYTES, b
    e = reg.entry("moe.up", rows=16, K=2048, N=1408, backend="pallas_tpu",
                  groups=8)
    assert e.grouped and e.blocks == {"bb": 32, "bn": 256, "bk": 256}


def test_cpu_engine_counts_no_decode_tiles():
    """Off the compiled backend the head takes the shape-clamped defaults,
    and no decode site is counted with a decode-sized weight tile."""
    from repro.configs import get_smoke_config
    from repro.ft.registry import TILE_MIN, group_rows
    from repro.kernels import ops
    from repro.models import get_model
    from repro.serve import ServeConfig, ServeEngine

    cfg = get_smoke_config("llama3.2-1b")
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg, max_seq=48)
    eng = ServeEngine(cfg, ServeConfig(max_batch=8, max_seq=48,
                                       ft_mode="entangle", ft_scope="all"),
                      params)
    D, V = eng._head_dims
    assert eng._head_blocks == default_blocks(
        group_rows(8, 4), D, V, ops.resolve_backend())
    m = eng.metrics
    assert m["ft_sites"] == len(eng.plans) + 1 and m["ft_decode_sites"] >= 1
    assert m["ft_decode_tile_sites"] == 0
    assert 0 < m["ft_decode_tile_min_bytes"] < TILE_MIN
