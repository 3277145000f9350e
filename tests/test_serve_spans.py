"""Host spans and admission stamps of the serving engine.

  * with a ``SpanLog`` every start-up and step phase is recorded under its
    fixed name and parent, one ``serve.step`` per ``step()``, on both the
    token-packed and the per-batch chunked admission paths;
  * with spans off the engine records nothing and serves the same tokens;
  * ``t_submit <= t_admitted < t_first`` on the engine's injectable clock,
    and the spans are timed on that same clock;
  * ``step_host_s`` subtracts the blocking device reads inside each step;
  * every protected GEMM site traces under ``ft.<site>``.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.plan import make_plan
from repro.ft import FTContext, PlanRegistry
from repro.kernels import autotune
from repro.models import get_model
from repro.serve import Request, ServeConfig, ServeEngine
from repro.serve import spans as spans_mod
from repro.serve.spans import SpanLog

LENGTHS = [5, 6, 12, 3, 4, 6]
MAX_NEW = [1, 2, 3, 2, 1, 2]

STEP_CHILDREN = {"serve.shed", "serve.plan", "serve.prefill", "serve.land",
                 "serve.flush", "serve.decode", "serve.decode.sync",
                 "serve.emit"}
INIT_CHILDREN = {"serve.init.quantize", "serve.init.census",
                 "serve.init.plans", "serve.init.params",
                 "serve.init.autotune"}


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("llama3.2-1b")
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg, max_seq=48)
    return cfg, params


@pytest.fixture
def pretuned(tmp_path, monkeypatch):
    """The shipped pre-tuned block cache, so ``blocks='auto'`` start-up
    sweeps nothing and writes only under tmp_path."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    autotune.reset_cache(str(tmp_path / "at.json"))
    yield
    autotune.reset_cache(None)


def _serve(cfg, params, *, token_budget=16, spans=None, clock=None,
           blocks=None):
    scfg = ServeConfig(max_batch=4, max_seq=48, prefill_chunk=8,
                       prefill_buckets=(8, 16), token_budget=token_budget,
                       ft_mode="entangle", ft_M=4, ft_scope="all",
                       blocks=blocks, clock=clock)
    eng = ServeEngine(cfg, scfg, params, spans=spans)
    rng = np.random.default_rng(5)
    for r, (n, m) in enumerate(zip(LENGTHS, MAX_NEW)):
        eng.submit(Request(rid=r, max_new=m,
                           prompt=rng.integers(0, cfg.vocab_size, n)
                           .astype(np.int32)))
    steps = 0
    while not eng.idle():
        eng.step()
        steps += 1
    return eng, steps


@pytest.mark.parametrize("token_budget", [16, 0], ids=["packed", "chunked"])
def test_spans_record_every_phase_nested(model, pretuned, token_budget):
    cfg, params = model
    log = SpanLog()
    eng, steps = _serve(cfg, params, token_budget=token_budget, spans=log,
                        blocks="auto")
    parents = collections.defaultdict(set)
    for name, t0, t1, parent in log.records:
        assert t0 <= t1
        parents[name].add(parent)
    want = STEP_CHILDREN | ({"serve.pack"} if token_budget else set())
    assert {n for n, ps in parents.items() if ps == {"serve.step"}} == want
    assert {n for n, ps in parents.items() if ps == {"serve.init"}} \
        == INIT_CHILDREN
    assert parents["serve.land.sync"] == {"serve.land"}
    assert parents["serve.step"] == parents["serve.init"] == {None}
    assert len(log.durations("serve.step")) == steps
    assert len(log.durations("serve.init")) == 1
    assert log.durations("serve.decode.sync") \
        and len(log.durations("serve.decode")) == eng.decode_calls
    # a span is logged when it closes, so each serve.step follows its
    # children, and they lie inside it
    kids = []
    for name, t0, t1, parent in log.records:
        if parent == "serve.step":
            kids.append((t0, t1))
        elif name == "serve.step":
            assert kids and all(t0 <= a <= b <= t1 for a, b in kids)
            kids = []
    assert not kids


def test_spans_off_records_nothing_and_serves_the_same_tokens(model):
    cfg, params = model
    log = SpanLog()
    on, _ = _serve(cfg, params, spans=log)
    off, _ = _serve(cfg, params)
    assert log.records
    assert off._spans is None
    assert off._span("serve.step") is spans_mod.OFF
    got = {r.rid: r.out.tolist() for r in off.done}
    assert got == {r.rid: r.out.tolist() for r in on.done}
    assert set(got) == set(range(len(LENGTHS)))


@pytest.mark.parametrize("token_budget", [16, 0], ids=["packed", "chunked"])
def test_admission_stamps_ordered_on_the_engine_clock(model, token_budget):
    cfg, params = model
    ticks = iter(range(1, 1_000_000))
    log = SpanLog()
    eng, _ = _serve(cfg, params, token_budget=token_budget, spans=log,
                    clock=lambda: float(next(ticks)))
    assert len(eng.done) == len(LENGTHS)
    for r in eng.done:
        assert r.t_submit <= r.t_admitted < r.t_first <= r.t_done, r.rid
        # the two parts add up to the time to first token
        assert (r.t_admitted - r.t_submit) + (r.t_first - r.t_admitted) \
            == r.t_first - r.t_submit
    # spans read the same fake clock: whole ticks, increasing in log order
    # of their closes
    ends = [t1 for _, _, t1, _ in log.records]
    assert all(float(t).is_integer() for t in ends)
    assert ends == sorted(ends)


def test_step_host_time_leaves_out_the_device_reads():
    now = [0.0]
    log = SpanLog()
    log.clock = lambda: now[0]

    def tick(dt):
        now[0] += dt

    for sync in (0.5, 2.0):
        with log.span("serve.step"):
            tick(1.0)
            with log.span("serve.land"):
                with log.span("serve.land.sync"):
                    tick(sync)
                tick(0.25)
            with log.span("serve.decode.sync"):
                tick(3.0)
            tick(0.75)
    assert log.step_host_s() == [2.0, 2.0]
    assert log.durations("serve.step") == [5.5, 7.0]


def test_span_log_is_bounded():
    log = SpanLog(maxlen=3)
    for _ in range(5):
        with log.span("serve.step"):
            pass
    assert len(log.records) == 3


def test_protected_sites_trace_under_their_names():
    rng = np.random.default_rng(0)
    ctx = FTContext(registry=PlanRegistry(make_plan(4, 32)), scope="all",
                    use_pallas=False)
    x = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    xe = jnp.asarray(rng.normal(size=(3, 4, 32)).astype(np.float32))
    we = jnp.asarray(rng.normal(size=(3, 32, 16)).astype(np.float32))

    def f(x, w, xe, we):
        a = ctx.matmul("out.o", x, w)
        g, u = ctx.matmul_fanout(("mlp.gate", "mlp.up"), x, (w, w))
        e = ctx.matmul_grouped("moe.down", xe, we)
        return a + g + u, e

    text = jax.jit(f).lower(x, w, xe, we).as_text(debug_info=True)
    for scope in ("ft.out.o", "ft.mlp.gate+mlp.up", "ft.mlp.gate",
                  "ft.mlp.up", "ft.moe.down"):
        assert scope in text, scope
