"""Steady-state serving invariants: mid-flight refill, async streaming,
deadline scheduling.

  * the refill x fail-stop bitwise matrix: steady-state refill admission
    (slots recycled into the LIVE prefill chunk stream) produces tokens
    bit-identical to boundary-quantized admission, per request, for
    dense/ssm/hybrid x ft_scope head/all x an injected fail-stop in every
    group — admission TIMING must never change tokens or break the
    entangled roll-forward;
  * refill genuinely refills: the matrix runs plan new batches while
    earlier batches are still mid-chunk (metrics['refill_admissions']);
  * refill reuses the startup-compiled plans: no new registry entries, no
    CompiledPlans lookup misses, after a full refill wave;
  * recycled-row zeroing rides the landing scatter: ONE _scatter_rows
    dispatch per steady-state step (trace-count), with zero rows merged;
  * the async frontend: submit() returns a handle whose iterator streams
    exactly the request's tokens (driving engine.step() on demand);
    cancel() works queued / mid-prefill / decoding; deadline_ms sheds
    loudly (DeadlineExceeded) under a fake clock; max_queue rejects with
    a typed AdmissionRejected; EDF orders admission by deadline; EOS ends
    a request early.
"""
import numpy as np
import jax
import pytest

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.serve import (AdmissionRejected, DeadlineExceeded, Request,
                         ServeConfig, ServeEngine)

RNG = np.random.default_rng(31)
_PARAMS_CACHE: dict = {}


def _setup(arch: str, max_seq: int = 48):
    if arch not in _PARAMS_CACHE:
        cfg = get_smoke_config(arch)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0), cfg, max_seq=max_seq)
        _PARAMS_CACHE[arch] = (cfg, model, params)
    return _PARAMS_CACHE[arch]


def _prompts(cfg, lengths):
    return [RNG.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
            for n in lengths]


# staggered wave engineered so refill really happens mid-flight: the
# length-12 prompt buckets to 16 (2 chunks of 8), and while it is being
# chunked the short early finishers (staggered max_new) free slots that
# the tail of the queue refills — impossible under boundary admission.
LENGTHS = [5, 6, 12, 3, 4, 6]
MAX_NEW = [1, 2, 3, 2, 1, 2]
BUCKETS = (8, 16)


def _run(cfg, params, *, refill, scope="head", ft=True, failed_group=None,
         lengths=LENGTHS, max_new=MAX_NEW):
    global RNG
    RNG = np.random.default_rng(31)  # same prompts for every variant
    scfg = ServeConfig(max_batch=4, max_seq=48, prefill_chunk=8,
                       prefill_buckets=BUCKETS, refill=refill,
                       **({"ft_mode": "entangle", "ft_M": 4,
                           "ft_scope": scope} if ft else {}))
    eng = ServeEngine(cfg, scfg, params)
    for r, p in enumerate(_prompts(cfg, lengths)):
        eng.submit(Request(rid=r, prompt=p, max_new=max_new[r]))
    eng.run_to_completion(max_steps=500, failed_group=failed_group)
    return {r.rid: np.asarray(r.out) for r in eng.done}, eng


@pytest.mark.parametrize("scope", ["head", "all"])
@pytest.mark.parametrize(
    "arch", ["llama3.2-1b", "falcon-mamba-7b", "recurrentgemma-2b"])
def test_refill_failstop_bitwise_matrix(arch, scope):
    """Refill vs boundary admission, healthy AND with a fail-stop injected
    into every group: identical tokens per request. Quantization is per
    row and slot -> group is positional, so WHEN a slot was refilled can
    never move another request's integer grid — admission timing is
    token-transparent and the roll-forward stays bit-exact."""
    cfg, _, params = _setup(arch)
    boundary, beng = _run(cfg, params, refill=False, scope=scope)
    assert set(boundary) == set(range(len(LENGTHS)))
    assert beng.metrics["refill_admissions"] == 0  # truly boundary
    for fg in range(4):
        out, eng = _run(cfg, params, refill=True, scope=scope,
                        failed_group=fg)
        assert eng.metrics["refill_admissions"] > 0, \
            "matrix never exercised a mid-flight refill"
        for r in boundary:
            np.testing.assert_array_equal(
                boundary[r], out[r],
                err_msg=f"{arch} scope={scope} failed_group={fg} rid={r} "
                        f"(refill or roll-forward changed tokens)")


def test_refill_reuses_compiled_plans_no_retrace():
    """A refill wave must replay the census'd [Bp, bucket] chunk programs:
    zero CompiledPlans lookup misses and zero NEW registry entries after
    the wave — refill never retraces and never creates a plan."""
    cfg, _, params = _setup("llama3.2-1b")
    RNGsave = np.random.default_rng(31)
    scfg = ServeConfig(max_batch=4, max_seq=48, prefill_chunk=8,
                       prefill_buckets=BUCKETS,
                       ft_mode="entangle", ft_M=4, ft_scope="all")
    eng = ServeEngine(cfg, scfg, params)
    n_entries = len(eng.registry.census())
    for r, n in enumerate(LENGTHS):
        eng.submit(Request(
            rid=r, prompt=RNGsave.integers(0, cfg.vocab_size, n)
            .astype(np.int32), max_new=MAX_NEW[r]))
    eng.run_to_completion(max_steps=500)
    assert eng.metrics["refill_admissions"] > 0
    assert eng.plans.misses == 0, \
        "refill requested a shape the startup census missed"
    assert len(eng.registry.census()) == n_entries, \
        "refill created new plan-registry entries (lazy fallback ran)"


def test_recycle_zeroing_rides_landing_scatter():
    """Satellite fix: recycled-row zeroing and the admission insert share
    ONE batched _scatter_rows dispatch — a landing chunk's scatter carries
    the pending zero rows in its spare capacity (zero mask)."""
    cfg, _, params = _setup("llama3.2-1b")
    eng = ServeEngine(cfg, ServeConfig(max_batch=4, max_seq=48), params)
    for r, p in enumerate(_prompts(cfg, [5, 6])):
        eng.submit(Request(rid=r, prompt=p, max_new=2))
    before = eng.scatter_calls
    eng.step()  # land A+B (scatter 1), decode to max_new -> both recycle
    assert eng.scatter_calls == before + 1
    eng.submit(Request(rid=2, prompt=_prompts(cfg, [4])[0], max_new=2))
    eng.step()  # C lands on one freed slot; the OTHER dirty slot rides
    #             the same scatter as a zero row — no extra dispatch
    assert eng.scatter_calls == before + 2
    assert eng.metrics["merged_zero_rows"] == 1
    assert eng.scatter_calls == eng.metrics["landings"], \
        "a separate recycle flush ran despite landing spare capacity"
    done = eng.run_to_completion(max_steps=50)
    assert len(done) == 3


def test_landing_scatter_rows():
    """The batched landing scatter, row by row: a valid row lands in its
    slot, a zero row writes zeros there, an invalid row leaves its slot
    as it was, and slots no row names are untouched."""
    big = {"a": np.arange(2 * 6 * 3, dtype=np.float32).reshape(2, 6, 3),
           "b": np.arange(2 * 6, dtype=np.int32).reshape(2, 6) + 100}
    small = {"a": -np.ones((2, 4, 3), np.float32),
             "b": -np.ones((2, 4), np.int32)}
    sids = np.array([4, 1, 0, 5], np.int32)
    valid = np.array([True, True, False, True])
    zero = np.array([False, True, False, False])
    out = ServeEngine._scatter_rows_impl(
        None, jax.tree.map(jax.numpy.asarray, big), small, sids, valid, zero)
    for k in big:
        want = big[k].copy()
        want[:, 4] = -1
        want[:, 1] = 0
        want[:, 5] = -1
        np.testing.assert_array_equal(np.asarray(out[k]), want)


def test_handle_iterator_streams_and_drives_engine():
    """submit() returns a handle; iterating it drives engine.step() on
    demand and yields exactly the tokens the request finished with."""
    cfg, _, params = _setup("llama3.2-1b")
    eng = ServeEngine(cfg, ServeConfig(max_batch=2, max_seq=48), params)
    h0 = eng.submit(Request(rid=0, prompt=_prompts(cfg, [5])[0], max_new=4))
    h1 = eng.submit(Request(rid=1, prompt=_prompts(cfg, [7])[0], max_new=6))
    streamed0 = list(h0)  # no manual step() calls anywhere
    assert h0.done and h0.status == "done"
    np.testing.assert_array_equal(np.asarray(streamed0), h0.req.out)
    assert len(streamed0) == 4
    streamed1 = list(h1.tokens())
    np.testing.assert_array_equal(np.asarray(streamed1), h1.req.out)
    assert h1.result() is h1.req and len(streamed1) == 6


def test_cancel_in_every_state():
    """cancel() queued: leaves the queue untouched-by-compute; cancel()
    mid-prefill: the row never claims a slot and its reservation frees
    immediately; cancel() decoding: partial output finalizes and the slot
    recycles for the next tenant."""
    cfg, _, params = _setup("llama3.2-1b")
    eng = ServeEngine(cfg, ServeConfig(max_batch=2, max_seq=48,
                                       prefill_chunk=8), params)
    # queued: cancel before any step
    hq = eng.submit(Request(rid=0, prompt=_prompts(cfg, [5])[0], max_new=4))
    hq.cancel()
    assert hq.status == "cancelled" and not eng.queue
    assert list(hq) == [] and len(hq.req.out) == 0
    # mid-prefill: bucket 32 -> 4 chunks; cancel after the first chunk
    hp = eng.submit(Request(rid=1, prompt=_prompts(cfg, [30])[0], max_new=4))
    eng.step()
    assert hp.status == "prefill" and eng._inflight
    hp.cancel()
    assert hp.status == "cancelled"
    # the engine still serves others; the cancelled row never lands
    hd = eng.submit(Request(rid=2, prompt=_prompts(cfg, [6])[0], max_new=5))
    done = eng.run_to_completion(max_steps=100)
    assert [r.rid for r in done] == [2] and len(hd.req.out) == 5
    assert all(s is None for s in eng.slots) and not eng._reserved
    # decoding: cancel after a couple of generated tokens
    hx = eng.submit(Request(rid=3, prompt=_prompts(cfg, [5])[0], max_new=16))
    eng.step()
    eng.step()
    assert hx.status == "decoding"
    hx.cancel()
    assert hx.status == "cancelled" and 1 <= len(hx.req.out) < 16
    assert all(s is None for s in eng.slots)
    assert eng.metrics["cancelled"] == 3


def test_deadline_shed_is_loud():
    """A queued request whose deadline_ms lapses before admission is shed
    BEFORE any prefill compute is spent on it; iterating its handle raises
    DeadlineExceeded. Admitted requests are never shed."""
    cfg, _, params = _setup("llama3.2-1b")
    now = [0.0]
    eng = ServeEngine(cfg, ServeConfig(max_batch=1, max_seq=48,
                                       clock=lambda: now[0]), params)
    busy = eng.submit(Request(rid=0, prompt=_prompts(cfg, [5])[0],
                              max_new=8, deadline_ms=50.0))
    eng.step()  # rid0 admitted: its deadline no longer applies
    hs = eng.submit(Request(rid=1, prompt=_prompts(cfg, [5])[0],
                            max_new=4, deadline_ms=10.0))
    now[0] = 1.0  # 1000 ms later: rid1's 10 ms budget is long gone
    pre = eng.prefill_calls
    eng.step()
    assert hs.status == "shed" and eng.prefill_calls == pre
    assert eng.metrics["shed"] == 1
    with pytest.raises(DeadlineExceeded, match="rid=1"):
        list(hs)
    # the admitted request survives its own (lapsed) deadline
    assert busy.result().status == "done" and len(busy.req.out) == 8


def test_admission_rejected_at_saturation():
    """max_queue bounds the wait queue with a typed rejection."""
    cfg, _, params = _setup("llama3.2-1b")
    eng = ServeEngine(cfg, ServeConfig(max_batch=1, max_seq=48,
                                       max_queue=2), params)
    for r in range(2):
        eng.submit(Request(rid=r, prompt=_prompts(cfg, [5])[0], max_new=2))
    with pytest.raises(AdmissionRejected, match="max_queue"):
        eng.submit(Request(rid=2, prompt=_prompts(cfg, [5])[0], max_new=2))
    assert eng.metrics["rejected"] == 1
    assert eng.metrics["queue_depth_peak"] == 2
    done = eng.run_to_completion(max_steps=100)
    assert sorted(r.rid for r in done) == [0, 1]


def test_edf_orders_admission_by_deadline():
    """Earliest-deadline-first: with one slot, the tightest deadline is
    admitted first however late it was submitted; deadline-less requests
    rank last (FIFO among themselves — the legacy order)."""
    cfg, _, params = _setup("llama3.2-1b")
    now = [0.0]
    eng = ServeEngine(cfg, ServeConfig(max_batch=1, max_seq=48,
                                       clock=lambda: now[0]), params)
    eng.submit(Request(rid=0, prompt=_prompts(cfg, [5])[0], max_new=1))
    eng.submit(Request(rid=1, prompt=_prompts(cfg, [5])[0], max_new=1,
                       deadline_ms=1e6))
    eng.submit(Request(rid=2, prompt=_prompts(cfg, [5])[0], max_new=1,
                       deadline_ms=1e3))
    done = eng.run_to_completion(max_steps=100)
    assert [r.rid for r in done] == [2, 1, 0]


def test_eos_token_ends_request_early():
    """Request.eos_token stops decode at the first EOS emission — the
    slot recycles into the refill stream right then, not at max_new."""
    cfg, _, params = _setup("llama3.2-1b")
    prompt = _prompts(cfg, [6])[0]
    eng = ServeEngine(cfg, ServeConfig(max_batch=1, max_seq=48), params)
    eng.submit(Request(rid=0, prompt=prompt.copy(), max_new=8))
    ref = eng.run_to_completion(max_steps=50)[0].out
    eos = int(ref[2])  # greedy decode is deterministic: rerun stops here
    stop = int(np.argmax(ref == eos))  # first occurrence (index <= 2)
    eng2 = ServeEngine(cfg, ServeConfig(max_batch=1, max_seq=48), params)
    eng2.submit(Request(rid=0, prompt=prompt.copy(), max_new=8,
                        eos_token=eos))
    out = eng2.run_to_completion(max_steps=50)[0].out
    np.testing.assert_array_equal(out, ref[: stop + 1])
