"""The harness end to end on the CPU at the program's smoke widths: a
sound run is correct, a run with its timed path broken underneath is not,
the control fails the limit, and ``run.py`` prints no result without a
TPU or without the program.

The look for a chip is skipped by asking for the CPU platform; the
configuration, traffic and peak files are the test-size ones under
``data/tiny`` (the peak entry there is test data, not a measured peak).
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spec  # noqa: E402

TINY = pathlib.Path(__file__).parent / "data" / "tiny"
SEED = 2**33 + 77


@pytest.fixture(autouse=True)
def tiny_root(monkeypatch):
    monkeypatch.setattr(spec, "ROOT", TINY)


def run(cell, hook=None, seed=SEED, control=False, trace=False):
    return harness.run_cell(cell, seed, 1.5, trace, time.monotonic(),
                            platform="cpu", hook=hook, control=control)


CELLS = ["tiny-dense.chat", "tiny-mamba.batch", "tiny-dense-share.chat"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    names = {m["name"] for m in spec.metrics_for(spec.load_benchmark(), cell,
                                                 "end_to_end")}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["checks"]["compiles_in_window"]["value"] == 0


def test_traced_run_reports_host_layers():
    res = run("tiny-dense.chat", trace=True)
    assert res["correct"], res["checks"]
    # the CPU has no device trace to read: only the host's metrics remain
    host = {m["name"] for m in spec.metrics_for(spec.load_benchmark(),
                                                "tiny-dense.chat",
                                                "per_layer")
            if m["source"] != "device_trace"}
    assert host and set(res["metrics"]) == host
    assert res["device"]["window_s"] > 0


def _wrap_decode(fault):
    """Break the decode step underneath the harness: return the cache it
    was given (state unchanged), copy each even row's token into the next
    row (half of the batch left out), add one to every token of every
    third step (tokens altered where they are produced), or add one to
    every token of slot 0 alone (a fault confined to one slot)."""
    def hook(engine):
        orig = engine._decode
        calls = [0]

        def broken(params, cache, last_tok, pos, active, head, **kw):
            nxt, new = orig(params, cache, last_tok, pos, active, head, **kw)
            calls[0] += 1
            if fault == "state_unchanged":
                return nxt, cache
            if fault == "half_batch":
                return nxt.at[1::2].set(nxt[0::2]), new
            if fault == "token_altered":
                if calls[0] % 3 == 0:
                    nxt = (nxt + 1) % engine.cfg.vocab_size
                return nxt, new
            if fault == "one_slot":
                return nxt.at[0].set((nxt[0] + 1) % engine.cfg.vocab_size), new
            raise ValueError(fault)

        engine._decode = broken
    return hook


def _gap_checks(res, key="checks"):
    """The cell's compared gap numbers, without the run's other checks."""
    return {k: v for k, v in res[key].items()
            if k not in ("compiles_in_window", "unfinished_due")}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered", "one_slot"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault):
    res = run(cell, hook=_wrap_decode(fault))
    assert not res["correct"]
    failed = [k for k, v in _gap_checks(res).items()
              if v["value"] > v["limit"]]
    assert failed, res["checks"]
    if fault == "one_slot" and cell == "tiny-dense-share.chat":
        assert "max_request_share_over_0.06" in failed


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(cell):
    res = run(cell, control=True)
    assert res["correct"], res["checks"]
    assert res["control_correct"] is False
    for k, v in _gap_checks(res, "control_checks").items():
        assert v["limit"] == res["checks"][k]["limit"]
        assert v["value"] > 3 * v["limit"], (k, v)


@pytest.mark.parametrize("config", ["qwen2-7b", "falcon-mamba-7b"])
def test_one_ruined_request_fails_the_request_share(config, monkeypatch):
    """At the protected chip cells' sample sizes, one request of 128
    served tokens ruined among sound ones stays under the pooled share's
    limit and fails the per-request share's."""
    import numpy as np

    from bench import check

    monkeypatch.setattr(spec, "ROOT", ROOT)
    chk = spec.load_config(spec.load_benchmark(), config)["check"]
    rng = np.random.default_rng(0)
    n = chk["sample_tokens"] // 128
    sound = [np.where(rng.random(128) < 0.004, 0.1, 0.01)
             for _ in range(n - 1)]
    runs = {"sound": sound, "one ruined": sound + [np.full(128, 0.5)]}
    got = {k: {name: check.NUMBERS[name](rs, chk) <= lim
               for name, lim in chk["limits"].items()}
           for k, rs in runs.items()}
    assert all(got["sound"].values()), got
    assert got["one ruined"] == {"share_gap_over_0.06": True,
                                 "max_request_share_over_0.06": False}


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, str(cwd / "bench" / "run.py"), "--workload",
         "qwen2-7b.chat.ft-all", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no tpu" in p.stderr.lower()


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
