"""launch/serve argument validation: FT/admission misconfigurations must
die at PARSE time with a clear message — not deep inside engine startup
or a traced step."""
import sys

import pytest

from repro.launch import serve as launch_serve


def _argv(*extra):
    return ["prog", "--arch", "llama3.2-1b", "--smoke", *extra]


@pytest.mark.parametrize("extra,msg", [
    (["--failed-group", "1"], "requires --ft-mode entangle"),
    (["--ft-mode", "entangle", "--failed-group", "4"], "--ft-M"),
    (["--ft-mode", "entangle", "--failed-group", "7", "--ft-M", "4"],
     "--ft-M"),
    (["--ft-mode", "entangle", "--ft-M", "3"], "divisible"),  # max_batch 4
    (["--ft-mode", "entangle", "--ft-M", "2", "--max-batch", "4"], ">= 3"),
    (["--ft-scope", "everything"], "invalid choice"),
    (["--prefill-chunk", "-3"], "prefill-chunk"),
    (["--token-budget", "-8"], "--token-budget"),
    (["--token-budget", "16"], "requires --prefill-chunk > 0"),
    (["--token-budget", "12", "--prefill-chunk", "8"], "multiple"),
    (["--token-budget", "64", "--prefill-chunk", "8", "--max-batch", "4"],
     "max-batch"),
    (["--prefill-buckets", "8,banana"], "comma-separated"),
    (["--prefill-buckets", "8,512", "--max-seq", "64"], "max-seq"),
    (["--arrival-rate", "-1.5"], "--arrival-rate"),
    (["--deadline-ms", "0"], "--deadline-ms"),
    (["--deadline-ms", "-250"], "--deadline-ms"),
    (["--replicas", "0"], "--replicas"),
    (["--kill-replica-at", "5"], "--replicas >= 2"),  # default pool of 1
    (["--replicas", "4", "--kill-replica-at", "20000"], "drain bound"),
    (["--replicas", "4", "--kill-replica-at", "5", "--kill-replica", "7"],
     "initial pool"),
    (["--replicas", "2", "--kill-replica", "1"], "--kill-replica-at"),
    (["--replicas", "4", "--max-replicas", "2"], "--max-replicas"),
    (["--scale-up-depth", "0"], "--scale-up-depth"),
    (["--trace-dir", "t", "--replicas", "2"], "single-engine"),
])
def test_bad_args_fail_at_parse_time(monkeypatch, capsys, extra, msg):
    monkeypatch.setattr(sys, "argv", _argv(*extra))
    with pytest.raises(SystemExit) as e:
        launch_serve.main()
    assert e.value.code == 2, "argparse .error exits with code 2"
    assert msg in capsys.readouterr().err


def test_steady_state_flags_accepted_at_parse_time(monkeypatch, capsys):
    """Valid --arrival-rate / --deadline-ms / --no-refill combinations
    parse cleanly: the parser takes them and dies on the NEXT invalid
    flag, proving their validation passed."""
    monkeypatch.setattr(sys, "argv", _argv(
        "--arrival-rate", "4.0", "--deadline-ms", "500", "--no-refill",
        "--prefill-chunk", "-1"))
    with pytest.raises(SystemExit) as e:
        launch_serve.main()
    assert e.value.code == 2
    assert "prefill-chunk" in capsys.readouterr().err


def test_token_budget_accepted_at_parse_time(monkeypatch, capsys):
    """A valid --token-budget / --prefill-chunk pairing parses cleanly:
    the parser takes it and dies on the NEXT invalid flag, proving the
    packed-geometry validation passed."""
    monkeypatch.setattr(sys, "argv", _argv(
        "--token-budget", "32", "--prefill-chunk", "8",
        "--arrival-rate", "-1"))
    with pytest.raises(SystemExit) as e:
        launch_serve.main()
    assert e.value.code == 2
    assert "arrival-rate" in capsys.readouterr().err


def test_fleet_flags_accepted_at_parse_time(monkeypatch, capsys):
    """A valid fleet configuration — pool of 4, kill schedule inside the
    drain bound, autoscaling bounds above the pool — parses cleanly: the
    parser takes it and dies on the NEXT invalid flag, proving every
    fleet cross-flag contract passed."""
    monkeypatch.setattr(sys, "argv", _argv(
        "--replicas", "4", "--kill-replica-at", "12", "--kill-replica", "2",
        "--max-replicas", "6", "--scale-up-depth", "3",
        "--prefill-chunk", "-1"))
    with pytest.raises(SystemExit) as e:
        launch_serve.main()
    assert e.value.code == 2
    assert "prefill-chunk" in capsys.readouterr().err


def test_new_scopes_accepted_at_parse_time(monkeypatch, capsys):
    """'out' and 'moe' are real choices now — the parser takes them and
    dies on the NEXT invalid flag, proving scope validation passed."""
    for scope in ("out", "moe", "all"):
        monkeypatch.setattr(sys, "argv", _argv(
            "--ft-mode", "entangle", "--ft-scope", scope,
            "--prefill-chunk", "-1"))
        with pytest.raises(SystemExit) as e:
            launch_serve.main()
        assert e.value.code == 2
        assert "prefill-chunk" in capsys.readouterr().err


@pytest.mark.parametrize("env_set", [False, True], ids=["fixed", "env"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_set):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR, where
    set, is left to JAX (no other directory is configured); otherwise the
    cache goes to ``<checkout>/.jax_cache``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    env_dir = str(tmp_path / "from_env")
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = enable_compile_cache(tmp_path)
        if env_set:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(tmp_path / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()


def test_trace_dir_writes_a_trace_and_prints_the_spans(monkeypatch, capsys,
                                                       tmp_path):
    """--trace-dir: the wave is traced with the engine's spans on the
    profiler's host plane, and the span summary is printed."""
    from jax.profiler import ProfileData

    # keep this worker's later compiles out of the persistent cache
    monkeypatch.setattr(launch_serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", _argv(
        "--requests", "3", "--max-new", "3", "--trace-dir", str(tmp_path)))
    launch_serve.main()
    out = capsys.readouterr().out
    assert "median admit wait" in out and "ms per step over" in out
    assert "3/3 requests completed" in out
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"serve.step", "serve.decode", "serve.decode.sync",
            "serve.emit"} <= names
