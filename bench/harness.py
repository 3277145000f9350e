"""One run of one cell: set-up, ramp, measured window, the check that
decides ``correct``, and the result line.

The program under test is ``repro.serve.engine.ServeEngine``; the harness
drives its ``submit``/``step`` and reads its requests' token times, its
step return values and counters, and the device trace. The weights, the
traffic, the reference and all the arithmetic are the benchmark's own.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import json
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import check, opcount, spec, stats, traffic
from bench import trace as trace_mod

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


class HarnessError(RuntimeError):
    """The run could not be measured as the cell states it."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class CompileCounter:
    """Counts traces and backend compiles, so the window can be checked to
    hold none."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.n += 1
            self.seconds += secs


def devices(platform: str, chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"JAX finds no {platform} (platform "
                     f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs[:chips]


def prng_key(seed: int):
    import jax

    lo, hi = traffic.seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    arch: object
    peaks: dict

    @property
    def serve(self) -> dict:
        return self.config["serve"]

    @property
    def protected(self) -> bool:
        return self.serve.get("ft_mode", "none") == "entangle"

    @property
    def peak_ops(self) -> float:
        """The peak of the operand type the cell's GEMMs run in."""
        return self.peaks["int8_ops_per_s" if self.protected
                          else "bf16_flops_per_s"]


def load_cell(name: str, kind: str = None) -> Cell:
    bm = spec.load_benchmark()
    w = spec.cell(bm, name)
    config = spec.load_config(bm, w["config"])
    mix = spec.load_traffic(w["traffic"])
    arch = spec.arch_module(config["arch"])
    peaks = spec.load_peaks()
    peak = None
    if kind is not None:
        peak = peaks["devices"].get(kind)
        if peak is None:
            raise HarnessError(f"device {kind!r} has no entry in "
                               f"bench/peaks.json")
    return Cell(name, w["chips"], config, mix, arch, peak)


def program_objects(cell: Cell):
    """The program's model config and serving config for the cell."""
    from repro.configs import get_config, get_smoke_config
    from repro.serve.engine import ServeConfig

    get = (get_smoke_config if cell.config.get("program_size") == "smoke"
           else get_config)
    base = get(cell.config["program_arch"])
    cfg = cell.arch.program_config(base, cell.config)
    scfg = ServeConfig(**cell.serve)
    return cfg, scfg


def make_params(cell: Cell, seed: int, device):
    """The weights, made on the device from the seed in one jitted call."""
    import functools

    import jax

    init = jax.jit(functools.partial(cell.arch.init_params, c=cell.config))
    return jax.block_until_ready(init(jax.device_put(prng_key(seed),
                                                     device)))


def check_layout(cell: Cell, params, cfg, max_seq: int) -> None:
    """The benchmark's weights must have exactly the program's layout, and
    the architecture module's GEMM sites (what the operation counts count)
    may not hold more weights than the program has outside the embedding
    and the head."""
    import jax

    from repro.models import get_model

    want = jax.eval_shape(lambda k: get_model(cfg).init(k, cfg, max_seq),
                          jax.random.PRNGKey(0))
    nonemb = opcount.param_counts(want)["nonemb"]
    sites = sum(s["layers"] * s["K"] * s["N"]
                for s in cell.arch.gemm_sites(cell.config)
                if s["site"] != "head")
    log(f"weights outside embedding and head: program {nonemb}, GEMM "
        f"sites {sites}")
    if sites > nonemb:
        raise HarnessError(f"GEMM sites hold {sites} weights, more than the "
                           f"program's {nonemb} outside embedding and head")
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise HarnessError("the benchmark's weights do not have the "
                           "program's parameter layout")


def warm_requests(cell: Cell, scfg, vocab: int) -> list:
    """Requests that drive every program of the cell once: prompts longer
    than one packed chunk, co-packed admissions, staggered finishes (slot
    recycling with and without a landing in the same step)."""
    from repro.serve.engine import Request

    rng = np.random.default_rng(0)
    C = scfg.prefill_chunk or 64
    rows = max(scfg.token_budget // C, 1) if scfg.token_budget else 2
    pmax = cell.mix["prompt"]["max"]
    lens = [min(x, pmax) for x in (C + 1, 17, 2 * C + 5, C)]
    out = []
    for i in range(2 * rows + 2):
        out.append(Request(rid=-1 - i, max_new=2 + i % 4,
                           prompt=rng.integers(0, vocab, lens[i % 4],
                                               dtype=np.int32)))
    return out


class Tracer:
    """Profiles ``seconds`` of the window from its start."""

    def __init__(self, enabled: bool, seconds: float):
        self.enabled, self.seconds = enabled, seconds
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if enabled \
            else None
        self.t_start = self.t_stop = None
        self.c_start = self.c_stop = None

    def tick(self, now: float, window_start: float, engine) -> None:
        import jax

        if not self.enabled or self.t_stop is not None:
            return
        if self.t_start is None and now >= window_start:
            jax.profiler.start_trace(self.dir)
            self.t_start = time.monotonic()
            self.c_start = counters(engine)
        elif self.t_start is not None and now >= self.t_start + self.seconds:
            jax.profiler.stop_trace()
            self.t_stop = time.monotonic()
            self.c_stop = counters(engine)

    def finish(self, engine) -> None:
        import jax

        if self.t_start is not None and self.t_stop is None:
            jax.profiler.stop_trace()
            self.t_stop = time.monotonic()
            self.c_stop = counters(engine)

    def reduce(self):
        if not self.enabled:
            return None
        try:
            paths = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            if not paths:
                raise HarnessError("the profiler wrote no trace")
            red = trace_mod.reduce(trace_mod.load(paths[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        red["counters"] = {k: self.c_stop[k] - self.c_start[k]
                           for k in self.c_start}
        red["t_start"], red["t_stop"] = self.t_start, self.t_stop
        return red


def counters(engine) -> dict:
    return {"packed_tokens": engine.metrics["packed_tokens"],
            "packed_calls": engine.metrics["packed_calls"],
            "decode_calls": engine.decode_calls,
            "prefill_calls": engine.prefill_calls}


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def drive(engine, specs: list, cell: Cell, seconds: float, tracer: Tracer,
          counter: CompileCounter) -> dict:
    """Offer the mix's load, measure ``seconds`` after the ramp, and (for
    an open loop) keep serving until every request due in the window has
    finished or the drain limit passes."""
    from repro.serve.engine import Request

    mix = cell.mix
    fg = mix.get("failed_group")
    openloop = mix["kind"] == "open_poisson"
    depth = mix.get("queue_depth", 0)
    t0 = time.monotonic()
    w0 = t0 + mix["ramp_s"]
    w1 = w0 + seconds
    hard = w1 + mix.get("drain_s", 0)
    reqs, queued, steps = [], {}, []
    lags = []
    open_due = 0  # requests due in the window and not yet finished
    i = 0
    c_win0 = c_win1 = None
    while True:
        now = time.monotonic()
        if c_win0 is None and now >= w0:
            c_win0 = (counter.n, counters(engine))
        if c_win1 is None and now >= w1:
            c_win1 = (counter.n, counters(engine))
        if openloop:
            while i < len(specs) and t0 + specs[i]["due"] <= now:
                s = specs[i]
                r = Request(rid=s["rid"], prompt=s["prompt"],
                            max_new=s["max_new"])
                due = t0 + s["due"]
                with annotate("bench.submit"):
                    engine.submit(r)
                lag = time.monotonic() - due
                win = w0 <= due < w1
                open_due += win
                reqs.append({"spec": s, "req": r, "due": due, "win": win,
                             "lag": lag, "t_left": None})
                lags.append(lag)
                queued[len(reqs) - 1] = r
                i += 1
        else:
            while len(queued) < depth and i < len(specs):
                s = specs[i]
                r = Request(rid=s["rid"], prompt=s["prompt"],
                            max_new=s["max_new"])
                with annotate("bench.submit"):
                    engine.submit(r)
                reqs.append({"spec": s, "req": r, "due": t0, "win": False,
                             "lag": 0.0, "t_left": None})
                queued[len(reqs) - 1] = r
                i += 1
            if i == len(specs) and not queued and now < w1:
                raise HarnessError("the backlog emptied inside the window")
        if now >= w1 and (not openloop or open_due == 0 or now >= hard):
            break
        if engine.idle():
            if i >= len(specs):
                break
            nxt = t0 + specs[i]["due"]
            with annotate("bench.wait"):
                time.sleep(max(0.0, min(nxt - time.monotonic(), 0.05)))
            continue
        with annotate("bench.step"):
            active = engine.step(failed_group=fg)
        t = time.monotonic()
        steps.append((t, active))
        for k in list(queued):
            if queued[k].status != "queued":
                reqs[k]["t_left"] = t
                del queued[k]
        if openloop:
            open_due = sum(1 for r in reqs
                           if r["win"] and r["req"].status != "done")
        tracer.tick(t, w0, engine)
    tracer.finish(engine)
    t_end = time.monotonic()
    if c_win1 is None:
        c_win1 = (counter.n, counters(engine))
    return {"t0": t0, "w0": w0, "w1": w1, "t_end": t_end, "reqs": reqs,
            "steps": steps, "lags": lags, "fg": fg,
            "compiles_in_window": c_win1[0] - c_win0[0],
            "win_counters": {k: c_win1[1][k] - c_win0[1][k]
                             for k in c_win0[1]}}


def request_records(run: dict) -> list:
    out = []
    for r in run["reqs"]:
        q = r["req"]
        out.append({"due": r["due"], "win": r["win"], "t_first": q.t_first,
                    "tok_times": list(q.tok_times), "t_left": r["t_left"],
                    "prompt_len": len(r["spec"]["prompt"]),
                    "max_new": r["spec"]["max_new"], "status": q.status,
                    "t_done": q.t_done,
                    "lag": r["lag"]})
    return out


def model_ops(cell: Cell, recs: list, w0: float, w1: float) -> float:
    """Model operations of every token the window processed: each token
    emitted in the window counts one token's GEMMs through every layer and
    the head plus its attention or scan; a first token counts its whole
    prompt (the prompt's GEMMs and attention) and one head projection."""
    sites = cell.arch.gemm_sites(cell.config)
    layer_ops = opcount.gemm_ops_per_token(
        [s for s in sites if s["site"] != "head"])
    head_ops = opcount.gemm_ops_per_token(
        [s for s in sites if s["site"] == "head"])
    ctx = cell.arch.context_ops
    c = cell.config
    ops = 0.0
    for r in recs:
        n = r["prompt_len"]
        for j, t in enumerate(r["tok_times"]):
            if not w0 <= t < w1:
                continue
            if j == 0:
                ops += n * layer_ops + n * ctx(c, (n - 1) / 2) + head_ops
            else:
                ops += layer_ops + head_ops + ctx(c, n + j - 1)
    return ops


def e2e_metrics(cell: Cell, run: dict, recs: list, seconds: float,
                setup_s: float) -> dict:
    w0, w1 = run["w0"], run["w1"]
    out = {"setup_s": setup_s}
    if cell.mix["kind"] == "open_poisson":
        due = [r for r in recs if r["win"]]
        ttft = stats.ttft_ms(due, run["t_end"])
        out["ttft_p50_ms"] = stats.nearest_rank(ttft, 0.50)
        out["ttft_p90_ms"] = stats.nearest_rank(ttft, 0.90)
        out["itl_p95_ms"] = stats.nearest_rank(stats.itl_ms(due), 0.95)
    else:
        n = sum(1 for r in recs for t in r["tok_times"] if w0 <= t < w1)
        out["tokens_per_s"] = n / seconds
    return out


def layer_records(cell: Cell, run: dict, recs: list, red) -> dict:
    """What the per-layer readers read."""
    return {"cell": cell, "config": cell.config, "serve": cell.serve,
            "peak_ops": cell.peak_ops, "peaks": cell.peaks,
            "w0": run["w0"], "w1": run["w1"], "t_end": run["t_end"],
            "requests": recs, "steps": run["steps"], "trace": red,
            "win_counters": run["win_counters"],
            "model_ops": lambda a, b: model_ops(cell, recs, a, b)}


def summary_lines(cell: Cell, run: dict, recs: list) -> None:
    """Medians and counts, on lines before the result."""
    due = [r for r in recs if r["win"]]
    if due:
        log(f"requests due in window {len(due)}; ttft median ms "
            f"{np.median(stats.ttft_ms(due, run['t_end']))}; itl median ms "
            f"{np.median(stats.itl_ms(due)) if stats.itl_ms(due) else None}")
    if run["lags"]:
        log(f"generator lag s: median {np.median(run['lags'])} max "
            f"{max(run['lags'])}")
    occ = [a for t, a in run["steps"] if run["w0"] <= t < run["w1"]]
    log(f"steps in window {len(occ)}; window counters "
        f"{run['win_counters']}; compiles in window "
        f"{run['compiles_in_window']}")


@dataclasses.dataclass
class Setup:
    """A cell's program, built and warmed, ready for traffic."""
    cell: Cell
    devs: list
    cfg: object
    scfg: object
    engine: object
    counter: CompileCounter
    setup_s: float


def setup(name: str, seed: int, t_proc0: float,
          platform: str = "tpu") -> Setup:
    """Make the weights, start the engine and drive each of the cell's
    programs once."""
    from repro.serve.engine import ServeEngine

    devs = devices(platform, load_cell(name).chips)
    dev = devs[0]
    cell = load_cell(name, dev.device_kind)
    counter = CompileCounter()
    cfg, scfg = program_objects(cell)
    need = traffic.max_positions(cell.mix)
    if need > scfg.max_seq:
        raise HarnessError(f"traffic needs {need} positions > max_seq "
                           f"{scfg.max_seq}")
    params = make_params(cell, seed, dev)
    check_layout(cell, params, cfg, scfg.max_seq)
    engine = ServeEngine(cfg, scfg, params, devices=[dev])
    del params
    for r in warm_requests(cell, scfg, cfg.vocab_size):
        engine.submit(r)
    engine.run_to_completion(max_steps=10_000,
                             failed_group=cell.mix.get("failed_group"))
    setup_s = time.monotonic() - t_proc0
    log(f"set-up {setup_s} s ({counter.n} traces/compiles, "
        f"{counter.seconds} s)")
    return Setup(cell, devs, cfg, scfg, engine, counter, setup_s)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_proc0: float, platform: str = "tpu",
             trace_seconds: float = 4.0, hook=None,
             control: bool = False) -> dict:
    """One run; returns the result line's object. ``hook(engine)``, where
    given, runs after the warm-up (tests plant faults through it);
    ``control`` also puts the control's gaps through the check
    (``bench/control.py``)."""
    st = setup(name, seed, t_proc0, platform)
    if hook is not None:
        hook(st.engine)
    return measure(st, seed, seconds, trace, trace_seconds, control)


def measure(st: Setup, seed: int, seconds: float, trace: bool,
            trace_seconds: float = 4.0, control: bool = False) -> dict:
    """Traffic, window, check and result of one set-up; frees the engine
    before the reference runs."""
    cell, dev = st.cell, st.devs[0]
    specs = traffic.generate(cell.mix, seed, seconds, st.cfg.vocab_size)
    tracer = Tracer(trace, trace_seconds)
    run = drive(st.engine, specs, cell, seconds, tracer, st.counter)
    recs = request_records(run)
    summary_lines(cell, run, recs)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    picked = check.sample(cell, run, seed)
    st.engine = None
    gc.collect()
    red = tracer.reduce() if trace else None
    verdict = check.compare(cell, seed, picked, run, dev, control)
    result = {"correct": verdict["correct"],
              "attempted": verdict["attempted"],
              "failed": verdict["failed"]}
    bm = spec.load_benchmark()
    if trace:
        recs_l = layer_records(cell, run, recs, red)
        metrics = {}
        for m in spec.metrics_for(bm, cell.name, "per_layer"):
            v = spec.metric_reader(m["name"])(recs_l)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = e2e_metrics(cell, run, recs, seconds, st.setup_s)
        log("end-to-end " + "; ".join(f"{k} {v}" for k, v in e2e.items()))
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.metrics_for(bm, cell.name, "end_to_end")}
    result["metrics"] = metrics
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(st.devs), "memory_peak_bytes": peak}
    if red is not None:
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    if control:
        result["control_correct"] = verdict["control_correct"]
        result["control_checks"] = verdict["control_checks"]
    result["checks"] = verdict["checks"]
    return result


def print_result(result: dict) -> None:
    for k, v in result["checks"].items():
        print(f"[bench] check {k}: value {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
