"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the run finished -- the one with the most served tokens, then
others drawn from the seed -- is run through the plain reference of the
configuration's architecture (``bench/models/<arch>.py``), teacher-forced
over each prompt and its served tokens. Each served token's gap is how far
its logit lies below the reference's best logit at that position; the
numbers compared are those the configuration's ``check.limits`` names,
each with its limit: the widest gap, the share of tokens more than 0.06
below the best, or the largest such share of any one request (a fault
confined to one slot shows there). The reference takes its own weights,
remade from the seed; it imports nothing of the program.

The reference computes the protected GEMMs as the configuration states
them (int8 weights, the entanglement's activation grid, exact integer
products) and everything else in float32; an unprotected configuration is
float32 throughout. The control (``bench/control.py``) is the same
reference one precision step lower; where a run reads it, its gaps go
through the same limits and come out as ``control_correct``.
"""
from __future__ import annotations

import json

import numpy as np

from bench import traffic


def sample(cell, run: dict, seed: int) -> list:
    """[(prompt, served)] of the finished requests chosen for the check."""
    done = [r for r in run["reqs"] if r["req"].status == "done"]
    done.sort(key=lambda r: r["spec"]["rid"])
    if not done:
        return []
    chk = cell.config["check"]
    first = max(done, key=lambda r: len(r["req"].out))
    rest = [r for r in done if r is not first]
    order = traffic.rng_for(seed, 3).permutation(len(rest))
    picked, tokens = [first], len(first["req"].out)
    for k in order:
        if tokens >= chk["sample_tokens"] or \
                len(picked) >= chk["sample_requests"]:
            break
        picked.append(rest[k])
        tokens += len(rest[k]["req"].out)
    return [(np.asarray(r["spec"]["prompt"]), np.asarray(r["req"].out))
            for r in picked]


def _padded(cell, prompt, served):
    """Sequence, positions and targets at the fixed shapes of the check:
    the sequence padded to ``max_seq``, positions to the mix's longest
    output (pad positions repeat the last one)."""
    T = cell.serve["max_seq"]
    P = cell.mix["output"]["max"]
    seq = np.zeros(T, np.int32)
    full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    seq[:len(full)] = full
    n = len(served)
    pos = np.full(P, len(prompt) + n - 2, np.int32)
    pos[:n] = len(prompt) - 1 + np.arange(n)
    tgt = np.full(P, served[-1], np.int32)
    tgt[:n] = served
    return seq, pos, tgt, n


_GAP_FNS: dict = {}


def _gap_fn(cell, mode, control_mode):
    """Jitted gaps of one sequence: the served tokens' gap below the
    reference's best, and, with a control mode, the gap of the token the
    control puts first."""
    import jax
    import jax.numpy as jnp

    key = (cell.config["arch"], json.dumps(cell.config, sort_keys=True),
           mode, control_mode)
    if key in _GAP_FNS:
        return _GAP_FNS[key]
    arch, c = cell.arch, cell.config

    def gaps(params, seq, pos, tgt):
        ref = arch.reference_logits(params, seq, c, mode)[pos]
        best = jnp.max(ref, axis=-1)
        served = best - jnp.take_along_axis(ref, tgt[:, None], -1)[:, 0]
        if control_mode is None:
            return served, served
        ctl = arch.reference_logits(params, seq, c, control_mode)[pos]
        pick = jnp.argmax(ctl, axis=-1)
        return served, best - jnp.take_along_axis(ref, pick[:, None],
                                                  -1)[:, 0]

    _GAP_FNS[key] = jax.jit(gaps)
    return _GAP_FNS[key]


FAR = 0.06


def _max_request_share(rs, chk) -> float:
    """The largest share of far tokens in any one checked request of at
    least ``request_min_tokens`` served tokens (one far token in a short
    answer is round-off, not a fault)."""
    n = chk.get("request_min_tokens", 1)
    return float(max(np.mean(g > FAR) for g in rs if len(g) >= n))


# each number reads the gaps of the checked requests, one array a request,
# and the configuration's check entry
NUMBERS = {
    "max_logit_gap": lambda rs, chk: float(np.max(np.concatenate(rs))),
    "mean_logit_gap": lambda rs, chk: float(np.mean(np.concatenate(rs))),
    "p99_logit_gap": lambda rs, chk: float(np.quantile(np.concatenate(rs),
                                                       0.99)),
    # share of checked served tokens whose logit lies more than FAR below
    # the reference's best: at the protected cells' coarse activation grids
    # (3 to 18 steps each side) the widest gap of a sound run and of the
    # int4 control differ by under 2x, this share by over 10x (PERF.md)
    "share_gap_over_0.06": lambda rs, chk: float(np.mean(
        np.concatenate(rs) > FAR)),
    # the same share in the one request where it is largest
    "max_request_share_over_0.06": _max_request_share,
}


def reference_gaps(cell, seed: int, picked: list, device,
                   control: bool = False) -> tuple:
    """Gaps of every checked served token, one array a request (and, with
    ``control``, of the token the control puts first at the same
    positions)."""
    from bench import harness

    params = harness.make_params(cell, seed, device)
    mode = cell.config["check"]["reference"]
    fn = _gap_fn(cell, mode, f"{mode}_control" if control else None)
    served, ctl = [], []
    for prompt, tokens in picked:
        seq, pos, tgt, n = _padded(cell, prompt, tokens)
        g, gc_ = fn(params, seq, pos, tgt)
        served.append(np.asarray(g)[:n])
        ctl.append(np.asarray(gc_)[:n])
    del params
    return served, (ctl if control else None)


def _judge(chk: dict, gaps) -> dict:
    return {k: {"value": None if gaps is None else NUMBERS[k](gaps, chk),
                "limit": lim} for k, lim in chk["limits"].items()}


def _passes(checks: dict) -> bool:
    return all(v["limit"] is not None and v["value"] is not None
               and v["value"] <= v["limit"] for v in checks.values())


def compare(cell, seed: int, picked: list, run: dict, device,
            control: bool = False) -> dict:
    chk = cell.config["check"]
    reqs = run["reqs"]
    if cell.mix["kind"] == "open_poisson":
        due = [r for r in reqs if r["win"]]
        attempted = len(due)
        failed = sum(1 for r in due if r["req"].status != "done")
    else:
        attempted = sum(1 for r in reqs if r["due"] < run["w1"])
        failed = sum(1 for r in reqs
                     if r["req"].status in ("shed", "cancelled"))
    g = g_c = None
    if picked:
        g, g_c = reference_gaps(cell, seed, picked, device, control)
        print(f"[bench] reference checked {len(picked)} requests, "
              f"{sum(map(len, g))} served tokens; "
              + "; ".join(f"{k} {f(g, chk)}" for k, f in NUMBERS.items()),
              flush=True)
        if control:
            print("[bench] control: " + "; ".join(
                f"{k} {f(g_c, chk)}" for k, f in NUMBERS.items()), flush=True)
    checks = _judge(chk, g)
    checks["compiles_in_window"] = {"value": run["compiles_in_window"],
                                    "limit": 0}
    if cell.mix["kind"] == "open_poisson":
        checks["unfinished_due"] = {"value": failed, "limit": 0}
    out = {"correct": _passes(checks), "attempted": attempted,
           "failed": failed, "checks": checks}
    if control:
        out["control_checks"] = _judge(chk, g_c)
        out["control_correct"] = _passes(out["control_checks"])
    return out
