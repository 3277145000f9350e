"""From a profiler trace to device busy time, per-program and per-kernel
device time, and idle gaps attributed to host spans.

Two stages, so the second can be checked on a small recorded trace:

* :func:`load` reads an ``.xplane.pb`` into plain event lists;
* :func:`reduce` turns those lists into numbers.

Device events come from the ``/device:TPU:<n>`` planes: the ``XLA Modules``
line (one event per program execution, named ``jit_<function>(<hash>)``)
and the ``XLA Ops`` line (one event per operation, named by its HLO text;
a Pallas kernel is a ``tpu_custom_call`` named after its kernel function).
Host spans are the harness's own ``bench.*`` annotations. The device clock
is aligned to the host's by the program launches: each program's device
start is matched to its host ``DoEnqueueProgram`` by ``run_id``, and the
device events are shifted so that the earliest launch starts with no delay.
"""
from __future__ import annotations

import collections
import re

HOST_SPAN_PREFIX = "bench."
# control-flow ops whose events span the ops of their bodies: counted in
# the busy union, left out of the list of operations that took most time
CONTAINERS = ("while", "conditional", "call")


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(path: str) -> dict:
    """Plain events of one trace: ``devices`` maps each device plane to its
    ``modules`` and ``ops`` ([name, start_ns, dur_ns]) and ``runs``
    ([run_id, start_ns]); ``spans`` holds the host ``bench.*`` spans and
    ``enqueues`` the host program launches ([run_id, start_ns])."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {"devices": {}, "spans": [], "enqueues": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": [], "runs": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        dev["modules"].append(
                            [ev.name, ev.start_ns, ev.duration_ns])
                        rid = _stat(ev, "run_id")
                        if rid is not None:
                            dev["runs"].append([int(rid), ev.start_ns])
                elif line.name == "XLA Ops":
                    dev["ops"].extend([ev.name, ev.start_ns, ev.duration_ns]
                                      for ev in line.events)
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        out["spans"].append(
                            [ev.name, ev.start_ns, ev.duration_ns])
                    elif ev.name == "DoEnqueueProgram":
                        rid = _stat(ev, "run_id")
                        if rid is not None:
                            out["enqueues"].append([int(rid), ev.start_ns])
    return out


def module_name(name: str) -> str:
    """``jit__decode_impl(8812)`` -> ``jit__decode_impl``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str) -> str:
    """HLO text ``%fusion.12 = ...`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def kernel_name(name: str):
    """The kernel function of a Pallas custom call, or None:
    ``%entangled_matmul_pallas.3 = ... custom_call_target="tpu_custom_call"``
    -> ``entangled_matmul_pallas``."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    return re.sub(r"\.\d+$", "", op_name(name))


def union(intervals) -> list:
    """Merge [start, end] intervals into disjoint sorted ones."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clock_offset(dev: dict, enqueues) -> float:
    """Nanoseconds to add to device times to put them on the host clock."""
    host = {rid: t for rid, t in enqueues}
    lags = [t - host[rid] for rid, t in dev["runs"] if rid in host]
    return -min(lags) if lags else 0.0


def reduce(ev: dict, top: int = 10) -> dict:
    """Numbers of one trace, averaged over its device planes where a
    per-chip quantity is asked for.

    ``window_s``: from the first harness span's start to the last one's
    end; ``busy_s``: the union of the intervals in which an operation ran,
    within the window; ``modules``: per program, executions and summed
    device seconds; ``kernels``: per Pallas kernel, calls and summed device
    seconds; ``device_ops``: the operations that took most time;
    ``idle_gaps``: idle device seconds by the harness span that the host
    was in at the middle of each gap."""
    spans = sorted(ev["spans"], key=lambda s: s[1])
    if not spans:
        raise ValueError("trace holds no harness spans")
    w0 = spans[0][1]
    w1 = max(s[1] + s[2] for s in spans)
    ndev = max(len(ev["devices"]), 1)
    busy = 0.0
    modules = collections.defaultdict(lambda: [0, 0.0])
    kernels = collections.defaultdict(lambda: [0, 0.0])
    ops = collections.defaultdict(float)
    gaps = collections.defaultdict(float)
    for dev in ev["devices"].values():
        off = clock_offset(dev, ev["enqueues"])
        for name, t, d in dev["modules"]:
            m = modules[module_name(name)]
            m[0] += 1
            m[1] += d * 1e-9
        cur_mod = sorted(([t + off, t + off + d, module_name(n)]
                          for n, t, d in dev["modules"]))
        iv = []
        mi = 0
        for name, t, d in sorted(dev["ops"], key=lambda o: o[1]):
            a, b = t + off, t + off + d
            k = kernel_name(name)
            if k is not None:
                kernels[k][0] += 1
                kernels[k][1] += d * 1e-9
            while mi + 1 < len(cur_mod) and cur_mod[mi][1] < a:
                mi += 1
            mod = (cur_mod[mi][2] if cur_mod and cur_mod[mi][0] <= a
                   <= cur_mod[mi][1] else "?")
            short = op_name(name)
            if not short.startswith(CONTAINERS):
                ops[f"{mod}:{short}"] += d * 1e-9
            iv.append([max(a, w0), min(b, w1)])
        merged = union([x for x in iv if x[1] > x[0]])
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            owner = "host:none"
            for name, t, d in spans:
                if t <= mid <= t + d:
                    owner = name
                if t > mid:
                    break
            gaps[owner] += (b - a) * 1e-9
    return {
        "devices": len(ev["devices"]),
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / ndev,
        "modules": {k: {"count": v[0], "device_s": v[1] / ndev}
                    for k, v in modules.items()},
        "kernels": {k: {"count": v[0], "device_s": v[1] / ndev}
                    for k, v in kernels.items()},
        "device_ops": [[k, v / ndev] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / ndev] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:top]],
    }


def programs(red: dict, fragment: str) -> tuple:
    """(executions, device seconds) of the programs whose name holds
    ``fragment``."""
    n, s = 0, 0.0
    for name, m in red["modules"].items():
        if fragment in name:
            n += m["count"]
            s += m["device_s"]
    return n, s
