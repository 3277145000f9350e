"""bench/trace.py on a small trace recorded on a TPU v5e (see the data
file's ``recorded`` key): busy union, idle share, per-program device time,
kernel time, and the attribution of idle gaps to host spans."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data" / "trace_small.json"


@pytest.fixture(scope="module")
def red():
    return trace.reduce(json.loads(DATA.read_text()))


def test_window_and_busy(red):
    ev = json.loads(DATA.read_text())
    spans = ev["spans"]
    w0 = min(s[1] for s in spans)
    w1 = max(s[1] + s[2] for s in spans)
    assert red["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    # the ops of each program execution are back to back except one
    # cross-program prefetch, so busy is close to the summed op time
    ops = sum(d for _, _, d in ev["devices"]["/device:TPU:0"]["ops"])
    assert 0.9 * ops * 1e-9 <= red["busy_s"] <= ops * 1e-9 + 1e-12
    assert red["busy_s"] == pytest.approx(0.001794479, rel=1e-6)
    idle = 1 - red["busy_s"] / red["window_s"]
    assert 0.8 < idle < 0.9


def test_programs_and_kernels(red):
    assert red["modules"]["jit_emm_step"]["count"] == 3
    assert red["modules"]["jit_plain"]["count"] == 3
    assert trace.programs(red, "emm_step") == (
        3, pytest.approx(0.001749501, rel=1e-6))
    k = red["kernels"]["entangled_matmul_pallas"]
    assert k["count"] == 3
    assert k["device_s"] == pytest.approx(0.001741386, rel=1e-6)
    assert red["device_ops"][0][0] == "jit_emm_step:entangled_matmul_pallas.1"


def test_idle_gaps_follow_host_spans(red):
    gaps = dict(red["idle_gaps"])
    # most idle time lies in the host's 2 ms sleeps
    assert gaps["bench.idle"] > gaps["bench.step"] > 0
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)


def test_union_and_names():
    assert trace.union([[0, 2], [1, 3], [5, 6]]) == [[0, 3], [5, 6]]
    assert trace.module_name("jit__decode_impl(8812)") == "jit__decode_impl"
    assert trace.kernel_name(
        '%entangled_matmul_pallas.3 = s32[4] custom-call(s8[4] %a), '
        'custom_call_target="tpu_custom_call"') == "entangled_matmul_pallas"
    assert trace.kernel_name("%fusion.2 = f32[4] fusion(f32[4] %a)") is None


def test_no_spans_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "spans": [], "enqueues": []})
