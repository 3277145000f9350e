"""Every file that BENCHMARK.json names resolves by name, and the file
keeps the shape the harness reads."""
import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"][1] == "bench/run.py"
    assert (ROOT / BM["command"][1]).is_file()
    assert all((ROOT / p).is_dir() for p in BM["paths"])
    # a full check of 24 cells fits its time budget
    runs = 2 + 14 * 24
    assert runs * (BM["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_files_resolve(c):
    assert NAME.match(c["name"])
    path = ROOT / c["file"]
    assert path.is_file() and c["file"].startswith("bench/configs/")
    cfg = json.loads(path.read_text())
    assert cfg["source"] == c["source"]
    assert set(c["reduced"]) <= set(cfg["cut"]) and set(cfg["cut"]) <= \
        set(c["reduced"])
    assert all(k in cfg for k in c["reduced"])
    arch = spec.arch_module(cfg["arch"])
    for fn in ("program_config", "init_params", "gemm_sites",
               "context_ops", "reference_logits"):
        assert callable(getattr(arch, fn))
    from bench import check
    assert cfg["check"]["reference"] in ("ft", "float")
    assert cfg["check"]["limits"]
    for number, limit in cfg["check"]["limits"].items():
        assert number in check.NUMBERS and limit > 0


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_workloads_resolve(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    spec.config_entry(BM, w["config"])
    mix = spec.load_traffic(w["traffic"])
    assert mix["kind"] in ("open_poisson", "backlog")
    cfg = spec.load_config(BM, w["config"])
    assert mix["prompt"]["max"] + mix["output"]["max"] <= \
        cfg["serve"]["max_seq"]
    e2e = [m["name"] for m in spec.metrics_for(BM, w["name"], "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics_for(BM, w["name"], "per_layer")


# what a deployment setting may change between two configurations of one
# model: everything else (every width, depth and serving size) must agree
DEPLOYMENT_KEYS = {"deployment", "precision", "memory", "check"}


@pytest.mark.parametrize("pair", [("falcon-mamba-7b.ft-off",
                                   "falcon-mamba-7b")], ids=str)
def test_setting_copies_keep_in_step(pair):
    a, b = (spec.load_config(BM, n) for n in pair)
    assert {k for k in a.keys() | b.keys() if a.get(k) != b.get(k)} <= \
        DEPLOYMENT_KEYS | {"serve"}
    diff = {k for k in a["serve"].keys() | b["serve"].keys()
            if a["serve"].get(k) != b["serve"].get(k)}
    assert diff == {"ft_mode"}


def test_mix_base_is_put_under_its_keys():
    chat = spec.load_traffic("chat")
    fs = spec.load_traffic("chat.failstop")
    assert "base" not in fs and fs["failed_group"] == 1
    assert {k: v for k, v in fs.items()
            if k not in ("about", "failed_group")} == \
        {k: v for k, v in chat.items() if k != "about"}


def test_pairs_and_names_unique():
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("m", BM["per_layer"], ids=lambda m: m["name"])
def test_metric_readers_resolve(m):
    assert NAME.match(m["name"])
    assert m["moves"] in {e["name"] for e in BM["end_to_end"]}
    cells = {w["name"] for w in BM["workloads"]}
    assert set(m["workloads"]) <= cells
    for c in m["workloads"]:
        assert m["moves"] in [e["name"] for e in
                              spec.metrics_for(BM, c, "end_to_end")]
    assert callable(spec.metric_reader(m["name"]))


def test_peaks_name_their_source():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["bf16_flops_per_s"] == 197e12
