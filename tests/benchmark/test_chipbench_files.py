"""Every cell's configuration, traffic, limits and metric readers, and
every layer kind's counts and reference block, are found by name; the
benchmark file keeps to its own limits."""
from __future__ import annotations

import importlib
import json
import os
import re

import pytest

import _chipbench
from chip import harness

with open(os.path.join(_chipbench.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_files_found_by_name(workload):
    cell = harness.Cell.load(workload)
    m = cell.model
    assert cell.chips == 1
    assert cell.limits["max_logit_gap"]["limit"] > 0
    for kind in m["pattern"]:
        importlib.import_module(f"chip.counts.{kind}")
        importlib.import_module(f"chip.layers.{kind}")
    names = [x["name"] for x in cell.end_to_end + cell.per_layer]
    assert "setup_s" in names
    for name in names:
        mod = importlib.import_module(
            f"chip.metrics.{name.split('.', 1)[0]}")
        assert callable(mod.read)
    program = harness.program_cfg(cell.config)
    assert program.n_layers == m["n_layers"]
    assert program.d_model == m["d_model"]
    assert program.vocab == m["vocab"]
    assert list(program.pattern) == m["pattern"]
    assert program.dtype == m["dtype"]
    mix = cell.traffic
    assert max(mix["prompt_lens"]) + mix["max_new"][1] <= \
        cell.serving["max_len"]


def test_benchmark_file_keeps_to_its_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    layers = set()
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        layers.add(m["layer"])
        for w in m["workloads"]:
            reported = [x for x in BENCH["end_to_end"]
                        if x["name"] == m["moves"]][0]
            assert w in reported.get("workloads", CELLS)
    for c in BENCH["configs"]:
        path = os.path.join(_chipbench.ROOT, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"].startswith(
            c["source"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("config", ["stablelm-1.6b", "mamba2-370m"])
def test_published_keys_agree_with_the_model_keys(config):
    with open(os.path.join(_chipbench.ROOT, "benchmarks", "chip", "configs",
                           f"{config}.json")) as f:
        c = json.load(f)
    m = c["model"]
    pairs = {"num_hidden_layers": "n_layers", "n_layer": "n_layers",
             "hidden_size": "d_model", "d_model": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab",
             "partial_rotary_factor": "rope_fraction",
             "rope_theta": "rope_theta", "layer_norm_eps": "norm_eps",
             "norm_epsilon": "norm_eps",
             "tie_word_embeddings": "tie_embeddings",
             "tie_embeddings": "tie_embeddings", "use_qkv_bias": "bias"}
    # a published key the program runs with another value is named in
    # ``assumed``, and nowhere else: the file keeps the published value
    assert c["reduced"] == []
    for hf, ours in pairs.items():
        if hf in c:
            assert (c[hf] != m[ours]) == (hf in c["assumed"]), hf
    for hf, ours in {"d_state": "d_state", "d_conv": "d_conv",
                     "expand": "expand", "headdim": "head_dim",
                     "ngroups": "n_groups"}.items():
        if hf in c:
            assert c[hf] == m["ssm"][ours], hf


def test_peak_table_refuses_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
