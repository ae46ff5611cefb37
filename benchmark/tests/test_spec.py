"""BENCHMARK.json against the files under benchmark/: every name resolves,
every reader exists, and the harness finds all of it by name."""

import json
import os
import re

import pytest

from benchmark.harness import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    return common.load_spec()


def test_keys_and_names(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmark"]
    assert 1 <= spec["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert len(json.dumps(spec)) < 64 * 1024


def test_cells_resolve_to_files(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    four = 0
    for w in spec["workloads"]:
        cell = common.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"],
                cell["why"]) == (w["config"], w["traffic"], w["chips"],
                                 w["why"])
        assert len(w["why"]) <= 200
        assert configs[w["config"]]["file"] == (
            f"benchmark/configs/{w['config']}.json")
        runner = os.path.join(common.BENCH_DIR, "runners",
                              f"{cell['traffic_data']['kind']}.py")
        assert os.path.exists(runner)
        four += w["chips"] == 4
    assert four <= max(1, len(spec["workloads"]) // 4)
    used = {w["config"] for w in spec["workloads"]}
    assert used == set(configs)


def test_bounds_and_sources(spec):
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")
    for m in spec["per_layer"]:
        assert m["moves"] in names
        assert "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_per_layer_metric_has_a_reader_that_agrees(spec):
    for m in spec["per_layer"]:
        reader = common.load_module("layer_metrics", m["name"])
        assert reader.LAYER == m["layer"], m["name"]
        assert reader.SOURCE == m["source"], m["name"]
        # a reader that finds nothing to read returns nothing
        empty = {"peaks": {"bf16_flops": float("nan")}, "chips": 1,
                 "cell": common.load_cell(spec["workloads"][0]["name"])}
        assert reader.read({}, {}, empty) is None, m["name"]


def test_every_cell_reports_what_the_contract_asks(spec):
    for w in spec["workloads"]:
        e2e = [m["name"] for m in
               common.metrics_for(spec, "end_to_end", w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = common.metrics_for(spec, "per_layer", w["name"])
        assert layer
        assert all(m["moves"] in e2e for m in layer), w["name"]


#: what ``reduced`` may never name (the contract): a width
WIDTH_KEY = re.compile(
    r"(hidden|intermediate|latent|state|proj\w*)_size|_dim$|_rank$|head_size"
    r"|expansion|expand|experts_per_tok|top_k")


def test_config_files_state_their_cut(spec):
    """Each file against the ``published`` block IT carries (the source's
    own values): only the keys in ``reduced`` differ, none of them a
    width, and every architecture key of the file is in the block — so a
    configuration of other widths or another architecture adds a file and
    this test holds it to its own source."""
    for c in spec["configs"]:
        with open(os.path.join(common.REPO, c["file"])) as f:
            data = json.load(f)
        assert data["source"] == c["source"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert not [k for k in c["reduced"] if WIDTH_KEY.search(k)]
        published = data["published"]
        arch = set(data) - set(common.CONFIG_META_KEYS)
        assert arch == set(published), c["name"]
        differs = {k for k in arch if data[k] != published[k]}
        assert differs == set(c["reduced"]), c["name"]
        for k in c["reduced"]:
            assert data["reduced"][k] == {"from": published[k],
                                          "to": data[k]}
        # the files the configuration names exist, and its adapter knows
        # every key of it and of its toy sibling
        adapter = common.adapter_of(data)
        assert hasattr(common.load_module("reference", data["reference"]),
                       "hidden_and_loss")
        tiny = common.load_json("configs", f"{data['rehearsal']}.json")
        assert (tiny["adapter"], tiny["reference"]) == (
            data["adapter"], data["reference"])
        for cfg in (data, tiny):
            adapter.model_config(cfg, remat_block=False, seq_len=64)
