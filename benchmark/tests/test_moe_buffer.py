"""``moe.buffer_live_pct``: the reader against hand-made counters, and its
entry in ``BENCHMARK.json``."""

import pytest

from benchmark.harness import common

NAME = "moe.buffer_live_pct"
CELLS = ["glm4_7_flash-l5.train-decayed", "lfm2_8b_a1b-l5.train-decayed"]


def _read(step_metrics):
    return common.load_module("layer_metrics", NAME).read(
        {}, {}, {"step_metrics": step_metrics})


@pytest.mark.parametrize("held,rows,want", [
    # the first size engaged in every block: the fullest one counts
    ([32768, 33100, 32500, 32900], [40960] * 4, 100 * 33100 / 40960),
    # one skewed block fell back to every pick
    ([32768, 70000], [40960, 131072], 100 * 32768 / 40960),
    # a collapsed router: nothing held, the smallest buffer
    ([0, 0, 0, 0, 0], [10240] * 5, 0.0),
])
def test_reads_the_fullest_blocks_share_of_its_buffer(held, rows, want):
    assert _read({"moe_held_pairs": held, "moe_buffer_rows": rows,
                  "moe_tokens_per_expert": [[1] * 32] * len(held)}) == (
        pytest.approx(want))


@pytest.mark.parametrize("step_metrics", [
    {},  # a dense program
    {"grad_norm": 1.0, "moe_tokens_per_expert": [[4, 4]]},  # every expert
    # the parent: a share whose buffer holds every pick, and no counter
    {"moe_held_pairs": [8000, 9000],
     "moe_tokens_per_expert": [[1024] * 64] * 2},
])
def test_a_program_without_the_counter_reads_nothing(step_metrics):
    assert _read(step_metrics) is None
    assert common.load_module("layer_metrics", NAME).read({}, {}, {}) is None


def test_its_entry_names_the_cells_with_a_share():
    spec = common.load_spec()
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "model step",
        "moves": "train_tokens_per_s", "workloads": CELLS}
    # appended by PR 47, nothing moved; PR 50 appended the one after it
    assert [m["name"] for m in spec["per_layer"][-2:]] == [
        NAME, "agent.kill_to_step_s"]
    module = common.load_module("layer_metrics", NAME)
    assert (module.LAYER, module.SOURCE) == (entry["layer"], entry["source"])
    for cell in CELLS:
        assert entry in common.metrics_for(spec, "per_layer", cell)
