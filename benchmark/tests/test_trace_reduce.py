"""trace_reduce.py on a hand-built trace whose every number can be worked
out on paper, and on a small trace recorded on the v5e in PR 22."""

import gzip
import json
import os

import pytest

from benchmark.harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace(dev_events, host_events, extra_planes=()):
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": dev_events}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": host_events}]},
    ]
    return {"planes": planes + list(extra_planes)}


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        (0, 3), (5, 8)]
    assert tr.total([(0, 3), (5, 8)]) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]
    assert tr.overlap((0, 5), (3, 9)) == 2 and tr.overlap((0, 1), (2, 3)) == 0


def test_self_time_takes_the_body_out_of_a_while():
    evs = [["while.1", 0.0, 100.0, ""], ["fusion.1", 10.0, 30.0, ""],
           ["fusion.2", 50.0, 40.0, ""], ["fusion.3", 120.0, 10.0, ""]]
    got = {ev[0]: (s, own) for ev, s, own in tr.self_times(evs)}
    assert got["while.1"] == (pytest.approx(30e-9),
                              [(0.0, 10.0), (40.0, 50.0), (90.0, 100.0)])
    assert got["fusion.1"] == (pytest.approx(30e-9), [(10.0, 40.0)])
    assert got["fusion.3"] == (pytest.approx(10e-9), [(120.0, 130.0)])


def test_instruction_text_is_split_into_name_and_tag():
    text = ('%jvp_flash_fwd_.2 = (bf16[64,8192,128]{2,1,0:T(8,128)(2,1)}, '
            'f32[64,1,8192]{2,1,0:T(1,128)}) custom-call(bf16[64,8192,128]'
            '{2,1,0} %bitcast.12), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={}')
    assert tr.split_instruction(text) == (
        "jvp_flash_fwd_.2", "pallas (bf16[64,8192,128], f32[64,1,8192])")
    assert tr.split_instruction(
        "%fusion.302 = f32[64]{0:T(128)S(1)} fusion(), kind=kLoop") == (
        "fusion.302", "f32[64]")
    assert tr.split_instruction("dot_general.1") == ("dot_general.1", "")


def test_kernels_are_found_by_their_pallas_call_name():
    assert tr.kernel_of(["jvp_flash_fwd_.2", 0, 1, "pallas x"]) == "flash_fwd"
    assert tr.kernel_of(["transpose_jvp_flash_bwd_dkv__.3", 0, 1,
                         "pallas x"]) == "flash_bwd_dkv"
    assert tr.kernel_of(["transpose_jvp_flash_bwd_dq__.2", 0, 1,
                         "pallas x"]) == "flash_bwd_dq"
    assert tr.kernel_of(["jvp_rmsnorm_fwd_.5", 0, 1, "pallas"]
                        ) == "rmsnorm_fwd"
    assert tr.kernel_of(["custom-call.9", 0, 1, "pallas"]) == "pallas_other"
    # a fusion that merely mentions a kernel's name is no kernel
    assert tr.kernel_of(["fusion_flash_fwd.7", 0, 1, "bf16[8]"]) is None
    assert tr.is_collective("all-gather-start.3")
    assert tr.is_collective("%all-reduce.1")
    assert not tr.is_collective("fusion.2")


@pytest.mark.parametrize("instruction,kernel", [
    ("gmm.14", "gmm"), ("tgmm.2", "tgmm"), ("gather_sum.24", "gather_sum"),
    ("ssd_chunk_fwd.3", "ssd_chunk_fwd"),
    ("ssd_chunk_bwd.1", "ssd_chunk_bwd"),
])
def test_the_routed_blocks_and_the_scans_kernels_have_names(instruction,
                                                            kernel):
    """Filed under its own name, not ``pallas_other`` (and ``tgmm`` not
    under ``gmm``, which its name holds)."""
    assert tr.kernel_of([instruction, 0, 1, "pallas bf16[8]"]) == kernel
    assert tr.kernel_of([instruction, 0, 1, "bf16[8]"]) is None


def test_a_kernels_seconds_are_kept_by_the_calling_instruction():
    dev = [["gather_sum.16", 0.0, 100.0, "pallas bf16[8]"],
           ["gather_sum.24", 100.0, 300.0, "pallas bf16[8]"],
           ["gather_sum.16", 400.0, 100.0, "pallas bf16[8]"],
           ["custom-call.9", 500.0, 50.0, "pallas f32[2]"],
           ["fusion.1", 550.0, 50.0, "f32[2]"]]
    r = tr.reduce_trace(_trace(dev, [["dispatch", 0.0, 600.0, ""]]))
    assert r["kernel_s"] == {"gather_sum": pytest.approx(500e-9),
                             "pallas_other": pytest.approx(50e-9)}
    assert r["op_self_s"]["gather_sum"] == pytest.approx(500e-9)
    assert r["kernel_call_s"] == {
        "gather_sum": {"gather_sum.16": pytest.approx(200e-9),
                       "gather_sum.24": pytest.approx(300e-9)},
        "pallas_other": {"custom-call.9": pytest.approx(50e-9)}}


def test_reduce_on_a_trace_worked_out_by_hand():
    # window 0..1000 ns (host spans).  Device: busy 100..400 and 600..900.
    dev = [
        ["fusion.1", 100.0, 200.0, ""],
        ["jvp_flash_fwd_.1", 300.0, 100.0, "pallas bf16[8]"],
        ["all-gather.1", 600.0, 100.0, ""],          # exposed: 600..700
        ["while.1", 700.0, 200.0, ""],               # holds its body:
        ["fusion.2", 700.0, 150.0, ""],
        ["all-reduce.1", 850.0, 50.0, ""],           # exposed inside it
    ]
    host = [["batch_build", 0.0, 100.0, ""], ["dispatch", 100.0, 50.0, ""],
            ["loss_sync", 150.0, 300.0, ""], ["ckpt_save", 450.0, 140.0, ""],
            ["loss_sync", 590.0, 410.0, ""], ["unrelated", 0.0, 5000.0, ""]]
    r = tr.reduce_trace(_trace(dev, host))
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(600e-9)
    assert r["idle_share"] == pytest.approx(0.4)
    assert r["kernel_s"] == {"flash_fwd": pytest.approx(100e-9)}
    assert r["collective_s"] == pytest.approx(150e-9)
    assert r["exposed_collective_s"] == pytest.approx(150e-9)
    # gaps: 0..100 under batch_build, 400..600 mostly under ckpt_save,
    # 900..1000 under loss_sync
    assert r["idle_gaps"] == {"batch_build": pytest.approx(100e-9),
                              "ckpt_save": pytest.approx(200e-9),
                              "loss_sync": pytest.approx(100e-9)}
    assert r["op_self_s"]["flash_fwd"] == pytest.approx(100e-9)
    assert r["op_self_s"]["fusion.2"] == pytest.approx(150e-9)
    assert r["op_self_s"]["while.1"] == pytest.approx(0.0)
    bd = tr.breakdown(r)
    assert bd["device_ops"][0][0] in ("fusion.1", "fusion.2")
    assert bd["idle_gaps"][0] == ["ckpt_save", pytest.approx(200e-9)]
    assert len(bd["device_ops"]) <= 10


def test_device_numbers_are_averaged_over_the_chips():
    dev0 = [["fusion.1", 0.0, 800.0, ""]]
    dev1 = [["fusion.1", 0.0, 400.0, ""]]
    host = [["dispatch", 0.0, 1000.0, ""]]
    t = _trace(dev0, host, [{"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": dev1}]}])
    r = tr.reduce_trace(t)
    assert r["n_devices"] == 2
    assert r["busy_s"] == pytest.approx(600e-9)
    assert r["idle_share"] == pytest.approx(0.4)


def test_a_trace_without_a_device_gives_nothing():
    assert tr.reduce_trace({"planes": []}) == {}
    assert tr.reduce_trace(_trace([], [["dispatch", 0.0, 10.0, ""]])) == {}
    assert tr.breakdown({}) == {"device_ops": [], "idle_gaps": []}


def _recorded(name):
    path = os.path.join(DATA, name)
    if not os.path.exists(path):
        pytest.skip(f"{name} not recorded")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_v5e_trace_of_two_steady_steps():
    """Two optimizer steps of ``mistral7b-l2.train-steady`` on one v5e (my
    chip run, PR 22).  The expectations are counts the program fixes (2
    layers x 2 steps, 5 rmsnorms a step) and arithmetic identities; the
    times are what that run measured and only pin the reduction."""
    trace = _recorded("l2_steady_2steps.json.gz")
    plane, = tr.device_planes(trace)
    kernels = [tr.kernel_of(ev) for ev in tr.op_events(plane)]
    assert {k: kernels.count(k) for k in set(kernels) if k} == {
        "flash_fwd": 4, "flash_bwd_dq": 4, "flash_bwd_dkv": 4,
        "rmsnorm_fwd": 10}
    r = tr.reduce_trace(trace)
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(1.146789807)
    assert r["busy_s"] == pytest.approx(1.140816642)
    assert r["idle_share"] == pytest.approx(0.0052086, abs=1e-6)
    # self times partition the busy time: nothing is counted twice
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busy_s"])
    assert r["kernel_s"]["flash_bwd_dkv"] == pytest.approx(0.07241182)
    assert r["kernel_s"]["flash_fwd"] == pytest.approx(0.035673265)
    assert r["collective_s"] == 0.0 and r["exposed_collective_s"] == 0.0
    # the device waits while the host fetches the loss and builds a batch
    assert max(r["idle_gaps"], key=r["idle_gaps"].get) == "loss_sync"
    assert sum(r["idle_gaps"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    top = tr.breakdown(r)["device_ops"]
    assert top[0][0].startswith("convolution_add_fusion")
    assert "flash_bwd_dkv" in [name for name, _ in top]


def test_recorded_v5e_trace_of_one_sharded_step():
    """One optimizer step of ``mistral7b-l8.train-fsdp2tp2``, devices 0 and
    1 of four (my chip run, PR 22): the program fixes the kernel counts (8
    layers, block remat runs the flash forward twice, 4 rmsnorms a layer +
    1), the collectives sit on the op line and block it, and the numbers
    are means over the two devices."""
    trace = _recorded("l8_fsdp2tp2_1step.json.gz")
    planes = tr.device_planes(trace)
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/device:TPU:1"]
    for plane in planes:
        kernels = [tr.kernel_of(ev) for ev in tr.op_events(plane)]
        assert {k: kernels.count(k) for k in set(kernels) if k} == {
            "flash_fwd": 16, "flash_bwd_dq": 8, "flash_bwd_dkv": 8,
            "rmsnorm_fwd": 33}
    names = {ev[0].split(".")[0] for ev in tr.op_events(planes[0])
             if tr.is_collective(ev[0])}
    assert {"all-reduce", "all-gather", "async-collective-start",
            "async-collective-done"} <= names
    r = tr.reduce_trace(trace)
    assert r["n_devices"] == 2
    assert r["window_s"] == pytest.approx(1.366719813)
    assert r["busy_s"] == pytest.approx(1.3613330105)
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busy_s"])
    # on this chip a collective op blocks the op line: all of it is exposed
    assert r["collective_s"] == pytest.approx(0.2902129335)
    assert r["exposed_collective_s"] == pytest.approx(r["collective_s"])
    assert r["kernel_s"]["flash_fwd"] == pytest.approx(0.071204469)
    assert sum(r["idle_gaps"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_a_gap_goes_to_the_innermost_span_that_covers_most_of_it():
    outer, inner = ["ckpt_save", 0.0, 100.0, ""], ["ckpt.save", 1.0, 98.0, ""]
    d2h, write = ["ckpt.save.d2h", 2.0, 80.0, ""], [
        "ckpt.save.arena_write", 82.0, 16.0, ""]
    spans = [outer, inner, d2h, write, ["loss_sync", 100.0, 50.0, ""]]
    assert tr.span_of_gap((0.0, 100.0), spans) == "ckpt.save.d2h"
    assert tr.span_of_gap((80.0, 100.0), spans) == "ckpt.save.arena_write"
    # nothing nested holds more than half of it: the innermost that does
    assert tr.span_of_gap((68.0, 96.0), spans) == "ckpt.save"
    # no span holds more than half: the one that holds most, as before
    assert tr.span_of_gap((90.0, 300.0), spans) == "loss_sync"
    assert tr.span_of_gap((500.0, 600.0), spans) == "no_span"
    # the window is the loop's own spans': a program span outside them
    # (the launcher's per-step report) does not stretch it
    t = _trace([["fusion.1", 0.0, 50.0, ""]],
               [["dispatch", 0.0, 100.0, ""],
                ["trainer.report_step", 100.0, 900.0, ""]])
    assert tr.window_of(t) == (0.0, 100.0)


def test_recorded_v5e_trace_of_one_elastic_period():
    """One period of ``mistral7b-l1.elastic`` on one v5e, four steps and a
    memory save (my chip run, PR 25, ``--dump-trace``): the program's own
    spans lie inside the loop's ``ckpt_save``, the idle gap of the save goes
    to the device-to-host copy, and no other number knows of them."""
    trace = _recorded("l1_elastic_1period.json.gz")
    names = {ev[0] for ev in tr.host_spans(trace, program=True)}
    assert {"ckpt_save", "ckpt.save", "ckpt.save.d2h",
            "ckpt.save.arena_write", "trainer.report_step"} <= names
    r = tr.reduce_trace(trace)
    assert r["window_s"] == pytest.approx(7.403581124)
    assert r["busy_s"] == pytest.approx(1.427133909)
    assert tr.breakdown(r)["idle_gaps"][0] == [
        "ckpt.save.d2h", pytest.approx(5.903127071)]
    assert sum(r["idle_gaps"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    loop_only = {"planes": [
        dict(p, lines=[dict(ln, events=[
            ev for ev in ln["events"] if not tr.is_program_span(ev[0])])
            for ln in p["lines"]]) for p in trace["planes"]]}
    before = tr.reduce_trace(loop_only)
    assert before["idle_gaps"]["ckpt_save"] == pytest.approx(5.903127071)
    assert {k: v for k, v in before.items() if k != "idle_gaps"} == {
        k: v for k, v in r.items() if k != "idle_gaps"}
