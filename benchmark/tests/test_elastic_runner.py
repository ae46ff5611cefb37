"""The elastic runner's clean-up finds this run's arenas and no other
run's."""

import os

from benchmark.harness import common


def test_arenas_match_this_job_only():
    elastic = common.load_module("runners", "elastic")
    mine = ["dlrtpu_bench-12-ab12cd34_ckpt_0", "dlrtpu_bench-12_ckpt_0"]
    others = ["dlrtpu_bench-123-ab12cd34_ckpt_0", "dlrtpu_ckpt-bench-12_x_0"]
    paths = [os.path.join("/dev/shm", n) for n in mine + others]
    try:
        for p in paths:
            open(p, "w").close()
        assert sorted(elastic._arenas("bench-12")) == sorted(paths[:2])
    finally:
        for p in paths:
            os.unlink(p)
