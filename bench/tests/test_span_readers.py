"""The readers of the program's own spans and counters, on a traced run of
the tiny configuration (CPU), and on runs that have nothing to read."""
import time
from types import SimpleNamespace

import harness
from conftest import HERE
from test_check import SEED, _bench

NEW = ("decode_solve_ms_per_step", "decode_factorizations_per_step",
       "decode_rows_per_step", "trunk_wait_ms_per_step")


def test_traced_tiny_run_reports_the_span_metrics():
    b = _bench()
    for m in b["per_layer"]:
        m["workloads"] = ["tiny.decode"]
    out = harness.run_cell("tiny.decode", SEED, 4.0, True, t_process=time.time(),
                           bench=b, root=f"{HERE}/data")
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(got), got
    assert got["decode_factorizations_per_step"] > 0
    # every solved system has at least one row
    assert got["decode_rows_per_step"] >= got["decode_factorizations_per_step"]
    # both are parts of their stage: solve inside decode, trunk inside glue
    assert 0 < got["decode_solve_ms_per_step"] <= got["decode_ms_per_step"]
    assert 0 < got["trunk_wait_ms_per_step"] <= got["glue_ms_per_step"]


def test_readers_read_nothing_without_the_programs_rollup():
    untraced = SimpleNamespace(steps=10)
    older = SimpleNamespace(steps=10, trace_summary={
        "per_stage_wall": {"decode": 1.0}, "counters": {}})
    for name in NEW:
        read = harness.load_reader(name)
        assert read(untraced) is None
        assert read(older) is None


def test_readers_divide_by_the_windows_steps():
    run = SimpleNamespace(steps=4, trace_summary={
        "per_cat_wall": {"decode.solve": 2.0, "trunk": 0.4},
        "counters": {"decode_lu_factorizations": 8.0,
                     "decode_system_rows": 400.0}})
    want = {"decode_solve_ms_per_step": 500.0,
            "decode_factorizations_per_step": 2.0,
            "decode_rows_per_step": 100.0, "trunk_wait_ms_per_step": 100.0}
    for name, v in want.items():
        assert harness.load_reader(name)(run) == v
