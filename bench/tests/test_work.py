"""Exact work counts of the committed cells' calls."""
import json

import pytest

import work
from conftest import BENCH

PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def _call(traffic: str) -> work.Work:
    cfg = json.loads((BENCH / "configs" / "jacobi2d5pt.json").read_text())
    t = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    return work.stencil_call(t["domain"], len(cfg["offsets"]),
                             t["steps_per_call"], 4)


@pytest.mark.parametrize("traffic, nbytes, flops", [
    ("stream", 1_073_741_824, 171_798_691_840),
    ("resident", 75_497_472, 12_079_595_520),
])
def test_stencil_call_counts(traffic, nbytes, flops):
    assert _call(traffic) == work.Work(bytes=nbytes, flops=flops)


@pytest.mark.parametrize("traffic, seconds, bound", [
    ("stream", 1_073_741_824 / 819e9, "bytes"),
    ("resident", 75_497_472 / 819e9, "bytes"),
])
def test_least_time(traffic, seconds, bound):
    assert work.least_time_s(_call(traffic), PEAK) == (seconds, bound)


def test_work_scales_with_calls():
    w = work.Work(bytes=3, flops=5)
    assert 4 * w == w * 4 == work.Work(bytes=12, flops=20)


def test_flops_bound_when_steps_dominate():
    w = work.stencil_call((8, 128), 5, 10**6, 4)
    t, bound = work.least_time_s(w, PEAK)
    assert bound == "flops" and t == 5 * 8 * 128 * 10**6 / 197e12


def test_peaks_name_their_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert peaks["TPU v5 lite"]["flops_per_s"] == 197e12
    assert "TPU v5e" in peaks["TPU v5 lite"]["source"]
