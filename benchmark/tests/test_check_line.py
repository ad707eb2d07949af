"""``check_line.py`` on one good line and on the shapes PR 22 was refused
for: a metric missing in the traced run, ``busy_s`` 0, ``busy_s`` > ``window_s``."""

import copy
import json

import pytest

import check_line
from lib import manifest as mf

CELL = "higgs-11m.train-eval"


def good_line(traced: bool) -> dict:
    m = mf.load()
    metrics = {x["name"]: {"value": 1.5, "unit": x["unit"]}
               for x in mf.metrics_of(m, "end_to_end", CELL)}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 3_000_000_000}
    line = {"correct": True, "attempted": 3, "failed": 0, "metrics": metrics,
            "device": device}
    if traced:
        metrics.update({x["name"]: {"value": 2.5, "unit": x["unit"]}
                        for x in mf.metrics_of(m, "per_layer", CELL)})
        device.update(busy_s=40.0, window_s=48.0)
        line["breakdown"] = {"device_ops": [["fusion.1", 3.0]],
                             "idle_gaps": [["bench.call", 0.5]]}
    line["compared"] = {"loss_gap": {"value": 1e-6, "limit": 1e-3}}
    return line


def check(line: dict, traced: bool) -> list:
    return check_line.problems(json.dumps(line), mf.load(), CELL, traced)


@pytest.mark.parametrize("traced", [False, True])
def test_good_line_passes(traced):
    assert check(good_line(traced), traced) == []


def _drop_metric(line):
    del line["metrics"]["round_program_ms"]


def _drop_end_to_end(line):
    del line["metrics"]["train_rounds_per_s"]


def _busy_zero(line):
    line["device"]["busy_s"] = 0.0


def _busy_over_window(line):
    line["device"]["busy_s"] = line["device"]["window_s"] * 1.5


def _no_window(line):
    del line["device"]["window_s"]


def _nan(line):
    line["metrics"]["setup_s"]["value"] = float("nan")


def _bad_unit(line):
    line["metrics"]["setup_s"]["unit"] = "seconds per run"


def _wrong_unit(line):
    line["metrics"]["setup_s"]["unit"] = "ms"


def _no_device_key(line):
    del line["device"]["memory_peak_bytes"]


def _no_correct(line):
    del line["correct"]


def _fat_metric(line):
    line["metrics"]["setup_s"]["why"] = "x"


@pytest.mark.parametrize("breaker, needle", [
    (_drop_metric, "round_program_ms"), (_drop_end_to_end, "train_rounds_per_s"),
    (_busy_zero, "not above 0"), (_busy_over_window, "exceeds"),
    (_no_window, "missing in the traced run"), (_nan, "non-finite"),
    (_bad_unit, "unit"), (_wrong_unit, "BENCHMARK.json says"),
    (_no_device_key, "memory_peak_bytes"), (_no_correct, "'correct' missing"),
    (_fat_metric, "{value, unit}")])
def test_broken_traced_line_is_caught(breaker, needle):
    line = copy.deepcopy(good_line(True))
    breaker(line)
    found = check(line, True)
    assert found and any(needle in p for p in found), found


def test_untraced_line_needs_no_layer_metric_and_no_busy():
    assert check(good_line(False), False) == []
    # the same line offered as a traced run lacks what a traced run owes
    assert check(good_line(False), True)


def test_not_json_and_not_object():
    m = mf.load()
    assert check_line.problems("rounds/s 0.16", m, CELL, False)
    assert check_line.problems("[1, 2]", m, CELL, False)
