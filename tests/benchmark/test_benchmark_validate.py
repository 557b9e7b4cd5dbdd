"""The result line's validator against good lines and against each way of
breaking the sentence the driver refuses a line by."""

import copy
import json
import math
import os

import pytest

from benchmarks.lib import validate

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def good_line(workload: str, trace: bool) -> dict:
    expected = validate.expected_metrics(BENCH, workload, trace)
    line = {"correct": True, "attempted": 40, "failed": 0,
            "metrics": {n: {"value": 12.5, "unit": u}
                        for n, u in expected.items()},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 4320767488}}
    if trace:
        line["device"].update(window_s=1.7665, busy_s=1.7651)
        line["breakdown"] = {"device_ops": [["fusion %f.1 f32[8]", 0.04]],
                             "idle_gaps": [["bench.fence", 0.001]]}
    return line


def check(line: dict, workload: str, trace: bool):
    return validate.validate_line(
        validate.dump_line(line),
        validate.expected_metrics(BENCH, workload, trace), trace=trace)


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("workload", CELLS)
def test_good_line_passes(workload, trace):
    assert check(good_line(workload, trace), workload, trace)["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(workload):
    e2e = validate.expected_metrics(BENCH, workload, False)
    both = validate.expected_metrics(BENCH, workload, True)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert len(both) > len(e2e)


def _break_untraced():
    def drop(key):
        def f(d):
            del d[key]
        return f

    def put(path, value):
        def f(d):
            for k in path[:-1]:
                d = d[k]
            d[path[-1]] = value
        return f

    def drop_metric(d):
        d["metrics"].pop(next(iter(d["metrics"])))

    first = "setup_s"
    return {
        "no_correct": drop("correct"), "no_attempted": drop("attempted"),
        "no_failed": drop("failed"), "no_metrics": drop("metrics"),
        "no_device": drop("device"),
        "correct_not_bool": put(["correct"], "yes"),
        "attempted_float": put(["attempted"], 4.5),
        "failed_negative": put(["failed"], -1),
        "failed_over_attempted": put(["failed"], 41),
        "metric_missing": drop_metric,
        "metric_null": put(["metrics", first, "value"], None),
        "metric_string": put(["metrics", first, "value"], "12"),
        "metric_bool": put(["metrics", first, "value"], True),
        "metric_bare_number": put(["metrics", first], 3.0),
        "metric_wrong_unit": put(["metrics", first, "unit"], "ms"),
        "metric_unknown": put(["metrics", "made_up"],
                              {"value": 1.0, "unit": "s"}),
        "no_platform": lambda d: d["device"].pop("platform"),
        "no_kind": lambda d: d["device"].pop("kind"),
        "no_count": lambda d: d["device"].pop("count"),
        "count_zero": put(["device", "count"], 0),
        "no_memory_peak": lambda d: d["device"].pop("memory_peak_bytes"),
        "memory_peak_zero": put(["device", "memory_peak_bytes"], 0),
        "memory_peak_float": put(["device", "memory_peak_bytes"], 1.5e9),
    }


@pytest.mark.parametrize("how", sorted(_break_untraced()))
def test_broken_untraced_line_is_refused(how):
    line = good_line("bert_large_dp1", False)
    _break_untraced()[how](line)
    with pytest.raises(validate.LineError):
        check(line, "bert_large_dp1", False)


TRACED_BREAKS = {
    "no_window": lambda d: d["device"].pop("window_s"),
    "no_busy": lambda d: d["device"].pop("busy_s"),
    "busy_zero": lambda d: d["device"].update(busy_s=0.0),
    "busy_negative": lambda d: d["device"].update(busy_s=-1.0),
    "busy_over_window": lambda d: d["device"].update(busy_s=1.77),
    "window_string": lambda d: d["device"].update(window_s="1.7"),
    "per_layer_missing": lambda d: d["metrics"].pop("prefill_share_pct"),
    "breakdown_eleven": lambda d: d["breakdown"].update(
        device_ops=[["op", 0.1]] * 11),
    "breakdown_unknown_list": lambda d: d["breakdown"].update(extra=[]),
    "breakdown_not_pair": lambda d: d["breakdown"].update(
        idle_gaps=[["gap"]]),
}


@pytest.mark.parametrize("how", sorted(TRACED_BREAKS))
def test_broken_traced_line_is_refused(how):
    line = good_line("mistral_7b_chat_steady", True)
    TRACED_BREAKS[how](line)
    with pytest.raises(validate.LineError):
        check(line, "mistral_7b_chat_steady", True)


@pytest.mark.parametrize("bad", [math.nan, math.inf, -math.inf])
def test_a_line_with_nan_or_infinity_cannot_be_made(bad):
    line = good_line("mistral_7b_offline", True)
    line["metrics"]["serve_tokens_per_s"]["value"] = bad
    with pytest.raises(ValueError):
        validate.dump_line(line)
    line = good_line("mistral_7b_offline", True)
    line["device"]["busy_s"] = bad
    with pytest.raises(ValueError):
        validate.dump_line(line)


@pytest.mark.parametrize("text", [
    '{"correct": true, "attempted": 1, "failed": 0, "metrics": '
    '{"setup_s": {"value": NaN, "unit": "s"}}, "device": {}}',
    '[1, 2]', 'not json', '{"correct": true}\n{"correct": true}'])
def test_text_that_is_not_one_json_object_is_refused(text):
    with pytest.raises(validate.LineError):
        validate.validate_line(text, {"setup_s": "s"}, trace=False)


def test_chips_are_checked_against_the_cell():
    line = good_line("bert_large_dp4", False)
    text = validate.dump_line(line)
    expected = validate.expected_metrics(BENCH, "bert_large_dp4", False)
    with pytest.raises(validate.LineError):
        validate.validate_line(text, expected, trace=False, chips=4)
    line["device"]["count"] = 4
    validate.validate_line(validate.dump_line(line), expected, trace=False,
                           chips=4)


def test_good_line_survives_a_copy():
    line = good_line("bert_large_dp1", True)
    again = copy.deepcopy(line)
    check(line, "bert_large_dp1", True)
    assert line == again
