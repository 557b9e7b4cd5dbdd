"""The result line's validator.

Encodes the sentence the driver refuses a line by: the last line of
standard output is one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device``, where ``metrics``
gives each metric of this workload as its value and unit, and ``device``
gives ``platform``, ``kind``, ``count``, ``memory_peak_bytes`` and, in a
traced run, ``window_s`` and ``busy_s`` (above 0, at most ``window_s``).
Every number is finite: the line is made with ``allow_nan=False``.
"""

import json
import math
from typing import Iterable, Optional


class LineError(ValueError):
    pass


def _finite_number(x, what: str) -> float:
    if isinstance(x, bool) or not isinstance(x, (int, float)):
        raise LineError(f"{what} is not a number: {x!r}")
    if not math.isfinite(x):
        raise LineError(f"{what} is not finite: {x!r}")
    return float(x)


def expected_metrics(bench: dict, workload: str, trace: bool) -> dict:
    """``{name: unit}`` of what the cell's line must carry: its end-to-end
    metrics, and in a traced run its per-layer metrics as well."""
    out = {}
    groups = [bench["end_to_end"]] + ([bench["per_layer"]] if trace else [])
    for group in groups:
        for m in group:
            cells = m.get("workloads")
            if cells is None or workload in cells:
                out[m["name"]] = m["unit"]
    return out


def validate_line(line: str, expected: dict, *, trace: bool,
                  chips: Optional[int] = None,
                  optional: Iterable[str] = ()) -> dict:
    """Parse ``line`` and check it; returns the object.  ``expected`` is
    ``{metric: unit}``; names in ``optional`` may be absent (a per-layer
    reader that found nothing to read), every other one must be there."""
    if "\n" in line.strip():
        raise LineError("the result is more than one line")

    def no_constants(name):
        raise LineError(f"the line holds {name}")

    try:
        obj = json.loads(line, parse_constant=no_constants)
    except json.JSONDecodeError as e:
        raise LineError(f"not JSON: {e}") from e
    if not isinstance(obj, dict):
        raise LineError("the line is not a JSON object")
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in obj:
            raise LineError(f"key {key!r} is missing")
    if not isinstance(obj["correct"], bool):
        raise LineError(f"correct is not true or false: {obj['correct']!r}")
    for key in ("attempted", "failed"):
        v = obj[key]
        if isinstance(v, bool) or not isinstance(v, int) or v < 0:
            raise LineError(f"{key} is not a count: {v!r}")
    if obj["failed"] > obj["attempted"]:
        raise LineError("failed is larger than attempted")
    metrics = obj["metrics"]
    if not isinstance(metrics, dict):
        raise LineError("metrics is not an object")
    optional = set(optional)
    for name, unit in expected.items():
        if name not in metrics:
            if name in optional:
                continue
            raise LineError(f"metric {name!r} is missing")
    for name, m in metrics.items():
        if name not in expected:
            raise LineError(f"metric {name!r} is not one of this cell's")
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            raise LineError(f"metric {name!r} is not {{value, unit}}: {m!r}")
        _finite_number(m["value"], f"metric {name!r}")
        if m["unit"] != expected[name]:
            raise LineError(f"metric {name!r} has unit {m['unit']!r}, "
                            f"BENCHMARK.json says {expected[name]!r}")
    dev = obj["device"]
    if not isinstance(dev, dict):
        raise LineError("device is not an object")
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if key not in dev:
            raise LineError(f"device.{key} is missing")
    for key in ("platform", "kind"):
        if not isinstance(dev[key], str) or not dev[key]:
            raise LineError(f"device.{key} is not a name: {dev[key]!r}")
    count = dev["count"]
    if isinstance(count, bool) or not isinstance(count, int) or count < 1:
        raise LineError(f"device.count is not a count: {count!r}")
    if chips is not None and count != chips:
        raise LineError(f"device.count {count} is not the cell's {chips}")
    peak = dev["memory_peak_bytes"]
    if isinstance(peak, bool) or not isinstance(peak, int) or peak <= 0:
        raise LineError(f"device.memory_peak_bytes is not a positive "
                        f"whole number: {peak!r}")
    if trace:
        for key in ("window_s", "busy_s"):
            if key not in dev:
                raise LineError(f"device.{key} is missing in a traced run")
        window = _finite_number(dev["window_s"], "device.window_s")
        busy = _finite_number(dev["busy_s"], "device.busy_s")
        if not 0.0 < busy <= window:
            raise LineError(f"device.busy_s {busy} is not above 0 and at "
                            f"most device.window_s {window}")
    if "breakdown" in obj:
        bd = obj["breakdown"]
        if not isinstance(bd, dict):
            raise LineError("breakdown is not an object")
        for key, rows in bd.items():
            if key not in ("device_ops", "idle_gaps"):
                raise LineError(f"breakdown.{key} is not a list the "
                                f"contract names")
            if not isinstance(rows, list) or len(rows) > 10:
                raise LineError(f"breakdown.{key} is not a list of at "
                                f"most 10 entries")
            for row in rows:
                if (not isinstance(row, list) or len(row) != 2
                        or not isinstance(row[0], str)):
                    raise LineError(f"breakdown.{key} entry is not "
                                    f"[name, seconds]: {row!r}")
                _finite_number(row[1], f"breakdown.{key} {row[0]!r}")
    return obj


def dump_line(obj: dict) -> str:
    """The one way a result line is made: NaN and infinity raise."""
    return json.dumps(obj, allow_nan=False, separators=(", ", ": "))
