"""The last-line validator on good lines and on each way a line can be
unreadable to the driver, and BENCHMARK.json against the files it names."""

import copy
import json
import os
import re

import pytest

from chipbench import harness

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
E2E = {"samples_per_s_chip": {"value": 97.2, "unit": "samples/s/chip"},
       "step_ms_p95": {"value": 82.7, "unit": "ms"},
       "setup_s": {"value": 36.2, "unit": "s"}}
GOOD = {"correct": True, "attempted": 97, "failed": 0, "metrics": E2E,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 9726002176}}
TRACED = copy.deepcopy(GOOD)
TRACED["metrics"]["device_step_ms"] = {"value": 82.28, "unit": "ms"}
TRACED["device"].update(busy_s=1.7269, window_s=1.7283)
TRACED["breakdown"] = {"device_ops": [["fusion.17 f32[8]", 0.03]],
                       "idle_gaps": [["host: loss fetch", 0.001]]}


def _edit(line, path, value="__drop__"):
    line = copy.deepcopy(line)
    node = line
    for key in path[:-1]:
        node = node[key]
    if value == "__drop__":
        del node[path[-1]]
    else:
        node[path[-1]] = value
    return line


def test_good_lines_pass():
    assert harness.validate_line(GOOD, E2E, traced=False) == []
    assert harness.validate_line(TRACED, set(E2E) | {"device_step_ms"},
                                 traced=True) == []


@pytest.mark.parametrize("traced,path,value,says", [
    (True, ("device", "window_s"), "__drop__", "busy_s and device.window_s"),
    (True, ("device", "busy_s"), "__drop__", "busy_s and device.window_s"),
    (True, ("device", "busy_s"), 0, "0 < busy_s <= window_s"),
    (True, ("device", "busy_s"), 1.8, "0 < busy_s <= window_s"),
    (True, ("metrics", "device_step_ms"), "__drop__", "is missing"),
    (False, ("metrics", "step_ms_p95"), "__drop__", "is missing"),
    (False, ("metrics", "step_ms_p95"), 82.7, '{"value": <number>'),
    (False, ("metrics", "step_ms_p95", "value"), float("nan"), "<number>"),
    (False, ("metrics", "step_ms_p95", "unit"), "__drop__", "<number>"),
    (False, ("device", "memory_peak_bytes"), "__drop__", "memory_peak_bytes"),
    (False, ("device", "count"), "1", "device.count"),
    (False, ("correct",), "yes", "'correct'"),
    (False, ("attempted",), 97.0, "'attempted'"),
    (False, ("failed",), "__drop__", "'failed' is missing"),
    (True, ("breakdown", "device_ops"), [["a", 1.0]] * 11, "at most 10"),
])
def test_each_malformation_is_named(traced, path, value, says):
    line = _edit(TRACED if traced else GOOD, path, value)
    expected = set(E2E) | ({"device_step_ms"} if traced else set())
    faults = harness.validate_line(line, expected, traced=traced)
    assert faults and any(says in f for f in faults), faults


def test_a_line_that_is_not_an_object_is_refused():
    assert harness.validate_line([1, 2], E2E, traced=False)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_names_units_and_references_hold_together():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert {m: e2e[m]["unit"] for m in e2e} == harness.END_TO_END
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in cells.values():
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cell, config, traffic = harness.resolve(w["name"])
        assert config["name"] == w["config"] and traffic["global_batch"] > 0
        assert config["reduced"] == configs[w["config"]]["reduced"]
        assert config["tolerance"]["reason"]
    for c in configs.values():
        assert any(w["config"] == c["name"] for w in cells.values())
        assert c["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"]))
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 4)


def test_every_per_layer_metric_has_its_reader_and_its_cells():
    readers = harness.layer_metrics()
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert set(declared) <= set(readers)
    for name, m in declared.items():
        reader = readers[name]
        assert (reader.UNIT, reader.LAYER, reader.MOVES) == \
            (m["unit"], m["layer"], m["moves"]), name
        assert m["moves"] in harness.END_TO_END
        applies = set()
        for w in MANIFEST["workloads"]:
            cell, config, traffic = harness.resolve(w["name"])
            if reader.applies(dict(cell, config=config, traffic=traffic)):
                applies.add(w["name"])
        assert applies == set(m.get("workloads",
                                    [w["name"] for w in MANIFEST["workloads"]]))
    # a reader whose metric no cell of the manifest reports stays undeclared
    for name in set(readers) - set(declared):
        assert not any(readers[name].applies(dict(
            w, config={}, traffic=harness.resolve(w["name"])[2]))
            for w in MANIFEST["workloads"]), name


def test_without_a_tpu_there_is_no_result(capsys):
    from chipbench import peaks, run
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", MANIFEST["workloads"][0]["name"],
                  "--seed", "2147483659", "--seconds", "1", "--trace", "0"])
    assert "needs a TPU" in str(e.value.code)
    with pytest.raises(SystemExit) as e:
        peaks.for_kind("TPU v9 imaginary")
    assert "no entry" in str(e.value.code)
    with pytest.raises(SystemExit) as e:
        harness.resolve("no-such-cell")
    assert "no workload" in str(e.value.code)
    assert capsys.readouterr().out == ""
