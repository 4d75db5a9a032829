"""BENCHMARK.json and the files it names: every cell finds its pieces."""

import json
import re
import subprocess
import sys

import pytest

import manifest

BENCH = manifest.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (manifest.ROOT / p).is_dir()
    script = manifest.ROOT / BENCH["command"][1]
    assert script.is_file() and script.is_relative_to(manifest.HERE)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist(cell):
    w = manifest.workload(BENCH, cell)
    conf = manifest.config(w["config"])
    assert conf["name"] == w["config"]
    mix = manifest.traffic(w["traffic"])
    assert manifest.generator(mix["kind"]).generate
    limits = manifest.limits(cell)
    assert limits["gap_mean"]["limit"] > 0
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_each_cell_reports_what_the_contract_asks(cell, trace):
    names = {m["name"] for m in manifest.metrics_for(BENCH, cell, trace)}
    if not trace:
        assert "setup_s" in names and len(names) >= 2
    else:
        assert names
        moves = {m["moves"] for m in BENCH["per_layer"] if m["name"] in names}
        e2e = {m["name"] for m in manifest.metrics_for(BENCH, cell, False)}
        assert moves <= e2e


@pytest.mark.parametrize("metric", METRICS)
def test_metric_reader_exists(metric):
    assert callable(manifest.reader(metric).read)


def test_names_units_and_bounds():
    seen = set()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["name"] not in seen
        seen.add(m["name"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for c in BENCH["configs"]:
        conf = manifest.config(c["name"])
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert c["reduced"] == conf["reduced"] and c["source"] == conf["source"]


def test_peaks_name_their_source():
    peaks = manifest.load_json(manifest.HERE / "peaks.json")
    for kind, p in peaks.items():
        assert p["source"] and p["bf16_flops_per_s"] > 0 and p["hbm_bytes_per_s"] > 0


def _run(args, cwd, env_extra):
    import os

    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_extra}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    try:
        json.loads(last)
    except ValueError:
        return True
    return False


def test_run_cell_refuses_without_a_chip():
    proc = _run([BENCH["command"][1], "--workload", CELLS[0], "--seed", "3",
                 "--seconds", "1", "--trace", "0"], manifest.ROOT, {})
    assert proc.returncode != 0 and _no_result(proc)
    assert "no TPU" in proc.stderr


def test_run_cell_refuses_outside_a_checkout(tmp_path):
    import shutil

    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(manifest.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run([BENCH["command"][1], "--workload", CELLS[0], "--seed", "3",
                 "--seconds", "1", "--trace", "0"], tmp_path, {})
    assert proc.returncode != 0 and _no_result(proc)
