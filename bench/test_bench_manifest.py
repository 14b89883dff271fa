"""The manifest keeps to its contract, every name finds its file, and the
command refuses to run without a TPU."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import manifest  # noqa: E402

MAN = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
E2E_KEYS = {"name", "unit", "better", "bound", "source", "workloads"}
PL_KEYS = {"name", "unit", "better", "source", "layer", "moves", "workloads"}


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert MAN["paths"] == ["bench"]
    assert 1 <= MAN["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_units_and_text(kind):
    entries = MAN[kind]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert TEXT.match(e[key]), (e["name"], key)
        for key in ("config", "traffic"):
            if key in e:
                assert NAME.match(e[key]), e[key]
        for key in e.get("reduced", []):
            assert NAME.match(key), key


def test_metric_entries():
    names = {w["name"] for w in MAN["workloads"]}
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in MAN["end_to_end"]:
        assert set(m) <= E2E_KEYS
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", names)) <= names
    for m in MAN["per_layer"]:
        assert set(m) <= PL_KEYS
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", names)
        assert set(m["workloads"]) <= set(moved), m["name"]


def test_every_cell_reports_enough():
    for w in MAN["workloads"]:
        cell = manifest.resolve_cell(MAN, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        assert w["chips"] in (1, 4)


def test_files_found_by_name():
    for c in MAN["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = manifest.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        cfg["_dir"] = os.path.dirname(os.path.join(ROOT, c["file"]))
        assert manifest.reference(cfg) is not None
        assert manifest.driver(cfg) is not None
    for w in MAN["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in MAN["per_layer"]:
        assert callable(manifest.metric_reader(m["name"]).read)


def test_peaks_table_refuses_unknown_devices():
    assert manifest.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        manifest.peaks("cpu")


def test_command_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "har-b1", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 3, proc.stderr
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_result_line_shape():
    """The result line a run prints: keys the driver reads, checks last."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import run as run_lib

    result = {"correct": True, "attempted": 3, "failed": 0,
              "metrics": {"setup_s": {"value": 1.0, "unit": "s"}},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1, "memory_peak_bytes": 1},
              "checks": {"x": {"value": 0.1, "limit": 0.2}},
              "_notes": {"n": 1}}
    import contextlib
    import io
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        run_lib.emit(result)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert err.getvalue().strip().splitlines()[-1].startswith("check x")
