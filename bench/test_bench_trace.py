"""The reduction from a profiler trace to busy time, program and kernel
time, idle gaps and the breakdown: on a hand-made trace with its numbers
worked out by hand, every per-layer reader fed from it, and on short
traces recorded on the chip (``bench/traces/``)."""
from __future__ import annotations

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from harness import xtrace  # noqa: E402

MS = 1e6      # ns


def hand_made() -> xtrace.Trace:
    # window 0-100 ms; ops 10-30 and 20-40 (overlap), 50-60, 95-110 (cut)
    ops = [("fusion.1", 10 * MS, 20 * MS), ("fusion.2", 20 * MS, 20 * MS),
           ("_lstm_seq_call.1", 50 * MS, 10 * MS),
           ("fusion.1", 95 * MS, 15 * MS)]
    mods = [("jit_plan(1)", 10 * MS, 30 * MS),
            ("jit_prefill_chunk_sample(2)", 50 * MS, 10 * MS),
            ("jit_plan(1)", 95 * MS, 15 * MS)]
    return xtrace.Trace(window=(0.0, 100 * MS), devices={
        "/device:TPU:0": {xtrace.OPS_LINE: ops, xtrace.MODULES_LINE: mods}})


def test_busy_is_the_union_inside_the_window():
    tr = hand_made()
    assert tr.window_s == pytest.approx(0.1)
    # 10-40, 50-60, 95-100 -> 45 ms
    assert xtrace.busy_s(tr) == pytest.approx(0.045)


def test_idle_gaps():
    gaps = xtrace.idle_gaps(hand_made())
    assert [(a / MS, b / MS) for a, b in gaps] == [(0, 10), (40, 50),
                                                  (60, 95)]


def test_time_by_program_and_op():
    tr = hand_made()
    mods = xtrace.time_by_name(tr, xtrace.MODULES_LINE)
    assert mods["jit_plan(1)"][0] == 2
    assert mods["jit_plan(1)"][1] == pytest.approx(0.035)
    n, s = xtrace.matching(mods, ("prefill_chunk_sample",))
    assert (n, s) == (1, pytest.approx(0.010))
    ops = xtrace.time_by_name(tr, xtrace.OPS_LINE)
    assert ops["fusion.1"][1] == pytest.approx(0.025)
    assert xtrace.matching(ops, ("_lstm_seq_call",)) == (1,
                                                         pytest.approx(0.01))


def test_op_name_keeps_the_hlo_instruction_name():
    raw = ("%_lstm_seq_call.1 = (f32[2,1,32]{2,1,0:T(1,128)}) custom-call("
           "f32[128,1,32]{2,1,0} %bitcast.10), custom_call_target="
           "\"tpu_custom_call\"")
    assert xtrace.op_name(raw) == "_lstm_seq_call.1"
    assert xtrace.op_name("fusion.3") == "fusion.3"


def test_breakdown_labels_gaps_with_the_open_span():
    spans = [("serve/tick", 35 * MS, 70 * MS),
             ("bench/waiting-for-arrivals", 55 * MS, 99 * MS)]
    bd = xtrace.breakdown(hand_made(), spans)
    idle = dict(bd["idle_gaps"])
    assert idle["host/outside-spans"] == pytest.approx(0.010)
    assert idle["serve/tick"] == pytest.approx(0.010)
    assert idle["bench/waiting-for-arrivals"] == pytest.approx(0.035)
    assert bd["device_ops"][0][0] == "fusion.1"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_trim_and_json_round_trip(tmp_path):
    tr = xtrace.trimmed(hand_made(), 45 * MS)
    assert tr.window == (0.0, 45 * MS)
    path = str(tmp_path / "t.json")
    xtrace.save(tr, path)
    with open(path) as fh:
        back = xtrace.Trace.from_json(json.load(fh))
    assert back.window == tr.window
    assert xtrace.busy_s(back) == pytest.approx(0.030)


def test_every_reader_on_a_hand_made_run():
    """Each per-layer reader of the manifest, fed the hand-made trace and
    host records of a serving and a HAR run, gives a number or None."""
    import types

    import run as run_lib
    from harness import manifest

    cap = types.SimpleNamespace(t0=10.0, t1=10.1)
    rec = types.SimpleNamespace(due=9.95, phase="window",
                                times=[10.02, 10.05, 10.09])
    records = [
        {"type": "event", "name": "serve/prefill_start", "ts": 10.01,
         "attrs": {"uid": 1, "prompt_len": 300}},
        {"type": "event", "name": "serve/prefill_chunk", "ts": 10.06,
         "attrs": {"uid": 1, "seg_len": 44, "filled": 300,
                   "chunk_s": 0.01}},
    ]
    m = manifest.load_json(os.path.join(BENCH, "configs",
                                        "rwkv6-3b-serve.json"))["model"]
    serving = {"trace": hand_made(), "capture": cap, "records": records,
               "requests": {1: rec}, "spans": [], "model": m}
    har = {"trace": hand_made(), "capture": cap, "windows": 100, "batch": 1,
           "spans": [], "model": manifest.load_json(os.path.join(
               BENCH, "configs", "mobirnn-har.json"))["model"]}
    peak = manifest.peaks("TPU v5 lite")
    for w in manifest.load_manifest()["workloads"]:
        cell = manifest.resolve_cell(manifest.load_manifest(), w["name"])
        tr = serving if cell.config["driver"] == "slot_engine" else har
        out = run_lib.per_layer(cell, tr, peak)
        assert set(out) == {m["name"] for m in cell.per_layer}, w["name"]
        for v in out.values():
            assert v["value"] >= 0


#: traces recorded on one TPU v5e by ``bench/run.py --trace 1`` and cut
#: with ``xtrace.trimmed``: per sample, its window (s), busy seconds, the
#: device's idle gaps, and (count, seconds) of each program or kernel
RECORDED = {
    "har-b1": {"window": 0.020, "busy": 0.001458034, "gaps": 289,
               "ops": {"_lstm_seq_call": (14, 0.001426761)},
               "modules": {"jit_forward": (14, None)}},
    "rwkv6-chat": {"window": 0.067, "busy": 0.058606139, "gaps": 72,
                   "ops": {"_lstm_seq_call": (0, 0.0)},
                   "modules": {"jit_plan": (1, 0.037210877),
                               "prefill_chunk_sample": (1, 0.021397208)}},
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_chip_trace(cell):
    with open(os.path.join(BENCH, "traces", cell + ".json")) as fh:
        tr = xtrace.Trace.from_json(json.load(fh))
    want = RECORDED[cell]
    assert tr.window_s == pytest.approx(want["window"])
    assert xtrace.busy_s(tr) == pytest.approx(want["busy"])
    gaps = xtrace.idle_gaps(tr)
    assert len(gaps) == want["gaps"]
    assert sum(b - a for a, b in gaps) * 1e-9 == pytest.approx(
        want["window"] - want["busy"])
    for line, key in ((xtrace.OPS_LINE, "ops"), (xtrace.MODULES_LINE,
                                                  "modules")):
        table = xtrace.time_by_name(tr, line)
        for needle, (n, sec) in want[key].items():
            got = xtrace.matching(table, (needle,))
            assert got[0] == n, needle
            if sec is not None:
                assert got[1] == pytest.approx(sec), needle
    # op names are HLO instruction names, never whole instructions
    ops = xtrace.time_by_name(tr, xtrace.OPS_LINE)
    assert all(" = " not in k for k in ops)
