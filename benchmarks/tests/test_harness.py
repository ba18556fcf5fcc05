"""The harness end to end at toy widths on the CPU, through the option
the driver never passes, and the shape of BENCHMARK.json.

    pytest benchmarks/tests

Not part of the repo's tier-1 tests. A toy run's numbers mean nothing:
what is checked is the last line's keys, that every metric a cell lists
is there, and that the run refuses without a TPU.
"""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def run_cell(cell, *extra, seed=5, seconds=3, trace=0, toy=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, *BENCH["command"][1:], "--workload", cell,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace), *extra]
    if toy:
        cmd += ["--toy", "1"]
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def metrics_of(cell, group):
    return [m for m in BENCH[group] if cell in m.get("workloads", [cell])]


@pytest.mark.parametrize("cell", CELLS)
def test_toy_run_prints_the_result_object(cell):
    line = last_line(run_cell(cell))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"]: m["unit"] for m in metrics_of(cell, "end_to_end")}
    assert "setup_s" in want and len(want) >= 2
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # a toy run can never produce a line that names a TPU
    assert line["device"]["platform"] == "cpu"
    for entry in line["checks"].values():
        assert set(entry) == {"value", "limit", "ok"}


@pytest.mark.parametrize("cell", CELLS)
def test_traced_toy_run_reports_per_layer_metrics(cell):
    line = last_line(run_cell(cell, trace=1))
    listed = {m["name"] for m in metrics_of(cell, "per_layer")}
    assert set(line["metrics"]) <= listed
    # what needs no device trace is there even on the CPU
    counted = {m["name"] for m in metrics_of(cell, "per_layer")
               if m["source"] == "program_counter"
               and not m["name"].endswith("mfu_pct")}
    assert counted <= set(line["metrics"])
    assert {"busy_s", "window_s"} <= set(line["device"])
    # a share of a peak is left out where there is no peak: never 0
    assert not any("mfu" in k or "roofline" in k for k in line["metrics"])


def test_refuses_without_a_tpu():
    proc = run_cell(CELLS[0], toy=False)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_unknown_device_is_an_error():
    from benchmarks import peaks
    with pytest.raises(SystemExit):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12


def test_names_and_units_keep_to_the_allowed_characters():
    names = [e["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for e in BENCH[group]]
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    units = [m["unit"] for g in ("end_to_end", "per_layer") for m in BENCH[g]]
    assert all(UNIT.match(u) for u in units), units
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in BENCH[group]]
        assert len(seen) == len(set(seen))
    metric_names = [m["name"] for g in ("end_to_end", "per_layer")
                    for m in BENCH[g]]
    assert len(metric_names) == len(set(metric_names))


def test_entries_have_just_the_keys_of_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_every_cell_reports_what_its_per_layer_metrics_move():
    end = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = end[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)
    for cell in CELLS:
        assert metrics_of(cell, "per_layer"), cell
        assert len(metrics_of(cell, "end_to_end")) >= 2, cell


def test_every_configuration_has_a_cell_and_every_name_its_file():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    here = os.path.join(ROOT, BENCH["paths"][0])
    for w in BENCH["workloads"]:
        mix = json.load(open(os.path.join(here, "traffic",
                                          w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(here, "runners",
                                           mix["runner"] + ".py"))
        assert os.path.exists(os.path.join(here, "limits",
                                           w["name"] + ".json"))
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(here, "layer_metrics",
                                           m["name"] + ".py")), m["name"]


def test_a_pinned_order_leaves_the_seed_the_token_ids_alone():
    from benchmarks.traffic import RequestSource
    here = os.path.join(ROOT, BENCH["paths"][0], "traffic")
    docs = json.load(open(os.path.join(here, "docs-closed16.json")))
    chat = json.load(open(os.path.join(here, "chat-closed32.json")))
    assert "order_seed" in docs and "order_seed" not in chat
    a, b = (RequestSource(docs, 50257, s) for s in (5, 2147483999))
    assert list(a.prompt_len) == list(b.prompt_len)
    assert list(a.answer_len) == list(b.answer_len)
    assert list(a.doc_of) == list(b.doc_of)
    (pa, wa), (pb, wb) = a.get(3), b.get(3)
    assert len(pa) == len(pb) and wa == wb and pa != pb
    # without it the seed reorders one multiset of sizes
    a, b = (RequestSource(chat, 50257, s) for s in (5, 2147483999))
    assert list(a.prompt_len) != list(b.prompt_len)
    assert sorted(a.prompt_len) == sorted(b.prompt_len)
