"""The benchmark finds its cells, traffic mixes, limits and metric readers
by name, and ``BENCHMARK.json`` keeps the shape the harness reads.

A new configuration, traffic mix or per-layer metric is a new file: the
last test adds one of each to a copy of the benchmark's directory and
runs discovery over it without touching any file that was there.
"""
import json
import re
import shutil

import pytest

from chipbench import bench, workloads

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = bench.load_benchmark()


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["command"][:3] == ["python3", "-m", "chipbench.run"]
    for path in SPEC["paths"]:
        assert (bench.ROOT / path).is_dir()


def test_names_units_and_bounds():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for entry in SPEC["configs"] + SPEC["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    texts = [e[k] for e in SPEC["configs"] + SPEC["workloads"]
             + SPEC["per_layer"] for k in ("why", "source", "layer")
             if k in e]
    for text in texts + SPEC["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text
    assert len(json.dumps(SPEC)) <= 64 * 1024


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves(wl):
    """Config, traffic and limits files exist for the cell, the cell
    builds, and it reports setup_s, another end-to-end metric and a
    per-layer metric, each with a reader file."""
    cell = workloads.Cell(bench.load_config(wl["config"]),
                          bench.load_traffic(wl["traffic"]))
    assert cell.work_per_call > 0
    assert bench.load_limits(wl["name"])
    e2e = {m["name"] for m in bench.metrics_for(SPEC, wl["name"], False)}
    layer = {m["name"] for m in bench.metrics_for(SPEC, wl["name"], True)}
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for name in e2e | layer:
        assert callable(bench.metric_reader(name))
    assert wl["chips"] in (1, 4)


def test_per_layer_moves_a_reported_metric():
    for m in SPEC["per_layer"]:
        for wl in m.get("workloads", []):
            e2e = {e["name"] for e in bench.metrics_for(SPEC, wl, False)}
            assert m["moves"] in e2e, (m["name"], wl)


def test_config_files_are_their_own():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    used = {wl["config"] for wl in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        data = json.loads((bench.ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert data["source"] in c["source"]


def test_new_files_add_a_cell_and_a_metric(tmp_path):
    """Copy the benchmark, add a configuration, a traffic mix, limits and
    a metric reader as new files, and list the cell: discovery finds all
    of them, and no file that was there changed."""
    base = tmp_path / "chipbench"
    shutil.copytree(bench.BENCH_DIR, base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    config = dict(bench.load_config("paper_k50"), name="paper_k50_k8",
                  K=8, mus=[10.0], sigma2_fracs=[0.0])
    (base / "configs" / "paper_k50_k8.json").write_text(json.dumps(config))
    traffic = dict(bench.load_traffic("we_pair"), trials=64)
    (base / "traffic" / "we_pair_small.json").write_text(
        json.dumps(traffic))
    (base / "limits" / "k8_pair.json").write_text(
        json.dumps({"limits": {"call_gap_se": 8.0}}))
    (base / "metrics" / "calls_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.calls))\n")
    spec = dict(SPEC)
    spec["workloads"] = SPEC["workloads"] + [
        {"name": "k8_pair", "config": "paper_k50_k8",
         "traffic": "we_pair_small", "chips": 1, "why": "test"}]
    spec["per_layer"] = SPEC["per_layer"] + [
        {"name": "calls_in_window", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "entry",
         "moves": "mc_trials_per_s", "workloads": ["k8_pair"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    loaded = bench.load_benchmark(tmp_path)
    wl = bench.find_workload(loaded, "k8_pair")
    cell = workloads.Cell(bench.load_config(wl["config"], base),
                          bench.load_traffic(wl["traffic"], base))
    assert cell.work_per_call == 1 * 64 * 2
    assert workloads.het_rates(cell.config).shape == (1, 8)
    assert bench.load_limits("k8_pair", base) == {"call_gap_se": 8.0}
    names = [m["name"] for m in bench.metrics_for(loaded, "k8_pair", True)]
    assert "calls_in_window" in names
    read = bench.metric_reader("calls_in_window", base)
    assert read(type("Ctx", (), {"calls": [1, 2, 3]})()) == 3.0
    for path, data in before.items():
        assert path.read_bytes() == data


def test_unknown_names_are_refused():
    with pytest.raises(KeyError, match="have"):
        bench.load_config("no_such_config")
    with pytest.raises(KeyError, match="no workload"):
        bench.find_workload(SPEC, "no_such_cell")
    with pytest.raises(KeyError):
        bench.metric_reader("no_such_metric")
