"""Every cell's files are found by name, and a cell, configuration or
metric added as new files is picked up with no edit to a file the
benchmark already has."""
import json
import os
import shutil

import pytest

import pb_check
import pb_models
import pb_spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = pb_spec.resolve(ROOT, name)
    assert cell.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == name)
    # every cell checks its warm-up drains and set-up's local rounds; a
    # cell with a drain window checks set-up's drains too
    drains = cell.traffic["fed"].get("batch_window", 0.0) != 0.0
    assert set(cell.limits) == {
        "loss_gap", "grad_gap", "change_gap", "round_gap",
        *(["drain_gap"] if drains else [])}
    assert set(cell.limits) <= set(pb_check.NUMBERS)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(pb_spec.reader(ROOT, m["name"]))
    model = pb_models.model(cell.config)
    for fn in ("make_weights", "make_data", "make_batcher", "half_batch",
               "ref_loss", "step_flops", "param_count"):
        assert callable(getattr(model, fn)), fn
    assert callable(pb_models.adapter(cell.config).program_task)


def test_configs_list_their_cuts():
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert c["file"].startswith(BENCH["paths"][0] + "/")


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        pb_spec.resolve(ROOT, "no-such-cell")


def test_added_files_are_picked_up(tmp_path):
    """A later change adds a configuration, a traffic mix, a cell and a
    metric as files plus entries in BENCHMARK.json; the harness finds them
    without an edit to any file it already has."""
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    d = tmp_path / "perfbench"
    cfg = json.loads((d / "configs" / "synthetic-1-1-mlp.json").read_text())
    cfg["name"] = "synthetic-1-1-mlp-wide"
    cfg["hidden"] = [128, 64]
    (d / "configs" / "synthetic-1-1-mlp-wide.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((d / "traffic" / "paper10-seq.json").read_text())
    traffic["clients"] = 20
    (d / "traffic" / "paper20-seq.json").write_text(json.dumps(traffic))
    (d / "cells" / "wide-seq.json").write_text(json.dumps(
        {"limits": {k: 0.5 for k in pb_check.NUMBERS}}))
    (d / "metrics" / "updates_per_drain.py").write_text(
        "def read(run):\n    return run.updates / run.drains\n")
    bench["configs"].append({
        "name": "synthetic-1-1-mlp-wide", "source": "https://example.org",
        "file": "perfbench/configs/synthetic-1-1-mlp-wide.json",
        "reduced": [], "why": "wider"})
    bench["workloads"].append({
        "name": "wide-seq", "config": "synthetic-1-1-mlp-wide",
        "traffic": "paper20-seq", "chips": 1, "why": "wider"})
    bench["per_layer"].append({
        "name": "updates_per_drain", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "event loop",
        "moves": "updates_per_s", "workloads": ["wide-seq"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = pb_spec.resolve(str(tmp_path), "wide-seq")
    assert cell.config["hidden"] == [128, 64]
    assert cell.traffic["clients"] == 20
    assert cell.limits["loss_gap"] == 0.5
    assert "updates_per_drain" in [m["name"] for m in cell.per_layer]
    read = pb_spec.reader(str(tmp_path), "updates_per_drain")
    assert read(type("Run", (), {"updates": 6, "drains": 3})) == 2
    model = pb_models.model(cell.config)
    assert model.param_count(cell.config) == 60 * 128 + 128 + 128 * 64 + 64 \
        + 64 * 10 + 10
    # the metric is scoped to its cell
    other = pb_spec.resolve(str(tmp_path), "mlp-paper-seq")
    assert "updates_per_drain" not in [m["name"] for m in other.per_layer]
