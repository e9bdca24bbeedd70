"""FLOP and byte counts against hand values, and the per-layer readers'
arithmetic on a hand-made window."""
import json
import os
from types import SimpleNamespace

import pytest

import pb_cell
import pb_models
import pb_spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "perfbench", "configs", f"{name}.json")) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(ROOT, "perfbench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_param_counts():
    danube = _config("h2o-danube-1.8b-2l")
    mlp = _config("synthetic-1-1-mlp")
    assert pb_models.model(danube).param_count(danube) == 302_789_120
    assert pb_models.model(danube).matmul_params(danube) == 220_856_320
    assert pb_models.model(mlp).param_count(mlp) == 6_314


def test_step_flops():
    danube = _config("h2o-danube-1.8b-2l")
    mlp = _config("synthetic-1-1-mlp")
    # 3 x (2 x 1024 tokens x 220,856,320 + 2 layers x 2 products x
    #      2 x 2 seqs x 32 heads x 80 x 131,328 causal pairs)
    want = 3 * (2 * 1024 * 220_856_320 + 2 * 2 * 2 * 2 * 32 * 80 * 131_328)
    assert pb_models.model(danube).step_flops(
        danube, _traffic("silo2-seq")) == want
    # 32 rows x (4*60*64 + 6*64*32 + 6*32*10)
    assert pb_models.model(mlp).step_flops(
        mlp, _traffic("paper10-seq")) == 32 * 29_568


@pytest.mark.parametrize("b,mode,per_elem", [
    (1, "off", 16), (3, "off", 8 + 3 * 8), (2, "bf16", 8 + 2 * 6),
    (4, "int8", 8 + 4 * (5 + 4 / 1024))])
def test_least_drain_bytes(b, mode, per_elem):
    n = 302_789_120
    assert pb_cell.least_drain_bytes(n, b, mode) == pytest.approx(
        n * per_elem, rel=1e-12)


def test_least_drain_flops():
    assert pb_cell.least_drain_flops(10, 1) == 70
    assert pb_cell.least_drain_flops(10, 3) == 10 * (21 + 12)


def _run(**kw):
    base = dict(window_s=10.0, updates=100, drains=50,
                server_s=[0.02] * 50, client_s=4.0, drain_sizes=[2] * 50,
                compiles=0, flops=1.97e14, least_agg_s=[0.004] * 50,
                peaks={"bf16_flops_per_s": 197e12,
                       "hbm_bytes_per_s": 819e9}, chips=1,
                trace={"window_s": 10.0, "busy_s": 7.5,
                       "busy_in_server_s": 0.8})
    base.update(kw)
    return SimpleNamespace(**base)


@pytest.mark.parametrize("metric,want", [
    ("loop_ms_per_update", (10.0 - 1.0 - 4.0) / 100 * 1e3),
    ("client_ms_per_update", 40.0),
    ("compiles_in_window", 0),
    ("mfu", 10.0),
    ("agg_p50_ms", 20.0),
    ("mfu.agg", 20.0),
    ("fedagg_roofline", 25.0),
    ("idle_pct", 25.0)])
def test_metric_readers(metric, want):
    assert pb_spec.reader(ROOT, metric)(_run()) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["mfu", "mfu.agg", "fedagg_roofline",
                                    "idle_pct"])
def test_shares_read_nothing_without_their_source(metric):
    # no peaks table entry and no trace: nothing to read, never a 0
    assert pb_spec.reader(ROOT, metric)(
        _run(peaks=None, trace=None, least_agg_s=[None] * 50)) is None
