"""A run with the timed path broken underneath comes out not correct.

Each cell runs here at a small size on the CPU, with the harness's look
for a chip skipped and everything else as in a chip run: set-up, warm-up
drains, a short window, the reference replay and the comparison against
the cell's own limits. The faults a training cell can have on one chip:

* ``state_unchanged`` -- the server's aggregation returns its model
  unchanged;
* ``half_batch``      -- every local step's loss leaves half its rows out;
* ``altered_delta``   -- the first update is altered where the client
  produces it;
* ``altered_round``   -- every local round of the largest K the traffic
  allows is altered where the client produces it, as a miscompiled
  program for one K would be: caught by set-up's round check even where
  the warm-up drains never train with that K.
"""
import os
import time

import pytest

import pb_cell
import pb_spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAULTS = ("state_unchanged", "half_batch", "altered_delta", "altered_round")

#: the same cells, cut to a size a CPU test holds
SMALL = {
    "danube2l-seq": dict(config=dict(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, vocab_size=256,
        attention_chunk=16), traffic=dict(seq_len=32)),
    "mlp-burst": dict(traffic=dict(clients=6, warmup_drains=4)),
    "mlp-paper-seq": dict(traffic=dict(warmup_drains=4),
                          fed=dict(k_max=6)),
}


def small_cell(name):
    cell = pb_spec.resolve(ROOT, name)
    cut = SMALL[name]
    cell.config.update(cut.get("config", {}))
    cell.traffic.update(cut.get("traffic", {}))
    cell.traffic["fed"].update(cut.get("fed", {}))
    return cell


def run(name, fault=""):
    return pb_cell.run_cell(small_cell(name), 2 ** 33 + 7, 0.3, False,
                            time.perf_counter(), ROOT, device_check=False,
                            fault=fault)


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", sorted(SMALL))
def test_fault_is_not_correct(name, fault):
    out = run(name, fault)
    checks = out["result"]["checks"]
    assert out["result"]["correct"] is False, checks
    assert out["check_lines"][0].startswith("check loss_gap")
    if fault == "altered_round":
        assert checks["round_gap"]["value"] > checks["round_gap"]["limit"]


@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_run_is_correct(name):
    out = run(name)
    res = out["result"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert out["info"]["counters"]["compiles_in_window"] == 0
