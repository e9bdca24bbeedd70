"""The control comes out not correct: the plain reference, computed in the
nearest precision below the one each configuration states, read against
the reference in f32 over a fixed arrival schedule and set-up's rounds
and drains, fails one of the cell's numbers. Run here at a small size; the readings at each cell's own
size, on the chip, are ``control.py``'s and are listed in PERF.md."""
import os

import pytest

import pb_check
import pb_models
import pb_reference
import pb_spec
from test_chipbench_faults import small_cell

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (client, model version it trained from, local steps) per drain: two
#: clients from the first model, then the first one again from the second
SCHEDULE = [[pb_reference.Arrival(1, 1, 2)], [pb_reference.Arrival(0, 1, 2)],
            [pb_reference.Arrival(1, 2, 2)]]
#: set-up's local rounds (client, K) and drain sizes
ROUNDS = [(0, 1), (0, 2), (1, 2)]
DRAINS = [1, 2, 3]


def replay(cell, mode):
    cfg, traffic = cell.config, cell.traffic
    model = pb_models.model(cfg)
    fed = {**cfg["fed"], **traffic["fed"]}
    data = model.make_data(cfg, {**traffic, "clients": 2}, 2 ** 32 + 5)
    seed = 1234567
    rep = pb_reference.replay(model, cfg, traffic, fed, seed, data[0],
                              SCHEDULE, mode=mode)
    drains = fed.get("batch_window", 0.0) != 0.0
    return rep._replace(
        rounds=pb_reference.replay_rounds(model, cfg, traffic, fed, seed,
                                          data[0], ROUNDS, mode=mode),
        drains=(pb_reference.replay_drains(model, cfg, fed, seed, 99, DRAINS)
                if drains else []))


def readings(cell, mode):
    return pb_check.numbers(replay(cell, mode), replay(cell, "f32"))


@pytest.mark.parametrize("name", ["danube2l-seq", "mlp-burst",
                                  "mlp-paper-seq"])
def test_control_is_not_correct(name):
    cell = small_cell(name)
    values = readings(cell, cell.config["control_precision"])
    assert not pb_check.verdict(values, cell.limits), values


@pytest.mark.parametrize("name", ["danube2l-seq", "mlp-paper-seq"])
def test_reference_agrees_with_itself(name):
    cell = small_cell(name)
    values = readings(cell, "f32")
    assert values == {k: 0.0 for k in cell.limits}
