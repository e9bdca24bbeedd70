"""The program's host spans (repro.utils.trace) under a profile session:
where each lands, what its stats count, and that the spans neither cost
nor change anything with the profiler off."""
import contextlib
import copy
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.core import cohort
from repro.core.client import Client
from repro.core.simulator import FederatedSimulation
from repro.utils import pytree as pt
from repro.utils import trace

PREFIXES = ("loop.", "server.", "client.")
SERVER_CHILDREN = ("server.flatten", "server.kernels", "server.sync",
                   "server.book", "server.unflatten")


def _fed(engine, compression="off"):
    return dataclasses.replace(configs.SYNTHETIC_1_1.fed, backend="pallas",
                               client_engine=engine, num_clients=5,
                               k_initial=2, k_max=4,
                               delta_compression=compression)


def _spans(trace_dir):
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        s = ev.start_ns
                        out.append((ev.name, s, s + ev.duration_ns,
                                    dict(ev.stats)))
    return out


def _traced_run(tmp_path_factory, engine, window, compression="off",
                algorithm="asyncfeded"):
    sim = FederatedSimulation(configs.SYNTHETIC_1_1,
                              _fed(engine, compression), algorithm,
                              seed=2, batch_window=window)
    d = str(tmp_path_factory.mktemp(f"trace-{engine}"))
    with jax.profiler.trace(d):
        res = sim.run(max_time=1e9, max_updates=8, eval_every=5)
    return sim, res, _spans(d)


@pytest.fixture(scope="module")
def loop_run(tmp_path_factory):
    return _traced_run(tmp_path_factory, "loop", 0.0)


@pytest.fixture(scope="module")
def cohort_run(tmp_path_factory):
    return _traced_run(tmp_path_factory, "cohort", 0.5)


@pytest.fixture(scope="module")
def loop_int8_run(tmp_path_factory):
    return _traced_run(tmp_path_factory, "loop", 0.0, "int8")


@pytest.fixture(scope="module")
def cohort_int8_run(tmp_path_factory):
    return _traced_run(tmp_path_factory, "cohort", 0.5, "int8")


@pytest.fixture(scope="module")
def loop_bf16_run(tmp_path_factory):
    return _traced_run(tmp_path_factory, "loop", 0.0, "bf16")


@pytest.fixture(scope="module")
def loop_displacement_run(tmp_path_factory):
    return _traced_run(tmp_path_factory, "loop", 0.0,
                       algorithm="asyncfeded-displacement")


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _within(inner, outers):
    return [o for o in outers if o[1] <= inner[1] and inner[2] <= o[2]]


@pytest.mark.parametrize("run", ["loop_run", "cohort_run", "loop_int8_run",
                                 "cohort_int8_run"])
def test_server_spans_nest_inside_the_drain_inside_the_loop(run, request):
    _, res, spans = request.getfixturevalue(run)
    drains = _named(spans, "server.drain")
    loops = _named(spans, "loop.drain")
    assert len(drains) == len(loops) == res.total_drains
    assert sum(s[3]["B"] for s in drains) == res.total_updates
    for d in drains:
        assert len(_within(d, loops)) == 1
    for name in SERVER_CHILDREN:
        children = _named(spans, name)
        assert children, name
        for c in children:
            assert len(_within(c, drains)) == 1, c


def _gram_bytes(b):
    """Bytes of a batched drain's packed Gram sweep: d0 and dn_sq (B,),
    cross and gram (B, B), f32."""
    return 4 * (2 * b + 2 * b * b)


@pytest.mark.parametrize("run", ["loop_run", "loop_int8_run",
                                 "loop_bf16_run", "loop_displacement_run"])
def test_sequential_drains_read_their_scalars_once_per_update(run, request):
    _, res, spans = request.getfixturevalue(run)
    drains = _named(spans, "server.drain")
    assert {d[3]["path"] for d in drains} == {"seq"}
    syncs = _named(spans, "server.sync")
    # gamma, eta, dist and dnorm, f32, in one read
    assert [s[3] for s in syncs] == [{"reads": 1, "d2h_bytes": 16}] * (
        res.total_updates)
    for ev in _named(spans, "loop.eval"):
        assert ev[3] == {"reads": 2}


def test_batched_drains_read_the_gram_sweep_once(cohort_run):
    _, _, spans = cohort_run
    batched = [d for d in _named(spans, "server.drain")
               if d[3]["path"] == "batched"]
    assert batched and all(d[3]["B"] > 1 for d in batched)
    for d in batched:
        syncs = [s for s in _named(spans, "server.sync") if _within(s, [d])]
        assert [s[3] for s in syncs] == [
            {"reads": 1, "d2h_bytes": _gram_bytes(d[3]["B"])}]
        kernels = [s for s in _named(spans, "server.kernels")
                   if _within(s, [d])]
        sched = [s for s in _named(spans, "server.schedule")
                 if _within(s, [d])]
        assert len(kernels) == len(sched) == 1
        assert _within(syncs[0], kernels) and _within(sched[0], kernels)


@pytest.mark.parametrize("run", ["loop_run", "cohort_run", "loop_int8_run",
                                 "cohort_int8_run"])
def test_every_aggregation_stages_and_sweeps_inside_spans(run, request):
    """Each update of a sequential drain, and each batched drain once,
    stages its deltas in a ``server.flatten`` span and runs its kernels
    in a ``server.kernels`` span, whatever the payload; a batched drain
    reads its Gram sweep once, inside its kernels."""
    _, _, spans = request.getfixturevalue(run)
    drains = _named(spans, "server.drain")
    assert (any(d[3]["path"] == "batched" for d in drains)
            == run.startswith("cohort"))
    for d in drains:
        want = d[3]["B"] if d[3]["path"] == "seq" else 1
        for name in ("server.flatten", "server.kernels"):
            inside = [s for s in _named(spans, name) if _within(s, [d])]
            assert len(inside) == want, (name, d)
        if d[3]["path"] == "batched":
            syncs = [s for s in _named(spans, "server.sync")
                     if _within(s, [d])]
            assert [s[3] for s in syncs] == [
                {"reads": 1, "d2h_bytes": _gram_bytes(d[3]["B"])}]
            assert _within(syncs[0], _named(spans, "server.kernels"))


def test_server_flatten_counts_host_deltas_it_uploads(loop_run, cohort_run):
    # loop-engine deltas stay on the device: nothing to upload
    assert {s[3]["h2d_bytes"] for s in _named(loop_run[2],
                                              "server.flatten")} == {0}
    sim, _, spans = cohort_run
    n = pt.tree_size(sim.server.params)
    for d in _named(spans, "server.drain"):
        if d[3]["path"] == "batched":
            flat, = [s for s in _named(spans, "server.flatten")
                     if _within(s, [d])]
            # the cohort's deltas come back as f32 host arrays
            assert flat[3]["h2d_bytes"] == d[3]["B"] * n * 4


def test_server_flatten_says_where_it_staged(loop_run, cohort_run):
    # loop-engine deltas go to the staging program as device leaves; the
    # cohort's host deltas are joined on the host and uploaded once
    assert {s[3]["staging"] for s in _named(loop_run[2],
                                            "server.flatten")} == {"device"}
    assert {s[3]["staging"] for s in _named(cohort_run[2],
                                            "server.flatten")} == {"host"}


def test_cohort_fanout_stats_match_the_arrays_moved(cohort_run):
    sim, _, spans = cohort_run
    n = pt.tree_size(sim.server.params)
    for f in _named(spans, "client.fanout"):
        assert f[3]["engine"] == "cohort"
        c_pad = cohort.bucket_size(f[3]["jobs"])
        sync, = [s for s in _named(spans, "client.sync") if _within(s, [f])]
        # deltas and momentum (f32, n each) and the loss of every row
        assert sync[3] == {"reads": 1, "d2h_bytes": c_pad * (2 * n + 1) * 4}
        # the batcher draws, then the stacking of what the core uploads
        assert len([s for s in _named(spans, "client.stage")
                    if _within(s, [f])]) == 2


def test_cohort_stage_counts_the_host_arrays_it_uploads(tmp_path):
    sim = FederatedSimulation(configs.SYNTHETIC_1_1, _fed("cohort"),
                              "asyncfeded", seed=4)
    clients, k = sim.clients[:3], 2
    n = pt.tree_size(sim.server.params)
    bs = sim.fed.local_batch_size
    with jax.profiler.trace(str(tmp_path)):
        cohort.run_cohort(sim.task, clients, sim.server.params, [k] * 3,
                          [1] * 3)
    draws, stacked = _named(_spans(str(tmp_path)), "client.stage")
    c_pad = cohort.bucket_size(3)
    # per row: K batches of 60 f32 features and an int32 label, the f32
    # momentum and the learning rate; the shared model is broadcast on
    # the device
    assert draws[3] == {}
    assert stacked[3] == {
        "h2d_bytes": c_pad * (k * bs * (60 * 4 + 4) + n * 4 + 4)}


def test_loop_client_stats_match_the_arrays_moved(tmp_path):
    task = configs.SYNTHETIC_1_1
    fed = _fed("loop")
    sim = FederatedSimulation(task, fed, "asyncfeded", seed=4)
    client = Client(0, sim.task, sim.task.load_data(fed, seed=4)[0][0], fed)
    bx, by = copy.deepcopy(client.batcher).next_stacked(3)
    with jax.profiler.trace(str(tmp_path)):
        client.run_local(sim.server.params, 3, 1)
    spans = _spans(str(tmp_path))
    stage, = _named(spans, "client.stage")
    sync, = _named(spans, "client.sync")
    assert stage[3] == {"h2d_bytes": bx.nbytes + by.nbytes}
    assert sync[3] == {"reads": 1, "d2h_bytes": 4}


def test_tracing_changes_no_result(loop_run):
    _, traced, _ = loop_run
    plain = FederatedSimulation(configs.SYNTHETIC_1_1, _fed("loop"),
                                "asyncfeded", seed=2, batch_window=0.0).run(
        max_time=1e9, max_updates=8, eval_every=5)
    assert [dataclasses.astuple(h) for h in plain.history] == [
        dataclasses.astuple(h) for h in traced.history]
    assert [p.accuracy for p in plain.points] == [
        p.accuracy for p in traced.points]


def test_off_path_is_the_shared_null_context():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    sp = trace.span("server.sync", reads=4)
    assert sp is trace.span("client.stage", h2d_bytes=lambda: 1 / 0)
    assert isinstance(sp, contextlib.nullcontext)
    with sp as entered:
        assert entered is None


def test_host_nbytes_counts_numpy_leaves_only():
    tree = {"a": np.zeros((3, 4), np.float32), "b": jax.numpy.ones(5),
            "c": [np.zeros(2, np.int8)]}
    assert trace.nbytes(tree) == 48 + 20 + 2
    assert trace.host_nbytes(tree) == 48 + 2
