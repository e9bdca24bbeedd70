"""The ring drain's in-place aggregation: ``ops.flat_aggregate(...,
in_place=True)`` writes x_{t+1} into the delta buffer it is given, bitwise
what the copying step gives, and the server hands it only a buffer the
drain made itself. Whatever delta a caller passes stays readable."""
import contextlib
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.configs.base import FedConfig
from repro.core import compression
from repro.core.server import ClientUpdate, make_server
from repro.core.simulator import FederatedSimulation
from repro.kernels.fedagg import ops
from repro.utils import pytree as pt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: a program compiled for the backend, and a function traced to a jaxpr
STAGES = ("/jax/core/compile/backend_compile_duration",
          "/jax/core/compile/jaxpr_trace_duration")


def _vec(n, seed):
    rng = np.random.default_rng(seed)
    return ops.pad_flat_vector(jnp.asarray(
        rng.standard_normal(n).astype(np.float32)))


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("n", [2 * ops._BLOCK, 2 * ops._BLOCK - 1000],
                         ids=["aligned", "unaligned"])
def test_in_place_step_is_bitwise_the_copying_step(n):
    xt, xs, d = (_vec(n, s) for s in range(3))
    want = ops.flat_aggregate(xt, xs, d, lam=1.5, eps=0.5, cap=4.0)
    assert not d.is_deleted()
    mine = d + 0.0
    got = ops.flat_aggregate(xt, xs, mine, lam=1.5, eps=0.5, cap=4.0,
                             in_place=True)
    assert mine.is_deleted() and not xt.is_deleted() and not xs.is_deleted()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_bits(g), _bits(w))


def _tree(seed, one_leaf=False):
    rng = np.random.default_rng(seed)
    if one_leaf:
        # a single 1-D f32 leaf already the padded length: the one case
        # where a flatten could hand its input back as its output
        return rng.standard_normal(ops._BLOCK).astype(np.float32)
    return {"w": rng.standard_normal((33, 7)).astype(np.float32),
            "b": [rng.standard_normal(129).astype(np.float32)]}


def _kernel_stats(srv, monkeypatch):
    """Record the ``in_place`` keyword of each ``flat_aggregate`` call and
    whether it carried a displacement ``drift``."""
    seen = []
    agg = srv._agg["flat_aggregate"]

    def spy(*a, **kw):
        seen.append((kw["in_place"], kw["drift"] is not None))
        return agg(*a, **kw)
    monkeypatch.setitem(srv._agg, "flat_aggregate", spy)
    return seen


@pytest.mark.parametrize("case,in_place", [
    ("device", True), ("numpy", True), ("one_leaf", True),
    ("forwarded", False), ("bf16", False), ("displacement", False)])
def test_the_callers_delta_stays_readable(case, in_place, monkeypatch):
    one_leaf = case in ("one_leaf", "forwarded")
    params = _tree(0, one_leaf)
    fed = FedConfig(delta_compression="bf16" if case == "bf16" else "off")
    srv = make_server("asyncfeded", params, fed, backend="pallas",
                      gmis_mode=("displacement" if case == "displacement"
                                 else "ring"))
    srv.on_connect(0)
    delta = jax.tree.map(jnp.asarray, _tree(1, one_leaf))
    if case == "numpy":
        delta = _tree(1)
    elif case == "forwarded":
        # the flatten hands back the caller's own array, as JAX may do
        # for an input it would only copy: the server must not donate it
        monkeypatch.setattr(pt.FlatSpec, "flatten", lambda self, tree: tree)
    elif case == "bf16":
        vec = ops.pad_flat_vector(jnp.concatenate(
            [jnp.ravel(l) for l in jax.tree.leaves(delta)]))
        delta = compression.quantize_vec(vec, "bf16", srv._flat.spec.n)
    before = jax.tree.map(np.array, delta)
    seen = _kernel_stats(srv, monkeypatch)
    srv.on_update(ClientUpdate(0, 1, 2, delta))
    assert seen == [(in_place, case == "displacement")]
    for leaf, want in zip(jax.tree.leaves(delta), jax.tree.leaves(before)):
        assert not getattr(leaf, "is_deleted", lambda: False)()
        np.testing.assert_array_equal(np.asarray(leaf), want)
    assert np.isfinite(srv.history[-1].gamma)


def test_the_server_model_is_the_copying_servers_model():
    params = _tree(0)
    runs = []
    for in_place in (True, False):
        srv = make_server("asyncfeded", params, FedConfig(), backend="pallas")
        if not in_place:
            agg = srv._agg["flat_aggregate"]
            srv._agg["flat_aggregate"] = (
                lambda *a, _agg=agg, **kw: _agg(*a, **{**kw,
                                                       "in_place": False}))
        for i in range(3):
            srv.on_connect(i)
        for i in range(5):
            delta = jax.tree.map(jnp.asarray, _tree(10 + i))
            srv.on_update(ClientUpdate(i % 3, max(1, srv.t - i % 3), 2,
                                       delta))
        runs.append((srv._flat.vec, [dataclasses.astuple(h)
                                     for h in srv.history]))
    (v1, h1), (v2, h2) = runs
    np.testing.assert_array_equal(_bits(v1), _bits(v2))
    assert h1 == h2


@contextlib.contextmanager
def _counting_compiles():
    seen = []

    def on_event(event, duration, **kw):
        if event in STAGES:
            seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _drive(seed):
    srv = make_server("asyncfeded", _tree(seed), FedConfig(),
                      backend="pallas")
    srv.on_connect(0)
    for i in range(2):
        delta = jax.tree.map(jnp.asarray, _tree(10 * seed + i))
        reply = srv.on_update(ClientUpdate(0, srv.t, 2, delta))
    jax.block_until_ready(jax.tree.leaves(reply.params))
    return srv


def test_a_second_server_of_the_same_layout_compiles_nothing():
    _drive(1)
    with _counting_compiles() as seen:
        _drive(2)
    assert seen == []


def test_state_unchanged_plant_still_leaves_the_model(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(ROOT, "perfbench"))
    import pb_cell
    srv = make_server("asyncfeded", _tree(0), FedConfig(), backend="pallas")
    pb_cell.plant_server(srv, "state_unchanged")
    srv.on_connect(0)
    x0 = np.array(srv._flat.vec)
    delta = jax.tree.map(jnp.asarray, _tree(1))
    srv.on_update(ClientUpdate(0, 1, 2, delta))
    np.testing.assert_array_equal(np.asarray(srv._flat.vec), x0)
    assert srv.history[-1].eta != 0.0
    for leaf, want in zip(jax.tree.leaves(delta),
                          jax.tree.leaves(_tree(1))):
        np.testing.assert_array_equal(np.asarray(leaf), want)


def _kernel_spans(tmp_path, algorithm, compress):
    fed = dataclasses.replace(configs.SYNTHETIC_1_1.fed, backend="pallas",
                              num_clients=3, k_initial=2, k_max=2,
                              delta_compression=compress)
    sim = FederatedSimulation(configs.SYNTHETIC_1_1, fed, algorithm, seed=2)
    with jax.profiler.trace(str(tmp_path)):
        res = sim.run(max_time=1e9, max_updates=4, eval_every=100)
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    stats = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for ev in line.events if ev.name == "server.kernels"]
    return res, stats


@pytest.mark.parametrize("algorithm,compress,want", [
    ("asyncfeded", "off", 1), ("asyncfeded", "bf16", 0),
    ("asyncfeded-displacement", "off", 0)])
def test_kernels_span_says_whether_the_step_ran_in_place(
        tmp_path, algorithm, compress, want):
    res, stats = _kernel_spans(tmp_path, algorithm, compress)
    assert len(stats) == res.total_updates
    assert [s["in_place"] for s in stats] == [want] * res.total_updates

