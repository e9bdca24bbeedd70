"""The flat<->pytree staging programs (repro.utils.pytree): bitwise what the
eager per-leaf reference gives, cached by layout so a second server of the
same layout traces and compiles nothing, and the batched drain's stacks as
the eager ``jnp.stack`` of per-delta flattens."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.core.server import ClientUpdate, make_server
from repro.utils import pytree as pt

#: a program compiled for the backend, and a function traced to a jaxpr
STAGES = ("/jax/core/compile/backend_compile_duration",
          "/jax/core/compile/jaxpr_trace_duration")


def _tree(kind, seed=0):
    """A pytree of ``kind``: its leaves' dtypes and sizes."""
    rng = np.random.default_rng(seed)

    def leaf(shape, dtype=np.float32):
        return rng.standard_normal(shape).astype(np.float32).astype(dtype)

    if kind == "f32":
        return {"w": leaf((33, 7)), "b": [leaf((129,)), leaf((2, 3, 5))]}
    if kind == "bf16":
        return {"w": leaf((6, 5), jnp.bfloat16), "b": leaf((11,))}
    if kind == "scalar":
        return {"s": leaf(()), "v": leaf((4,))}
    raise ValueError(kind)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint16 if x.dtype.itemsize == 2 else np.uint32)


def _eager_flatten(tree, spec):
    vec = pt.tree_flatten_to_vector(tree)
    return jnp.pad(vec, (0, spec.n_padded - spec.n))


@pytest.mark.parametrize("where", ["host", "device"])
@pytest.mark.parametrize("kind,block", [("f32", 64), ("bf16", 1),
                                        ("bf16", 128), ("scalar", 7)])
def test_flatten_and_unflatten_are_bitwise_the_eager_reference(
        kind, block, where):
    tree = _tree(kind)
    if where == "device":
        tree = jax.tree.map(jnp.asarray, tree)
    assert pt.on_host(tree) == (where == "host")
    spec = pt.FlatSpec(tree, block=block)
    vec = spec.flatten(tree)
    assert vec.shape == (spec.n_padded,) and vec.dtype == jnp.float32
    np.testing.assert_array_equal(_bits(vec),
                                  _bits(_eager_flatten(tree, spec)))
    # an odd vector: every element, padding included, is its own value
    odd = jnp.asarray(np.random.default_rng(1).standard_normal(
        spec.n_padded).astype(np.float32))
    got, want = spec.unflatten(odd), pt.tree_unflatten_from_vector(odd, tree)
    assert jax.tree.structure(got) == jax.tree.structure(tree)
    for g, w, t in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(tree)):
        assert g.shape == t.shape and g.dtype == t.dtype == w.dtype
        np.testing.assert_array_equal(_bits(g), _bits(w))


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


def _drive(seed, device_batches):
    """A pallas server driven through one arrival of each kind and the
    burst sizes 2 and 3; the bursts carry device deltas when
    ``device_batches``, else NumPy ones, as the cohort engine returns, and
    then a mix of both."""
    params = _tree("f32", seed)
    srv = make_server("asyncfeded", params, FedConfig(), backend="pallas")
    for i in range(3):
        srv.on_connect(i)
    host = [_tree("f32", 10 * seed + i) for i in range(3)]
    dev = [jax.tree.map(jnp.asarray, d) for d in host]
    srv.on_update(ClientUpdate(0, 1, 2, host[0]))
    srv.on_update(ClientUpdate(1, 1, 2, dev[1]))
    bursts = [dev[:2], dev] if device_batches else [
        host[:2], host, [dev[0], host[1], dev[2]]]
    for deltas in bursts:
        replies = srv.on_update_batch(
            [ClientUpdate(i, srv.t, 2, d) for i, d in enumerate(deltas)])
        jax.block_until_ready([r.params for r in replies])
    return srv


def test_a_second_server_of_the_same_layout_compiles_nothing():
    # the first server warms with device bursts only, as the benchmark's
    # set-up does; the second drains NumPy and mixed bursts of the same
    # sizes, and neither traces nor compiles a program
    _drive(1, device_batches=True)
    with _counting_compiles() as seen:
        srv = _drive(2, device_batches=False)
        jax.block_until_ready(jax.tree.leaves(srv.params))
    assert seen == []


@pytest.mark.parametrize("where", ["host", "device", "mixed"])
@pytest.mark.parametrize("b", [2, 7])
def test_batched_drain_stacks_as_eager_jnp_stack(b, where):
    params = _tree("bf16")
    srv = make_server("asyncfeded", params, FedConfig(), backend="pallas")
    spec = srv._flat.spec
    for i in range(b):
        srv.on_connect(i)
        # spread the clients' snapshots over distinct model versions
        srv.on_update(ClientUpdate(i, srv.t, 2, _tree("bf16", 50 + i)))
    deltas = [_tree("bf16", 100 + i) for i in range(b)]
    if where != "host":
        deltas = [jax.tree.map(jnp.asarray, d)
                  if where == "device" or i % 2 else d
                  for i, d in enumerate(deltas)]
    upds = [ClientUpdate(i, 1 + i, 2, deltas[i]) for i in range(b)]
    want_s = jnp.stack([srv.gmis.get(u.snapshot_iter)[0] for u in upds])
    want_d = jnp.stack([_eager_flatten(d, spec) for d in deltas])
    seen = {}
    agg = srv._agg["flat_aggregate_batched"]

    def spy(x_t, stales, ds, **kw):
        seen.update(stales=stales, deltas=ds)
        return agg(x_t, stales, ds, **kw)
    srv._agg["flat_aggregate_batched"] = spy
    srv.on_update_batch(upds)
    assert seen["stales"].shape == seen["deltas"].shape == (b, spec.n_padded)
    np.testing.assert_array_equal(_bits(seen["stales"]), _bits(want_s))
    np.testing.assert_array_equal(_bits(seen["deltas"]), _bits(want_d))
