"""Flat-state server runtime: FlatSpec/FlatParams adapter, pytree vs pallas
backend parity, and the batched burst path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.core.compression import quantize_vec
from repro.core.server import AsyncFedEDServer, ClientUpdate, make_server
from repro.utils import pytree as pt


def mk_params(seed=0):
    return {"a": jax.random.normal(jax.random.PRNGKey(seed), (33, 7)),
            "b": [jax.random.normal(jax.random.PRNGKey(seed + 1), (129,)),
                  jax.random.normal(jax.random.PRNGKey(seed + 2), (2, 3, 5))]}


def mk_delta(seed, like, scale=0.05):
    leaves = jax.tree.leaves(like)
    ks = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    new = [scale * jax.random.normal(k, l.shape) for k, l in zip(ks, leaves)]
    return jax.tree.unflatten(jax.tree.structure(like), new)


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _fold_device_eta(srv):
    """Make ``srv`` fold the aggregation program's device eta into its
    displacement accumulators, as the reference for the host's f32."""
    seen = []
    if srv.backend == "pallas":
        agg = srv._agg["flat_aggregate"]

        def spy(*a, **kw):
            out = agg(*a, **kw)
            seen.append(out[1])
            return out
        srv._agg["flat_aggregate"] = spy
    else:
        agg = srv._aggregate_pytree

        def spy(upd):
            seen.append(agg(upd))
            return seen[-1]
        srv._aggregate_pytree = spy
    fold = srv.gmis.on_aggregate
    srv.gmis.on_aggregate = lambda eta, delta: fold(seen[-1][1], delta)


class TestFlatSpec:
    def test_roundtrip_with_padding(self):
        tree = {"w": jnp.arange(13, dtype=jnp.float32).reshape(13),
                "b": {"c": jnp.ones((3, 5), jnp.bfloat16)}}
        spec = pt.FlatSpec(tree, block=64)
        assert spec.n == 13 + 15
        assert spec.n_padded == 64
        vec = spec.flatten(tree)
        assert vec.shape == (64,) and vec.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(vec[spec.n:]), 0.0)
        back = spec.unflatten(vec)
        assert back["b"]["c"].dtype == jnp.bfloat16
        for l1, l2 in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(l1, np.float32),
                                          np.asarray(l2, np.float32))

    def test_flat_params_cache_invalidation(self):
        tree = {"w": jnp.ones((5,))}
        fp = pt.FlatParams.from_tree(tree, block=8)
        assert fp.tree is tree                       # seeded cache
        fp2 = fp.replace(fp.vec * 2.0)
        np.testing.assert_allclose(fp2.tree["w"], 2.0)
        assert fp.vec.shape == fp2.vec.shape


class TestBackendParity:
    @pytest.mark.parametrize("gmis_mode", ["ring", "displacement"])
    def test_scripted_run_parity(self, gmis_mode):
        fed = FedConfig(lam=1.0, eps=1.0, staleness_cap=4.0)
        s1 = make_server("asyncfeded", mk_params(), fed, gmis_mode=gmis_mode)
        s2 = make_server("asyncfeded", mk_params(), fed, gmis_mode=gmis_mode,
                         backend="pallas")
        for srv in (s1, s2):
            replies = [srv.on_connect(i) for i in range(3)]
            for step in range(6):
                cid = step % 3
                srv.on_update(ClientUpdate(
                    cid, replies[cid].iteration, 5,
                    mk_delta(step, srv.params)))
                replies[cid] = srv.on_connect(cid)
        for l1, l2 in zip(jax.tree.leaves(s1.params),
                          jax.tree.leaves(s2.params)):
            np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose([r.gamma for r in s1.history],
                                   [r.gamma for r in s2.history],
                                   rtol=1e-4, atol=1e-6)
        assert ([r.k_next for r in s1.history]
                == [r.k_next for r in s2.history])

    @pytest.mark.parametrize("backend,compression", [
        ("pytree", "off"), ("pallas", "off"), ("pallas", "int8")])
    def test_displacement_folds_the_aggregations_own_eta(self, backend,
                                                        compression):
        """The server reads (gamma, eta, dist, dnorm) to the host in one
        transfer and folds the host's f32 eta into every displacement
        accumulator: bitwise what folding the aggregation program's own
        device eta gives."""
        fed = FedConfig(lam=1.0, eps=1.0, staleness_cap=4.0,
                        delta_compression=compression)
        runs = []
        for device_eta in (False, True):
            srv = make_server("asyncfeded", mk_params(), fed,
                              gmis_mode="displacement", backend=backend)
            if device_eta:
                _fold_device_eta(srv)
            replies = [srv.on_connect(i) for i in range(3)]
            for step in range(6):
                cid = step % 3
                delta = mk_delta(step, mk_params())
                if compression != "off":
                    spec = srv._flat.spec
                    delta = quantize_vec(spec.flatten(delta), compression,
                                         spec.n)
                srv.on_update(ClientUpdate(cid, replies[cid].iteration, 5,
                                           delta))
                replies[cid] = srv.on_connect(cid)
            runs.append(srv)
        host, device = runs
        assert ([dataclasses.astuple(h) for h in host.history]
                == [dataclasses.astuple(h) for h in device.history])
        for l1, l2 in zip(jax.tree.leaves(host.params),
                          jax.tree.leaves(device.params)):
            np.testing.assert_array_equal(_bits(l1), _bits(l2))
        assert host.gmis._disp.keys() == device.gmis._disp.keys()
        for cid in host.gmis._disp:
            for l1, l2 in zip(jax.tree.leaves(host.gmis._disp[cid]),
                              jax.tree.leaves(device.gmis._disp[cid])):
                np.testing.assert_array_equal(_bits(l1), _bits(l2))

    def test_reply_params_structure_preserved(self):
        fed = FedConfig()
        srv = make_server("asyncfeded", mk_params(), fed, backend="pallas")
        rep = srv.on_connect(0)
        assert (jax.tree.structure(rep.params)
                == jax.tree.structure(mk_params()))
        rep2 = srv.on_update(ClientUpdate(0, rep.iteration, 5,
                                          mk_delta(0, mk_params())))
        assert rep2.params["a"].shape == (33, 7)

    def test_backend_validation(self):
        with pytest.raises(ValueError):
            AsyncFedEDServer(mk_params(), FedConfig(), backend="tpu")
        with pytest.raises(ValueError):
            AsyncFedEDServer(mk_params(), FedConfig(), per_leaf=True,
                             backend="pallas")


class TestBatchedUpdates:
    def _servers(self, fed, backend):
        srv = make_server("asyncfeded", mk_params(), fed, backend=backend)
        for i in range(4):
            srv.on_connect(i)
        return srv

    def test_batch_matches_sequential(self):
        fed = FedConfig(lam=1.0, eps=1.0)
        s_seq = self._servers(fed, "pallas")
        s_bat = self._servers(fed, "pallas")
        ups = [ClientUpdate(i, 1, 5, mk_delta(20 + i, mk_params()))
               for i in range(4)]
        for u in ups:
            s_seq.on_update(u)
        replies = s_bat.on_update_batch(ups)
        assert len(replies) == 4
        for l1, l2 in zip(jax.tree.leaves(s_seq.params),
                          jax.tree.leaves(s_bat.params)):
            np.testing.assert_allclose(l1, l2, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose([r.gamma for r in s_seq.history],
                                   [r.gamma for r in s_bat.history],
                                   rtol=1e-3, atol=1e-6)
        assert ([r.k_next for r in s_seq.history]
                == [r.k_next for r in s_bat.history])
        # every drained client resumes from the final model/iteration
        assert all(r.iteration == s_bat.t for r in replies)

    def test_batch_of_one_equals_on_update(self):
        fed = FedConfig(lam=1.0, eps=1.0)
        s1 = self._servers(fed, "pallas")
        s2 = self._servers(fed, "pallas")
        upd = ClientUpdate(0, 1, 5, mk_delta(31, mk_params()))
        r1 = s1.on_update(upd)
        (r2,) = s2.on_update_batch([upd])
        assert r1.iteration == r2.iteration and r1.k_next == r2.k_next
        for l1, l2 in zip(jax.tree.leaves(s1.params),
                          jax.tree.leaves(s2.params)):
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))

    def test_pytree_backend_batch_fallback(self):
        """The base-class fallback loops on_update and rewrites replies to
        the final model; params must match the pallas batched path."""
        fed = FedConfig(lam=1.0, eps=1.0)
        s_tree = self._servers(fed, "pytree")
        s_flat = self._servers(fed, "pallas")
        ups = [ClientUpdate(i, 1, 5, mk_delta(40 + i, mk_params()))
               for i in range(3)]
        r_tree = s_tree.on_update_batch(ups)
        r_flat = s_flat.on_update_batch(ups)
        assert [r.k_next for r in r_tree] == [r.k_next for r in r_flat]
        assert all(r.iteration == s_tree.t for r in r_tree)
        for l1, l2 in zip(jax.tree.leaves(s_tree.params),
                          jax.tree.leaves(s_flat.params)):
            np.testing.assert_allclose(l1, l2, rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("backend", ["pytree", "pallas"])
    def test_displacement_batch_reanchors_snapshots(self, backend):
        """Displacement-GMIS fallback: every drained client resumes from the
        final model, so its displacement accumulator must be re-zeroed there
        — otherwise its next gamma charges drift it never experienced."""
        fed = FedConfig(lam=1.0, eps=1.0)
        srv = make_server("asyncfeded", mk_params(), fed,
                          gmis_mode="displacement", backend=backend)
        for i in range(3):
            srv.on_connect(i)
        ups = [ClientUpdate(i, 1, 5, mk_delta(50 + i, mk_params()))
               for i in range(3)]
        srv.on_update_batch(ups)
        for i in range(3):
            assert float(srv.gmis.distance_from(i, srv.t, None)) == 0.0
        # a fresh update right after the batch must be treated as fresh
        rep = srv.on_update(ClientUpdate(0, srv.t, 5,
                                         mk_delta(60, mk_params())))
        assert srv.history[-1].gamma == 0.0

    def test_baseline_server_batch_fallback(self):
        """Non-AsyncFedED servers inherit the sequential fallback."""
        fed = FedConfig(fedasync_alpha=0.5)
        srv = make_server("fedasync+constant", {"w": jnp.zeros((16,))}, fed)
        ups = [ClientUpdate(i, 1, 5, {"w": jnp.full((16,), 0.1 * (i + 1))})
               for i in range(2)]
        replies = srv.on_update_batch(ups)
        assert len(replies) == 2 and srv.t == 3
