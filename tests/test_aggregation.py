"""Unit tests for the paper's core math: Eq.(5)-(8), GMIS, servers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.core import aggregation as agg
from repro.core.adaptive_k import AdaptiveK, update_k
from repro.core.gmis import DisplacementGMIS, RingGMIS
from repro.core.server import (AsyncFedEDServer, ClientUpdate, FedAsyncServer,
                               SyncServer, make_server)
from repro.utils import pytree as pt


def tree(vals):
    return {"a": jnp.asarray(vals, jnp.float32),
            "b": {"c": jnp.asarray([[1.0, 2.0], [3.0, 4.0]])}}


class TestStaleness:
    def test_hand_computed(self):
        # x_t - x_stale = [3, 4] -> dist 5; delta = [0, 2] -> norm 2; gamma 2.5
        x_t = {"w": jnp.array([3.0, 4.0])}
        x_stale = {"w": jnp.array([0.0, 0.0])}
        delta = {"w": jnp.array([0.0, 2.0])}
        gamma, dist, dnorm = agg.staleness(x_t, x_stale, delta)
        assert np.isclose(float(dist), 5.0)
        assert np.isclose(float(dnorm), 2.0)
        assert np.isclose(float(gamma), 2.5)

    def test_fresh_update_zero_gamma(self):
        x = tree([1.0, 2.0])
        delta = {"a": jnp.array([0.1, 0.1]), "b": {"c": jnp.ones((2, 2))}}
        gamma, _, _ = agg.staleness(x, x, delta)
        assert float(gamma) == 0.0

    def test_zero_delta_huge_gamma(self):
        x_t = tree([1.0, 2.0])
        x_s = tree([0.0, 0.0])
        zero = pt.tree_zeros_like(x_t)
        gamma, _, _ = agg.staleness(x_t, x_s, zero)
        assert float(gamma) > 1e10      # effectively discarded by Eq.(7)

    def test_cap(self):
        x_t = {"w": jnp.array([100.0])}
        x_s = {"w": jnp.array([0.0])}
        d = {"w": jnp.array([1.0])}
        gamma, _, _ = agg.staleness(x_t, x_s, d, cap=5.0)
        assert float(gamma) == 5.0


class TestAdaptiveLR:
    def test_eq7(self):
        assert np.isclose(float(agg.adaptive_lr(jnp.float32(3.0), 2.0, 1.0)),
                          0.5)

    def test_max_at_zero_gamma(self):
        # max eta = lam / eps
        assert np.isclose(float(agg.adaptive_lr(jnp.float32(0.0), 2.0, 4.0)),
                          0.5)


class TestAggregate:
    def test_eq5_applied(self):
        x_t = {"w": jnp.array([1.0, 1.0])}
        x_s = {"w": jnp.array([1.0, 1.0])}   # gamma 0 -> eta = lam/eps
        d = {"w": jnp.array([2.0, -2.0])}
        res = agg.asyncfeded_aggregate(x_t, x_s, d, lam=1.0, eps=2.0)
        np.testing.assert_allclose(res.params["w"], [2.0, 0.0])
        assert np.isclose(float(res.eta), 0.5)

    def test_dist_variant_matches(self):
        k = jax.random.PRNGKey(0)
        x_t = {"w": jax.random.normal(k, (64,))}
        x_s = {"w": x_t["w"] + 0.1}
        d = {"w": jax.random.normal(jax.random.PRNGKey(1), (64,)) * 0.2}
        r1 = agg.asyncfeded_aggregate(x_t, x_s, d, lam=1.0, eps=1.0)
        dist = pt.tree_dist(x_t, x_s)
        r2 = agg.asyncfeded_aggregate_with_dist(x_t, dist, d, lam=1.0, eps=1.0)
        np.testing.assert_allclose(r1.params["w"], r2.params["w"], rtol=1e-6)
        np.testing.assert_allclose(float(r1.gamma), float(r2.gamma), rtol=1e-6)

    def test_per_leaf_uniform_matches_global(self):
        # when every leaf has identical gamma, per-leaf == global
        x_t = {"w": jnp.ones((8,)), "v": jnp.ones((8,))}
        x_s = {"w": jnp.zeros((8,)), "v": jnp.zeros((8,))}
        d = {"w": jnp.ones((8,)) * 0.5, "v": jnp.ones((8,)) * 0.5}
        r_leaf = agg.asyncfeded_aggregate_per_leaf(x_t, x_s, d, lam=1.0, eps=1.0)
        r_glob = agg.asyncfeded_aggregate(x_t, x_s, d, lam=1.0, eps=1.0)
        np.testing.assert_allclose(r_leaf.params["w"], r_glob.params["w"],
                                   rtol=1e-6)


class TestAggregationEdgeCasesBothBackends:
    """Eq.(6/7) boundary semantics must agree between the pytree reference
    and the flat Pallas backend (kernels/fedagg/ops.py)."""

    LAM, EPS = 2.0, 0.5

    def _both(self, x_t, x_s, d, cap=0.0):
        from conftest import flat_aggregate_tree
        r_tree = agg.asyncfeded_aggregate(x_t, x_s, d, lam=self.LAM,
                                          eps=self.EPS, cap=cap)
        r_flat = flat_aggregate_tree(x_t, x_s, d, lam=self.LAM,
                                     eps=self.EPS, cap=cap)
        return r_tree, r_flat

    def _assert_agree(self, r_tree, r_flat):
        np.testing.assert_allclose(float(r_tree.gamma), float(r_flat.gamma),
                                   rtol=1e-5, atol=1e-8)
        np.testing.assert_allclose(float(r_tree.eta), float(r_flat.eta),
                                   rtol=1e-5)
        for l1, l2 in zip(jax.tree.leaves(r_tree.params),
                          jax.tree.leaves(r_flat.params)):
            np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-7)

    def test_zero_norm_delta_discarded(self):
        """||Delta|| = 0 with server drift -> gamma = dist/_TINY, so Eq.(7)
        effectively discards the update on both backends."""
        x_t = {"w": jnp.full((67,), 2.0)}
        x_s = {"w": jnp.zeros((67,))}
        zero = {"w": jnp.zeros((67,))}
        r_tree, r_flat = self._both(x_t, x_s, zero)
        for r in (r_tree, r_flat):
            assert float(r.gamma) > 1e10
            assert float(r.eta) < 1e-9
            np.testing.assert_allclose(r.params["w"], x_t["w"], rtol=1e-6)
        self._assert_agree(r_tree, r_flat)

    def test_server_has_not_moved(self):
        """dist <= _TINY -> gamma = 0 -> eta = lam/eps (fresh update), even
        when the delta is also zero (0/0 case)."""
        x = {"w": jnp.ones((33,))}
        d = {"w": jnp.full((33,), 0.25)}
        for delta in (d, {"w": jnp.zeros((33,))}):
            r_tree, r_flat = self._both(x, x, delta)
            for r in (r_tree, r_flat):
                assert float(r.gamma) == 0.0
                assert np.isclose(float(r.eta), self.LAM / self.EPS)
            self._assert_agree(r_tree, r_flat)

    def test_staleness_cap_clamps(self):
        x_t = {"w": jnp.full((17,), 100.0)}
        x_s = {"w": jnp.zeros((17,))}
        d = {"w": jnp.full((17,), 0.01)}
        r_tree, r_flat = self._both(x_t, x_s, d, cap=5.0)
        for r in (r_tree, r_flat):
            assert np.isclose(float(r.gamma), 5.0)
            assert np.isclose(float(r.eta), self.LAM / (5.0 + self.EPS))
        self._assert_agree(r_tree, r_flat)

    def test_generic_agreement(self):
        k = jax.random.PRNGKey(0)
        x_t = {"w": jax.random.normal(k, (513,)),
               "v": jax.random.normal(jax.random.PRNGKey(1), (7, 11))}
        x_s = jax.tree.map(lambda x: x + 0.05, x_t)
        d = jax.tree.map(lambda x: x * 0.02, x_t)
        self._assert_agree(*self._both(x_t, x_s, d, cap=3.0))


class TestAdaptiveK:
    def test_eq8_floor(self):
        # K + floor((gamma_bar - gamma) * kappa)
        assert update_k(10, 1.0, 3.0, 1.0) == 12
        assert update_k(10, 5.5, 3.0, 1.0) == 7   # floor(-2.5) = -3
        assert update_k(10, 3.0, 3.0, 1.0) == 10

    def test_clamping(self):
        assert update_k(2, 100.0, 3.0, 1.0, k_min=1) == 1
        assert update_k(10, 0.0, 100.0, 1.0, k_max=20) == 20

    def test_controller_converges_to_setpoint(self):
        """With staleness increasing in K (as Eq.(6) implies), the controller
        drives gamma -> gamma_bar."""
        ctl = AdaptiveK(k_initial=10, gamma_bar=3.0, kappa=0.5)
        k = ctl.get(0)
        for _ in range(60):
            gamma = 0.3 * k          # monotone proxy: staler with bigger K
            k = ctl.observe(0, gamma)
        assert abs(0.3 * k - 3.0) <= 0.5


class TestGMIS:
    def test_ring_eviction(self):
        g = RingGMIS(depth=3)
        for t in range(1, 6):
            g.append(t, {"w": jnp.array([float(t)])})
        assert g.num_stored == 3
        _, actual = g.get(1)          # evicted -> clamps to oldest
        assert actual == 3
        params, actual = g.get(4)
        assert actual == 4 and float(params["w"][0]) == 4.0

    def test_displacement_matches_ring(self):
        key = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (32,))}
        ring = RingGMIS(depth=16)
        disp = DisplacementGMIS()
        ring.append(1, params)
        disp.register_snapshot("c0", 1, params)
        cur = params
        for t in range(2, 7):
            delta = {"w": jax.random.normal(jax.random.PRNGKey(t), (32,)) * 0.1}
            eta = 0.5
            cur = pt.tree_axpy(eta, delta, cur)
            ring.append(t, cur)
            disp.on_aggregate(eta, delta)
        d_ring = float(pt.tree_dist(cur, ring.get(1)[0]))
        d_disp = float(disp.distance_from("c0", 1, cur))
        np.testing.assert_allclose(d_ring, d_disp, rtol=1e-5)


class TestServers:
    def _delta(self, seed, scale=0.1):
        return {"w": jax.random.normal(jax.random.PRNGKey(seed), (16,)) * scale}

    def test_asyncfeded_server_flow(self):
        params = {"w": jnp.zeros((16,))}
        fed = FedConfig(lam=1.0, eps=1.0, gamma_bar=3.0, kappa=1.0, k_initial=5)
        srv = AsyncFedEDServer(params, fed)
        r0 = srv.on_connect(0)
        assert r0.iteration == 1 and r0.k_next == 5
        rep = srv.on_update(ClientUpdate(0, r0.iteration, 5, self._delta(0)))
        assert rep.iteration == 2
        assert len(srv.history) == 1
        # fresh update: gamma == 0, eta == lam/eps
        assert srv.history[0].gamma == 0.0
        assert np.isclose(srv.history[0].eta, 1.0)

    def test_asyncfeded_ring_vs_displacement_equal(self):
        params = {"w": jnp.zeros((16,))}
        fed = FedConfig(lam=1.0, eps=1.0)
        s1 = make_server("asyncfeded", params, fed)
        s2 = make_server("asyncfeded-displacement", params, fed)
        for srv in (s1, s2):
            ra = srv.on_connect(0)
            rb = srv.on_connect(1)
            srv.on_update(ClientUpdate(0, ra.iteration, 5, self._delta(1)))
            srv.on_update(ClientUpdate(1, rb.iteration, 5, self._delta(2)))
        np.testing.assert_allclose(s1.params["w"], s2.params["w"], rtol=1e-5)
        assert np.isclose(s1.history[1].gamma, s2.history[1].gamma, rtol=1e-4)

    def test_fedasync_hinge_downweights_stale(self):
        params = {"w": jnp.zeros((4,))}
        fed = FedConfig(fedasync_alpha=0.5, hinge_a=5.0, hinge_b=2.0)
        srv = FedAsyncServer(params, fed, mode="hinge")
        assert np.isclose(srv._alpha(1), 0.5)
        assert srv._alpha(10) < 0.05

    def test_fedavg_weighted_mean(self):
        params = {"w": jnp.zeros((2,))}
        srv = SyncServer(params, FedConfig(), name="fedavg")
        ups = [ClientUpdate(0, 1, 5, {"w": jnp.array([1.0, 0.0])}, 100),
               ClientUpdate(1, 1, 5, {"w": jnp.array([0.0, 1.0])}, 300)]
        srv.round(ups)
        np.testing.assert_allclose(srv.params["w"], [0.25, 0.75])

    def test_fedbuff_aggregates_when_full(self):
        params = {"w": jnp.zeros((2,))}
        fed = FedConfig(fedbuff_size=2, lam=1.0)
        srv = make_server("fedbuff", params, fed)
        srv.on_update(ClientUpdate(0, 1, 5, {"w": jnp.array([2.0, 0.0])}))
        assert srv.t == 1
        srv.on_update(ClientUpdate(1, 1, 5, {"w": jnp.array([0.0, 2.0])}))
        assert srv.t == 2
        np.testing.assert_allclose(srv.params["w"], [1.0, 1.0])
