"""Compressed delta transport (DESIGN.md §13): quantization properties,
int8 sweep parity vs the dequant-then-f32 reference, end-to-end
server equivalence across backends, error-feedback residual lifecycle,
and the budget-law cohort-width gain."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import shapes
from repro.configs.base import FedConfig
from repro.core import budget as budget_mod
from repro.core import compression
from repro.core.client import Client
from repro.core.server import ClientUpdate, make_server
from repro.kernels.fedagg import fedagg, ops
from repro.kernels.fedagg import ref as fedagg_ref

BLOCK = fedagg.BLOCK_ROWS * fedagg.LANES


def _vec(n, seed=0, scale=0.05):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), (n,))


# ------------------------------------------------------ quantization core --
class TestQuantize:
    def test_roundtrip_error_bounded_per_block(self):
        n = BLOCK * 2
        v = _vec(n)
        cd = compression.quantize_vec(v, "int8", n)
        err = np.asarray(compression.dequantize(cd) - v)
        # per-element error <= half a quantization step of its own block
        scales = np.repeat(np.asarray(cd.scales), fedagg.QBLOCK)
        assert np.all(np.abs(err) <= 0.5 * scales + 1e-9)

    def test_zero_block_exact(self):
        n = BLOCK
        v = jnp.zeros((n,))
        cd = compression.quantize_vec(v, "int8", n)
        assert float(jnp.max(jnp.abs(compression.dequantize(cd)))) == 0.0
        assert float(jnp.max(jnp.abs(cd.scales))) == 0.0

    def test_bf16_is_cast(self):
        n = BLOCK
        v = _vec(n)
        cd = compression.quantize_vec(v, "bf16", n)
        assert cd.q.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(compression.dequantize(cd)),
            np.asarray(v.astype(jnp.bfloat16).astype(jnp.float32)))

    def test_scale_delta_int8_exact(self):
        # clip verdicts scale compressed deltas on the SCALES, which is
        # exact: dequant(q, s * scales) == s * dequant(q, scales)
        n = BLOCK
        cd = compression.quantize_vec(_vec(n), "int8", n)
        scaled = compression.scale_delta(cd, 0.37)
        np.testing.assert_allclose(
            np.asarray(compression.dequantize(scaled)),
            0.37 * np.asarray(compression.dequantize(cd)), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(scaled.q),
                                      np.asarray(cd.q))

    def test_delta_norm_is_dequantized_norm(self):
        n = BLOCK
        cd = compression.quantize_vec(_vec(n), "int8", n)
        want = float(jnp.linalg.norm(compression.dequantize(cd)))
        assert compression.delta_norm(cd) == pytest.approx(want, rel=1e-6)

    def test_wire_bytes(self):
        n = BLOCK * 2
        cd8 = compression.quantize_vec(_vec(n), "int8", n)
        cd16 = compression.quantize_vec(_vec(n), "bf16", n)
        assert cd8.wire_bytes() == n + 4 * (n // fedagg.QBLOCK)
        assert cd16.wire_bytes() == 2 * n

    def test_not_a_pytree(self):
        # generic tree ops must fail loudly on a compressed delta rather
        # than silently walking into the payload
        cd = compression.quantize_vec(_vec(BLOCK), "int8", BLOCK)
        leaves = jax.tree.leaves(cd)
        assert leaves == [cd]

    def test_shapes_mirror_pinned(self):
        # configs.shapes stays import-free of the kernel layer by
        # mirroring the scale-block size; keep the two constants locked
        assert shapes.DELTA_SCALE_BLOCK == fedagg.QBLOCK


# ----------------------------------------------------- quant-fused kernels --
class TestQuantKernels:
    """The sweeps with int8 ``scales``: parity vs dequantize-then-f32
    through the ref.py oracles."""

    @pytest.mark.parametrize("nblocks", [1, 2, 5])
    def test_norms_q(self, nblocks):
        n = BLOCK * nblocks
        xt, xs = _vec(n, 0, 1.0), _vec(n, 1, 1.0)
        cd = compression.quantize_vec(_vec(n, 2), "int8", n)
        d = compression.dequantize(cd)
        got = fedagg.fedagg_norms(xt, xs, cd.q, cd.scales)
        want = fedagg_ref.norms_ref(xt, xs, d)
        np.testing.assert_allclose(got, want, rtol=1e-5)

    @pytest.mark.parametrize("nblocks", [1, 2, 5])
    def test_axpy_q(self, nblocks):
        n = BLOCK * nblocks
        xt = _vec(n, 0, 1.0)
        cd = compression.quantize_vec(_vec(n, 1), "int8", n)
        got = fedagg.fedagg_axpy(xt, cd.q, jnp.float32(0.37), cd.scales)
        want = fedagg_ref.axpy_ref(xt, compression.dequantize(cd),
                                   jnp.float32(0.37))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)

    @pytest.mark.parametrize("b", [2, 3])
    @pytest.mark.parametrize("nblocks", [1, 2])
    def test_norms_batched_q(self, b, nblocks):
        n = BLOCK * nblocks
        xt = _vec(n, 0, 1.0)
        xs = jnp.stack([_vec(n, 10 + i, 1.0) for i in range(b)])
        cds = [compression.quantize_vec(_vec(n, 20 + i), "int8", n)
               for i in range(b)]
        ds = jnp.stack([compression.dequantize(c) for c in cds])
        got = fedagg.fedagg_norms_batched(
            xt, xs, jnp.stack([c.q for c in cds]),
            jnp.stack([c.scales for c in cds]))
        want = fedagg_ref.norms_batched_ref(xt, xs, ds)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("b", [2, 3])
    def test_apply_batched_q(self, b):
        n = BLOCK
        xt = _vec(n, 0, 1.0)
        cds = [compression.quantize_vec(_vec(n, 30 + i), "int8", n)
               for i in range(b)]
        ds = jnp.stack([compression.dequantize(c) for c in cds])
        etas = jnp.arange(1, b + 1, dtype=jnp.float32) / 10
        got = fedagg.fedagg_apply_batched(
            xt, jnp.stack([c.q for c in cds]), etas,
            jnp.stack([c.scales for c in cds]))
        want = fedagg_ref.apply_batched_ref(xt, ds, etas)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_flat_aggregate_q_matches_dequant_reference(self):
        n = BLOCK * 2
        xt, xs = _vec(n, 0, 1.0), _vec(n, 1, 1.0)
        cd = compression.quantize_vec(_vec(n, 2), "int8", n)
        d = compression.dequantize(cd)
        got = ops.flat_aggregate(xt, xs, cd.q, scales=cd.scales, lam=1.0,
                                 eps=1.0)
        want = ops.flat_aggregate(xt, xs, d, lam=1.0, eps=1.0)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-7)

    def test_bf16_payload_rides_f32_kernels_exactly(self):
        # bf16 needs no scales: the sweeps upcast tiles on load, so
        # feeding the bf16 payload is exact f32 accumulation
        n = BLOCK
        xt, xs = _vec(n, 0, 1.0), _vec(n, 1, 1.0)
        cd = compression.quantize_vec(_vec(n, 2), "bf16", n)
        got = fedagg.fedagg_norms(xt, xs, cd.q)
        want = fedagg_ref.norms_ref(xt, xs, compression.dequantize(cd))
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_batched_b_max_knees(self):
        # compressed tiles cost fewer VMEM bytes, so the free-batch knee
        # moves out with the payload width
        assert fedagg.batched_b_max(4) == 15      # f32 (historical value)
        assert fedagg.batched_b_max(2) == 20      # bf16
        assert fedagg.batched_b_max(1) == 24      # int8


# --------------------------------------------------------- error feedback --
class TestErrorFeedback:
    def _client(self, mode, n=256):
        fed = FedConfig(delta_compression=mode, num_clients=2)
        c = Client.__new__(Client)       # skip dataset plumbing
        c.client_id = 0
        c.fed = fed
        c._residual = None
        c._flatspec = None
        return c

    def test_residual_cancels_bias(self):
        # emitting the SAME delta T times: with error feedback the sum of
        # dequantized emissions tracks T * delta to one quantization step,
        # instead of T * (one-shot bias)
        c = self._client("int8")
        delta = {"w": 0.01 * jax.random.normal(jax.random.PRNGKey(0), (300,))}
        T = 8
        acc = None
        for t in range(T):
            upd = c.compress_update(ClientUpdate(0, 1, 1, delta))
            d = compression.dequantize(upd.delta)
            acc = d if acc is None else acc + d
        true = T * np.asarray(
            jnp.pad(delta["w"], (0, compression.BLOCK - 300)))
        onestep = np.repeat(
            np.asarray(compression.quantize_vec(
                jnp.pad(delta["w"], (0, compression.BLOCK - 300)),
                "int8", 300).scales), fedagg.QBLOCK)
        assert np.all(np.abs(np.asarray(acc) - true) <= onestep + 1e-9)

    def test_release_residual(self):
        c = self._client("int8")
        delta = {"w": jnp.ones((300,)) * 0.003}
        c.compress_update(ClientUpdate(0, 1, 1, delta))
        assert c._residual is not None
        c.release_residual()
        assert c._residual is None

    def test_off_mode_is_noop(self):
        c = self._client("off")
        delta = {"w": jnp.ones((8,))}
        upd = ClientUpdate(0, 1, 1, delta)
        assert c.compress_update(upd) is upd

    def test_no_double_compression(self):
        c = self._client("int8")
        delta = {"w": jnp.ones((300,)) * 0.003}
        upd = c.compress_update(ClientUpdate(0, 1, 1, delta))
        assert compression.is_compressed(upd.delta)
        assert c.compress_update(upd) is upd


# ------------------------------------------------- server-level equivalence --
def _params(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (63, 5)),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (17,))}


def _deltas(params, count, scale=0.01):
    out = []
    for i in range(count):
        k = jax.random.PRNGKey(100 + i)
        out.append(jax.tree.map(
            lambda l: scale * jax.random.normal(
                jax.random.fold_in(k, hash(l.shape) % 97), l.shape), params))
    return out


class TestServerEquivalence:
    @pytest.mark.parametrize("mode", ["int8", "bf16"])
    def test_pallas_matches_pytree_compressed(self, mode):
        """The quant-fused flat path and the dequantize-then-leafwise
        reference must agree on every scalar and the final model."""
        params = _params()
        fed = FedConfig(num_clients=4, delta_compression=mode)
        spec_block = compression.BLOCK
        import repro.utils.pytree as pt
        spec = pt.FlatSpec(params, block=spec_block)
        servers = {b: make_server("asyncfeded", params, fed, backend=b)
                   for b in ("pytree", "pallas")}
        for i, d in enumerate(_deltas(params, 4)):
            cd = compression.quantize_vec(spec.flatten(d), mode, spec.n)
            recs = {}
            for b, srv in servers.items():
                srv.on_connect(i % 2)
                srv.on_update(ClientUpdate(i % 2, srv.t, 1, cd))
                recs[b] = srv.history[-1]
            assert recs["pytree"].gamma == pytest.approx(
                recs["pallas"].gamma, rel=1e-4, abs=1e-6)
            assert recs["pytree"].eta == pytest.approx(
                recs["pallas"].eta, rel=1e-4)
        for l1, l2 in zip(jax.tree.leaves(servers["pytree"].params),
                          jax.tree.leaves(servers["pallas"].params)):
            np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                       rtol=1e-4, atol=1e-6)

    def test_batched_drain_matches_sequential_int8(self):
        """An int8 burst through on_update_batch == one-at-a-time."""
        params = _params()
        fed = FedConfig(num_clients=6, delta_compression="int8")
        import repro.utils.pytree as pt
        spec = pt.FlatSpec(params, block=compression.BLOCK)
        srv_seq = make_server("asyncfeded", params, fed, backend="pallas")
        srv_bat = make_server("asyncfeded", params, fed, backend="pallas")
        upds = []
        for i, d in enumerate(_deltas(params, 3)):
            cd = compression.quantize_vec(spec.flatten(d), "int8", spec.n)
            for srv in (srv_seq, srv_bat):
                srv.on_connect(i)
            upds.append(ClientUpdate(i, 1, 1, cd))
        for u in upds:
            srv_seq.on_update(u)
        srv_bat.on_update_batch(list(upds))
        for h1, h2 in zip(srv_seq.history, srv_bat.history):
            assert h1.gamma == pytest.approx(h2.gamma, rel=1e-4, abs=1e-6)
            assert h1.eta == pytest.approx(h2.eta, rel=1e-4)
        np.testing.assert_allclose(
            np.asarray(srv_seq._flat.vec), np.asarray(srv_bat._flat.vec),
            rtol=1e-4, atol=1e-6)

    def test_mixed_mode_burst_falls_back(self):
        """A burst mixing compressed and raw deltas must still drain
        (sequential fallback), not crash the batched stacker."""
        params = _params()
        fed = FedConfig(num_clients=4, delta_compression="int8")
        import repro.utils.pytree as pt
        spec = pt.FlatSpec(params, block=compression.BLOCK)
        srv = make_server("asyncfeded", params, fed, backend="pallas")
        ds = _deltas(params, 2)
        cd = compression.quantize_vec(spec.flatten(ds[0]), "int8", spec.n)
        for i in range(2):
            srv.on_connect(i)
        replies = srv.on_update_batch([ClientUpdate(0, 1, 1, cd),
                                       ClientUpdate(1, 1, 1, ds[1])])
        assert len(replies) == 2 and srv.t == 3

    def test_fedbuff_buffers_compressed(self):
        params = _params()
        fed = FedConfig(num_clients=4, delta_compression="int8",
                        fedbuff_size=2)
        import repro.utils.pytree as pt
        spec = pt.FlatSpec(params, block=compression.BLOCK)
        srv = make_server("fedbuff", params, fed)
        ds = _deltas(params, 2)
        cds = [compression.quantize_vec(spec.flatten(d), "int8", spec.n)
               for d in ds]
        srv.on_update(ClientUpdate(0, 1, 1, cds[0]))
        assert compression.is_compressed(srv.buffer[0][0])
        srv.on_update(ClientUpdate(1, 1, 1, cds[1]))
        assert not srv.buffer                      # flushed at size 2
        want = params
        for cd in cds:
            d = spec.unflatten(compression.dequantize(cd))
            want = jax.tree.map(lambda a, b: a + (fed.lam / 2) * b, want, d)
        for l1, l2 in zip(jax.tree.leaves(srv.params),
                          jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                       rtol=1e-5, atol=1e-7)

    def test_batch_limit_scales_with_mode(self):
        params = _params()
        for mode, want in (("off", 15), ("bf16", 20), ("int8", 24)):
            fed = FedConfig(num_clients=4, delta_compression=mode)
            srv = make_server("asyncfeded", params, fed, backend="pallas")
            assert srv.batch_limit() == want


# ------------------------------------------------------------- budget law --
class TestBudgetLaw:
    def test_delta_wire_bytes(self):
        P = 4 * (1 << 20)                      # 1M f32 elements
        assert shapes.delta_wire_bytes(P, "off") == P
        assert shapes.delta_wire_bytes(P, "bf16") == P // 2
        elems = P // 4
        assert shapes.delta_wire_bytes(P, "int8") == (
            elems + 4 * (elems // shapes.DELTA_SCALE_BLOCK))

    def test_footprint_default_unchanged(self):
        # the historical C * (4P + KB + A) law must stay byte-identical
        # for every pre-compression call site (delta_bytes omitted)
        got = shapes.cohort_footprint_bytes(1000, 64, 512, 8, 10)
        assert got == 8 * (4 * 1000 + 10 * 64 + 512)

    def test_footprint_with_wire_delta(self):
        got = shapes.cohort_footprint_bytes(1000, 64, 512, 8, 10,
                                            delta_bytes=250)
        assert got == 8 * (3 * 1000 + 250 + 10 * 64 + 512)

    def test_plan_cohort_width_gain_under_budget(self):
        """The acceptance row: at a budget sitting in the crossing
        interval, int8 transport doubles the planned cohort width."""
        from repro.core import tasks

        class _FakeTask:
            def batch_bytes(self, fed):
                return 0

            def activation_bytes(self, fed):
                return 0

        fake = _FakeTask()
        orig = tasks.as_task
        tasks.as_task = lambda t: t if t is fake else orig(t)
        try:
            P = 4 * (1 << 20)                  # 4 MiB of params
            budget = 224 * (1 << 20)           # between 16*3.25P and 16*4P
            for mode, want_width in (("off", 8), ("int8", 16)):
                fed = FedConfig(num_clients=16, client_engine="cohort",
                                delta_compression=mode)
                plan = budget_mod.plan_cohort(
                    fake, fed, clients=16, k=1, param_bytes=P,
                    budget_bytes=budget)
                assert plan.width == want_width, (mode, plan)
        finally:
            tasks.as_task = orig

    def test_config_validation(self):
        with pytest.raises(ValueError, match="delta_compression"):
            FedConfig(delta_compression="fp4")
        for mode in ("off", "int8", "bf16"):
            assert FedConfig(delta_compression=mode).delta_compression == mode
