"""Per-kernel correctness: sweep shapes/dtypes, assert_allclose vs ref.py
oracles (kernels run in interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import flat_aggregate_tree
from repro.core import compression
from repro.core.aggregation import gamma_eta_from_sq
from repro.kernels.fedagg import fedagg
from repro.kernels.fedagg import ref as fedagg_ref
from repro.kernels.fedagg.ops import flat_aggregate_batched, pad_flat_vector
from repro.kernels.rglru.ops import rglru_pallas
from repro.kernels.rglru.ref import rglru_scan_ref
from repro.kernels.rglru.rglru import rglru_scan
from repro.kernels.ssd.ref import ssd_scan_ref
from repro.kernels.ssd.ssd import ssd_scan
from repro.kernels.swa_attn.ops import decode_attention_pallas
from repro.kernels.swa_attn.ref import swa_decode_ref

BLOCK = fedagg.BLOCK_ROWS * fedagg.LANES


class TestFedAgg:
    @pytest.mark.parametrize("nblocks", [1, 2, 5])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_norms(self, nblocks, dtype):
        n = BLOCK * nblocks
        xt = jax.random.normal(jax.random.PRNGKey(0), (n,), dtype)
        xs = (xt + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (n,),
                                           dtype)).astype(dtype)
        d = jax.random.normal(jax.random.PRNGKey(2), (n,), dtype) * 0.05
        got = fedagg.fedagg_norms(xt, xs, d)
        want = fedagg_ref.norms_ref(xt, xs, d)
        np.testing.assert_allclose(got, want, rtol=2e-3 if dtype == jnp.bfloat16
                                   else 1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_axpy(self, dtype):
        n = BLOCK * 2
        xt = jax.random.normal(jax.random.PRNGKey(0), (n,), dtype)
        d = jax.random.normal(jax.random.PRNGKey(1), (n,), dtype)
        eta = jnp.float32(0.37)
        got = fedagg.fedagg_axpy(xt, d, eta)
        want = fedagg_ref.axpy_ref(xt, d, eta)
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32),
            rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-6)

    def test_fused_matches_two_phase(self):
        n = BLOCK
        xt = jax.random.normal(jax.random.PRNGKey(0), (n,))
        xs = xt + 0.05
        d = jax.random.normal(jax.random.PRNGKey(1), (n,)) * 0.1
        eta = jnp.float32(0.5)
        out, partial = fedagg.fedagg_fused(xt, xs, d, eta)
        np.testing.assert_allclose(out, fedagg_ref.axpy_ref(xt, d, eta),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(partial, fedagg_ref.norms_ref(xt, xs, d),
                                   rtol=1e-5)

    def test_pytree_wrapper_matches_core(self):
        from repro.core.aggregation import asyncfeded_aggregate
        k = jax.random.PRNGKey(3)
        tree = {"a": jax.random.normal(k, (33, 7)),
                "b": [jax.random.normal(k, (129,)),
                      jax.random.normal(k, (2, 3, 5))]}
        stale = jax.tree.map(lambda x: x + 0.02, tree)
        delta = jax.tree.map(lambda x: x * 0.01, tree)
        r1 = flat_aggregate_tree(tree, stale, delta, lam=2.0, eps=1.0)
        r2 = asyncfeded_aggregate(tree, stale, delta, lam=2.0, eps=1.0)
        np.testing.assert_allclose(float(r1.gamma), float(r2.gamma), rtol=1e-4)
        for l1, l2 in zip(jax.tree.leaves(r1.params),
                          jax.tree.leaves(r2.params)):
            np.testing.assert_allclose(l1, l2, rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("n", [1, 4097, BLOCK - 1, BLOCK + 129])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_padding_path_odd_sizes(self, n, dtype):
        """Sizes that are NOT BLOCK multiples go through the zero-padding
        path of a BLOCK-padded FlatSpec; padding must be
        value-transparent."""
        from repro.core.aggregation import asyncfeded_aggregate
        tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (n,), dtype)}
        stale = {"w": (tree["w"] + jnp.asarray(0.03, dtype)).astype(dtype)}
        delta = {"w": (jax.random.normal(jax.random.PRNGKey(1), (n,), dtype)
                       * 0.02).astype(dtype)}
        vec = pad_flat_vector(jnp.ravel(tree["w"]).astype(jnp.float32))
        assert vec.shape[0] % BLOCK == 0
        r1 = flat_aggregate_tree(tree, stale, delta, lam=1.5, eps=0.5)
        r2 = asyncfeded_aggregate(tree, stale, delta, lam=1.5, eps=0.5)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(float(r1.gamma), float(r2.gamma),
                                   rtol=tol, atol=1e-6)
        np.testing.assert_allclose(
            r1.params["w"].astype(jnp.float32),
            r2.params["w"].astype(jnp.float32), rtol=tol, atol=1e-5)


def _payload(d, payload):
    """A flat f32 delta as the kernels take it: f32, a bf16 wire payload,
    or int8 with its block scales."""
    if payload == "f32":
        return d, None
    cd = compression.quantize_vec(d, payload, int(d.shape[0]))
    return cd.q, cd.scales


@jax.jit
def _norms_then_scalars(x, x_stale, delta, scales):
    """Eq. 6-7's scalars from the norms sweep's own squared sums, in a
    program of their own."""
    sq = fedagg.fedagg_norms(x, x_stale, delta, scales)
    return gamma_eta_from_sq(sq[0], sq[1], 1.5, 0.5, 4.0)


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("payload,in_place", [
    ("f32", True), ("f32", False), ("bf16", False), ("int8", False)])
@pytest.mark.parametrize("mode", ["ring", "displacement"])
def test_flat_aggregate_scalars_are_the_norms_sweeps(payload, in_place,
                                                     mode):
    """``flat_aggregate`` hands back (gamma, eta, dist, dnorm) as one f32
    (4,) array, bitwise the four scalars of the norms sweep's sums."""
    from repro.kernels.fedagg import ops
    n = 2 * BLOCK
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    xt, other, d = (jax.random.normal(k, (n,), jnp.float32) for k in keys)
    delta, scales = _payload(0.05 * d, payload)
    # ring: the stale model; displacement: the drift against zeros
    stale, drift = ((xt + 0.1 * other, None) if mode == "ring"
                    else (jnp.zeros_like(xt), 0.1 * other))
    want = _norms_then_scalars(xt if drift is None else drift, stale,
                               delta, scales)
    mine = delta + 0 if in_place else delta
    _, scalars = ops.flat_aggregate(xt, stale, mine, scales=scales,
                                    drift=drift, lam=1.5, eps=0.5, cap=4.0,
                                    in_place=in_place)
    assert scalars.shape == (4,) and scalars.dtype == jnp.float32
    np.testing.assert_array_equal(_bits(scalars),
                                  _bits(jnp.stack(want)))


class TestFedAggBatched:
    def _inputs(self, b, nblocks, dtype=jnp.float32, seed=0):
        n = BLOCK * nblocks
        xt = jax.random.normal(jax.random.PRNGKey(seed), (n,), dtype)
        xs = (xt[None] + 0.05 * jax.random.normal(
            jax.random.PRNGKey(seed + 1), (b, n), dtype)).astype(dtype)
        d = (jax.random.normal(jax.random.PRNGKey(seed + 2), (b, n), dtype)
             * 0.1).astype(dtype)
        return xt, xs, d

    @pytest.mark.parametrize("b", [1, 3, 8])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_norms_batched(self, b, dtype):
        xt, xs, d = self._inputs(b, 2, dtype)
        got = fedagg.fedagg_norms_batched(xt, xs, d)
        want = fedagg_ref.norms_batched_ref(xt, xs, d)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        for g_, w_ in zip(got, want):
            np.testing.assert_allclose(g_, w_, rtol=tol, atol=tol)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_apply_batched(self, dtype):
        xt, _, d = self._inputs(4, 1, dtype)
        etas = jnp.array([0.3, 0.5, 0.0, 1.2], jnp.float32)
        got = fedagg.fedagg_apply_batched(xt, d, etas)
        want = fedagg_ref.apply_batched_ref(xt, d, etas)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(got.astype(jnp.float32),
                                   want.astype(jnp.float32),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("b", [2, 15])
    @pytest.mark.parametrize("payload", ["f32", "int8"])
    def test_packed_gram_splits_into_the_kernels_outputs(self, b, payload):
        """The drain's one read of the Gram sweep splits back into exactly
        the four arrays the kernel emits."""
        from repro.kernels.fedagg import ops
        xt, xs, d = self._inputs(b, 1, seed=b)
        rows = [_payload(r, payload) for r in d]
        deltas = jnp.stack([q for q, _ in rows])
        scales = (None if payload == "f32"
                  else jnp.stack([s for _, s in rows]))
        want = jax.jit(fedagg.fedagg_norms_batched)(xt, xs, deltas, scales)
        packed = ops._norms_batched(
            xt, xs, deltas, *(() if scales is None else (scales,)))
        assert packed.shape == (2 * b + 2 * b * b,)
        got = ops.unpack_gram(np.asarray(packed), b)
        for g_, w_ in zip(got, want, strict=True):
            assert g_.shape == w_.shape
            np.testing.assert_array_equal(_bits(g_), _bits(w_))

    @pytest.mark.parametrize("b", [2, 5])
    def test_sequential_equivalence(self, b):
        """Batched path == B one-at-a-time aggregations against the moving
        server state (the whole point of the Gram-matrix schedule)."""
        xt, xs, d = self._inputs(b, 2, seed=7)
        new, etas, gammas, dists, _, _ = flat_aggregate_batched(
            xt, xs, d, lam=2.0, eps=1.0)
        rnew, retas, rgammas, rdists = fedagg_ref.aggregate_batched_seq_ref(
            xt, xs, d, 2.0, 1.0)
        np.testing.assert_allclose(etas, retas, rtol=1e-4)
        np.testing.assert_allclose(gammas, rgammas, rtol=1e-4)
        np.testing.assert_allclose(dists, rdists, rtol=1e-4)
        np.testing.assert_allclose(new, rnew, rtol=1e-4, atol=1e-5)

    def test_sequential_equivalence_with_cap(self):
        xt, xs, d = self._inputs(3, 1, seed=11)
        d = d * 0.001                       # large gammas -> cap active
        new, etas, gammas, _, _, _ = flat_aggregate_batched(
            xt, xs, d, lam=1.0, eps=1.0, cap=2.0)
        rnew, retas, rgammas, _ = fedagg_ref.aggregate_batched_seq_ref(
            xt, xs, d, 1.0, 1.0, cap=2.0)
        assert np.all(np.asarray(gammas) <= 2.0 + 1e-6)
        np.testing.assert_allclose(gammas, rgammas, rtol=1e-4)
        np.testing.assert_allclose(new, rnew, rtol=1e-4, atol=1e-5)

    def test_zero_delta_in_batch(self):
        """A zero-norm delta inside a burst is discarded (eta ~ 0) without
        perturbing its neighbours' schedule."""
        xt, xs, d = self._inputs(3, 1, seed=13)
        d = d.at[1].set(0.0)
        new, etas, *_ = flat_aggregate_batched(xt, xs, d, lam=1.0, eps=1.0)
        rnew, retas, *_ = fedagg_ref.aggregate_batched_seq_ref(
            xt, xs, d, 1.0, 1.0)
        assert float(etas[1]) < 1e-6
        np.testing.assert_allclose(etas, retas, rtol=1e-4, atol=1e-9)
        np.testing.assert_allclose(new, rnew, rtol=1e-4, atol=1e-5)

    def test_batched_pytree_wrapper_odd_sizes(self):
        """Non-BLOCK-multiple pytrees through a BLOCK-padded FlatSpec and
        the batched entry point vs B sequential core aggregations."""
        from repro.utils import pytree as pt
        from repro.core.aggregation import asyncfeded_aggregate
        k = jax.random.PRNGKey(5)
        tree = {"a": jax.random.normal(k, (41, 13)),
                "b": jax.random.normal(jax.random.PRNGKey(6), (257,))}
        stales, deltas = [], []
        for i in range(3):
            stales.append(jax.tree.map(
                lambda x: x + 0.01 * (i + 1), tree))
            deltas.append(jax.tree.map(
                lambda x: x * 0.005 * (i + 1), tree))
        spec = pt.FlatSpec(tree, block=BLOCK)
        new, etas, gammas, _, _, _ = flat_aggregate_batched(
            spec.flatten(tree), jnp.stack([spec.flatten(t) for t in stales]),
            jnp.stack([spec.flatten(t) for t in deltas]), lam=1.0, eps=1.0)
        new = spec.unflatten(new)
        cur = tree
        for i in range(3):
            res = asyncfeded_aggregate(cur, stales[i], deltas[i],
                                       lam=1.0, eps=1.0)
            cur = res.params
            np.testing.assert_allclose(float(etas[i]), float(res.eta),
                                       rtol=1e-4)
        for l1, l2 in zip(jax.tree.leaves(new), jax.tree.leaves(cur)):
            np.testing.assert_allclose(l1, l2, rtol=1e-4, atol=1e-6)


class TestFedAggSharded:
    """Model-sharded entry points (kernels/fedagg/sharded.py) vs the
    replicated ops, at shard counts that do NOT divide the true size (the
    remainder lives in zero padding) and non-pow2 padded block counts,
    including int8 payloads with their block ``scales``, displacement
    ``drift``, and bf16 payloads (f32 tiles upcast on load). shards=1 is a
    valid 1-device mesh and runs everywhere; shards>1 takes the
    ``multidevice`` fixture (tier1-multidevice CI, or the re-exec in
    test_flat_sharded.py)."""

    def _padded(self, n_true, shards, seed=0):
        """(x_t, x_stale, delta) padded to BLOCK*shards — the server's
        layout for a true size the shard count does not divide."""
        from repro.kernels.fedagg import ops
        k = jax.random.PRNGKey(seed)
        block = BLOCK * shards
        n_pad = -(-n_true // block) * block
        xt = np.zeros(n_pad, np.float32)
        xt[:n_true] = np.asarray(
            jax.random.normal(k, (n_true,), jnp.float32))
        xs, d = xt.copy(), np.zeros(n_pad, np.float32)
        xs[:n_true] += 0.03
        d[:n_true] = np.asarray(jax.random.normal(
            jax.random.PRNGKey(seed + 1), (n_true,), jnp.float32)) * 0.02
        assert n_pad % BLOCK == 0 and ops is not None
        return jnp.asarray(xt), jnp.asarray(xs), jnp.asarray(d)

    def _assert_single(self, got, want):
        (gv, gs), (wv, ws) = got, want
        np.testing.assert_allclose(np.asarray(jax.device_get(gv)),
                                   np.asarray(jax.device_get(wv)),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gs), np.asarray(ws),
                                   rtol=1e-4, atol=1e-6)

    def test_shards1_mesh_is_identity_layout(self):
        """A 1-shard mesh is valid on any device count and must match the
        replicated entry point — the cheap always-on guard."""
        from repro.kernels.fedagg import ops, sharded
        xt, xs, d = self._padded(BLOCK + 129, 1)
        got = sharded.flat_aggregate(xt, xs, d, lam=2.0, eps=1.0, shards=1)
        want = ops.flat_aggregate(xt, xs, d, lam=2.0, eps=1.0)
        self._assert_single(got, want)

    @pytest.mark.parametrize("shards", [2, 8])
    @pytest.mark.parametrize("n_true", [BLOCK + 517, 3 * BLOCK - 1])
    def test_flat_aggregate_nondividing(self, multidevice, shards, n_true):
        """True sizes with a non-dividing remainder: the padded tail is
        value-transparent on every shard, incl. a shard that is almost
        entirely padding (n_true = BLOCK+517 at shards=8)."""
        from repro.kernels.fedagg import ops, sharded
        xt, xs, d = self._padded(n_true, shards, seed=shards)
        got = sharded.flat_aggregate(xt, xs, d, lam=2.0, eps=1.0,
                                     shards=shards)
        want = ops.flat_aggregate(xt, xs, d, lam=2.0, eps=1.0)
        self._assert_single(got, want)

    def test_nonpow2_blocks_per_shard(self, multidevice):
        """Padded length = 6 kernel blocks over 2 shards: 3 (non-pow2)
        blocks per shard — the grid sweep must not assume pow2 tiling."""
        from repro.kernels.fedagg import ops, sharded
        xt, xs, d = self._padded(6 * BLOCK - 777, 2, seed=5)
        assert xt.shape[0] == 6 * BLOCK
        got = sharded.flat_aggregate(xt, xs, d, lam=1.5, eps=0.5,
                                     shards=2)
        want = ops.flat_aggregate(xt, xs, d, lam=1.5, eps=0.5)
        self._assert_single(got, want)

    def test_displacement_nondividing(self, multidevice):
        from repro.kernels.fedagg import ops, sharded
        xt, disp, d = self._padded(2 * BLOCK + 33, 2, seed=9)
        z = jnp.zeros_like(xt)
        got = sharded.flat_aggregate(xt, z, d, drift=disp, lam=2.0, eps=1.0,
                                     shards=2)
        want = ops.flat_aggregate(xt, z, d, drift=disp, lam=2.0, eps=1.0)
        self._assert_single(got, want)

    @pytest.mark.parametrize("shards", [2, 4])
    def test_q_int8_nondividing(self, multidevice, shards):
        """int8 payloads: scales stay adjacent to the q blocks they
        dequantize under the contiguous model split."""
        from repro.core import compression
        from repro.kernels.fedagg import ops, sharded
        xt, xs, d = self._padded(2 * BLOCK * shards - 917, shards, seed=3)
        cd = compression.quantize_vec(d, "int8", int(d.shape[0]))
        got = sharded.flat_aggregate(xt, xs, cd.q, scales=cd.scales,
                                     lam=2.0, eps=1.0, shards=shards)
        want = ops.flat_aggregate(xt, xs, cd.q, scales=cd.scales,
                                  lam=2.0, eps=1.0)
        self._assert_single(got, want)

    def test_displacement_q_int8(self, multidevice):
        from repro.core import compression
        from repro.kernels.fedagg import ops, sharded
        xt, disp, d = self._padded(2 * BLOCK + 1001, 2, seed=13)
        z = jnp.zeros_like(xt)
        cd = compression.quantize_vec(d, "int8", int(d.shape[0]))
        got = sharded.flat_aggregate(xt, z, cd.q, scales=cd.scales,
                                     drift=disp, lam=1.0, eps=1.0, shards=2)
        want = ops.flat_aggregate(xt, z, cd.q, scales=cd.scales, drift=disp,
                                  lam=1.0, eps=1.0)
        self._assert_single(got, want)

    @pytest.mark.parametrize("payload", ["f32", "bf16", "int8"])
    @pytest.mark.parametrize("mode", ["ring", "displacement"])
    def test_scalars_are_the_norms_sweeps(self, multidevice, payload,
                                          mode):
        """The sharded step's (4,) scalars are bitwise Eq. 6-7 of its
        own psum'd norms sweep."""
        from repro.kernels.fedagg import sharded
        from repro.sharding.specs import FLAT_SCALES_SPEC, FLAT_VEC_SPEC
        shards = 2
        xt, xs, d = self._padded(2 * BLOCK + 311, shards, seed=41)
        delta, scales = _payload(d, payload)
        stale, drift = ((xs, None) if mode == "ring"
                        else (jnp.zeros_like(xt), xs - xt))
        interp = fedagg.resolve_interpret()
        quant = scales is not None
        rest = tuple(a for a in (scales, drift) if a is not None)

        def body(x, x_stale, dl, *r):
            sq = sharded._psum(fedagg.fedagg_norms(
                x if drift is None else r[-1], x_stale, dl,
                r[0] if quant else None, interpret=interp))
            return jnp.stack(gamma_eta_from_sq(sq[0], sq[1], 2.0, 1.0))

        want = sharded._smap(
            body, shards, (FLAT_VEC_SPEC,) * 3
            + (FLAT_SCALES_SPEC,) * quant + (FLAT_VEC_SPEC,) * len(
                rest[quant:]), sharded._REP)(xt, stale, delta, *rest)
        _, scalars = sharded.flat_aggregate(
            xt, stale, delta, scales=scales, drift=drift, lam=2.0, eps=1.0,
            shards=shards)
        assert scalars.shape == (4,)
        np.testing.assert_array_equal(_bits(scalars), _bits(want))

    def test_packed_gram_is_the_psumd_sweep(self, multidevice):
        """The sharded Gram sweep packs its psum'd outputs for one read."""
        from repro.kernels.fedagg import ops, sharded
        from repro.sharding.specs import FLAT_STACKED_SPEC, FLAT_VEC_SPEC
        b, shards = 3, 2
        xt, _, _ = self._padded(2 * BLOCK + 71, shards, seed=43)
        n = xt.shape[0]
        xs = xt[None] + 0.01 * jax.random.normal(
            jax.random.PRNGKey(44), (b, n), jnp.float32)
        d = jax.random.normal(jax.random.PRNGKey(45), (b, n),
                              jnp.float32) * 0.02
        interp = fedagg.resolve_interpret()
        want = sharded._smap(
            lambda *a: sharded._psum(fedagg.fedagg_norms_batched(
                *a, interpret=interp)), shards,
            (FLAT_VEC_SPEC, FLAT_STACKED_SPEC, FLAT_STACKED_SPEC),
            (sharded._REP,) * 4)(xt, xs, d)
        packed = sharded._norms_batched(shards, interp, False)(xt, xs, d)
        got = ops.unpack_gram(np.asarray(packed), b)
        for g_, w_ in zip(got, want, strict=True):
            np.testing.assert_array_equal(_bits(g_), _bits(w_))

    def test_batched_nondividing(self, multidevice):
        """Batched Gram sweep at a non-dividing remainder: one psum of
        the (B,)/(B,B) partials reproduces the replicated schedule."""
        from repro.kernels.fedagg import ops, sharded
        b, shards = 3, 2
        xt, _, _ = self._padded(2 * BLOCK + 71, shards, seed=17)
        n = xt.shape[0]
        xs = xt[None] + 0.01 * jax.random.normal(
            jax.random.PRNGKey(18), (b, n), jnp.float32)
        d = jax.random.normal(jax.random.PRNGKey(19), (b, n),
                              jnp.float32) * 0.02
        new, etas, gammas, dists, dnorms, _ = sharded.flat_aggregate_batched(
            xt, xs, d, lam=2.0, eps=1.0, shards=shards)
        rnew, retas, rgammas, rdists, rdnorms, _ = ops.flat_aggregate_batched(
            xt, xs, d, lam=2.0, eps=1.0)
        np.testing.assert_allclose(etas, retas, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(gammas, rgammas, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(jax.device_get(new)),
                                   np.asarray(jax.device_get(rnew)),
                                   rtol=1e-4, atol=1e-5)

    def test_batched_bf16_payload(self, multidevice):
        """bf16 wire payloads ride the UNCOMPRESSED batched kernels (f32
        upcast on tile load) — sharded must agree with replicated on the
        exact same bf16 stacks."""
        from repro.kernels.fedagg import ops, sharded
        b, shards = 2, 2
        xt, _, _ = self._padded(2 * BLOCK + 5, shards, seed=23)
        n = xt.shape[0]
        xs = (xt[None] + 0.01 * jax.random.normal(
            jax.random.PRNGKey(24), (b, n))).astype(jnp.bfloat16)
        d = (jax.random.normal(jax.random.PRNGKey(25), (b, n))
             * 0.02).astype(jnp.bfloat16)
        new, etas, gammas, *_ = sharded.flat_aggregate_batched(
            xt, xs, d, lam=2.0, eps=1.0, shards=shards)
        rnew, retas, rgammas, *_ = ops.flat_aggregate_batched(
            xt, xs, d, lam=2.0, eps=1.0)
        np.testing.assert_allclose(etas, retas, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(gammas, rgammas, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(jax.device_get(new)),
                                   np.asarray(jax.device_get(rnew)),
                                   rtol=1e-4, atol=1e-5)

    def test_batched_q_int8(self, multidevice):
        from repro.core import compression
        from repro.kernels.fedagg import ops, sharded
        b, shards = 3, 2
        xt, _, _ = self._padded(2 * BLOCK + 600, shards, seed=29)
        n = xt.shape[0]
        xs = xt[None] + 0.01 * jax.random.normal(
            jax.random.PRNGKey(30), (b, n), jnp.float32)
        rows = [compression.quantize_vec(
            jax.random.normal(jax.random.PRNGKey(31 + i), (n,),
                              jnp.float32) * 0.02, "int8", n)
            for i in range(b)]
        qs = jnp.stack([r.q for r in rows])
        scales = jnp.stack([r.scales for r in rows])
        new, etas, gammas, *_ = sharded.flat_aggregate_batched(
            xt, xs, qs, scales=scales, lam=2.0, eps=1.0, shards=shards)
        rnew, retas, rgammas, *_ = ops.flat_aggregate_batched(
            xt, xs, qs, scales=scales, lam=2.0, eps=1.0)
        np.testing.assert_allclose(etas, retas, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(gammas, rgammas, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(jax.device_get(new)),
                                   np.asarray(jax.device_get(rnew)),
                                   rtol=1e-4, atol=1e-5)


class TestSSD:
    @pytest.mark.parametrize("shape", [(2, 128, 8, 16, 64),
                                       (1, 256, 16, 32, 128),
                                       (3, 64, 4, 8, 32)])
    def test_against_oracle(self, shape):
        bh, s, p, n, chunk = shape
        chunk = min(chunk, s)
        k = jax.random.PRNGKey(0)
        x = jax.random.normal(k, (bh, s, p))
        dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (bh, s)))
        a = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (bh,)) * 0.3)
        b = jax.random.normal(jax.random.PRNGKey(3), (bh, s, n)) * 0.3
        c = jax.random.normal(jax.random.PRNGKey(4), (bh, s, n)) * 0.3
        y, st = ssd_scan(x, dt, a, b, c, chunk=chunk)
        yr, sr = ssd_scan_ref(x, dt, a, b, c, chunk=chunk)
        np.testing.assert_allclose(y, yr, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(st, sr, rtol=1e-4, atol=1e-4)

    def test_chunk_invariance(self):
        """Kernel result must not depend on the chunk size (pure tiling)."""
        bh, s, p, n = 2, 128, 8, 16
        k = jax.random.PRNGKey(0)
        x = jax.random.normal(k, (bh, s, p))
        dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (bh, s)))
        a = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (bh,)) * 0.3)
        b = jax.random.normal(jax.random.PRNGKey(3), (bh, s, n)) * 0.3
        c = jax.random.normal(jax.random.PRNGKey(4), (bh, s, n)) * 0.3
        y32, _ = ssd_scan(x, dt, a, b, c, chunk=32)
        y128, _ = ssd_scan(x, dt, a, b, c, chunk=128)
        np.testing.assert_allclose(y32, y128, rtol=1e-4, atol=1e-4)

    def test_model_wrapper(self):
        from repro.kernels.ssd.ops import ssd_chunked_pallas
        from repro.models.ssm import ssd_chunked
        bsz, s, h, p, g, n = 2, 64, 4, 8, 2, 16
        k = jax.random.PRNGKey(0)
        x = jax.random.normal(k, (bsz, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                               (bsz, s, h)))
        a = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (h,)) * 0.3)
        b = jax.random.normal(jax.random.PRNGKey(3), (bsz, s, g, n)) * 0.3
        c = jax.random.normal(jax.random.PRNGKey(4), (bsz, s, g, n)) * 0.3
        y1, s1 = ssd_chunked_pallas(x, dt, a, b, c, chunk=32)
        y2, s2 = ssd_chunked(x, dt, a, b, c, 32)
        np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-4)


class TestRGLRU:
    @pytest.mark.parametrize("shape", [(2, 256, 128, 128, 64),
                                       (1, 64, 512, 32, 512),
                                       (3, 128, 96, 64, 32)])
    def test_against_oracle(self, shape):
        b, s, w, chunk, tile_w = shape
        k = jax.random.PRNGKey(0)
        log_at = -jnp.abs(jax.random.normal(k, (b, s, w))) * 0.1
        xi = jax.random.normal(jax.random.PRNGKey(1), (b, s, w))
        got = rglru_scan(log_at, xi, chunk=chunk, tile_w=tile_w)
        want = rglru_scan_ref(log_at, xi)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_gate_wrapper_matches_model(self):
        from repro.models.rglru import rglru_scan as model_scan
        b, s, w = 2, 128, 64
        k = jax.random.PRNGKey(0)
        x = jax.random.normal(k, (b, s, w))
        r = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(1), (b, s, w)))
        i = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2), (b, s, w)))
        lam = jax.random.normal(jax.random.PRNGKey(3), (w,)) * 0.5 + 2.0
        h1, f1 = rglru_pallas(x, r, i, lam, chunk=64, tile_w=32)
        h2, f2 = model_scan(x, r, i, lam)
        np.testing.assert_allclose(h1, h2, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(f1, f2, rtol=1e-4, atol=1e-5)


class TestSWAAttn:
    @pytest.mark.parametrize("gqa", [(8, 8), (8, 2), (4, 1)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_against_oracle(self, gqa, dtype):
        h, kv = gqa
        b, s, d = 2, 256, 64
        k = jax.random.PRNGKey(0)
        q = jax.random.normal(k, (b, 1, h, d), dtype)
        kc = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, d), dtype)
        vc = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, d), dtype)
        vl = jnp.array([s // 2, s], jnp.int32)
        got = decode_attention_pallas(q, kc, vc, vl, block_kv=64)
        want = swa_decode_ref(q[:, 0], kc, vc, vl)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(got[:, 0].astype(jnp.float32),
                                   want.astype(jnp.float32), rtol=tol,
                                   atol=tol)

    def test_valid_len_masking(self):
        """Entries beyond valid_len must not affect the result."""
        b, s, h, kv, d = 1, 128, 4, 4, 32
        q = jax.random.normal(jax.random.PRNGKey(0), (b, 1, h, d))
        kc = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, d))
        vc = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, d))
        vl = jnp.array([64], jnp.int32)
        out1 = decode_attention_pallas(q, kc, vc, vl, block_kv=32)
        kc2 = kc.at[:, 64:].set(999.0)
        vc2 = vc.at[:, 64:].set(-999.0)
        out2 = decode_attention_pallas(q, kc2, vc2, vl, block_kv=32)
        np.testing.assert_allclose(out1, out2, rtol=1e-6)
