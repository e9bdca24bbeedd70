"""Compile guard: every fedagg kernel, and every model-sharded body, must
compile for a TPU v5e chip that is described, not attached; the server's
staging programs must fit beside the model there.

Interpret-mode parity tests (test_kernels.py, test_compression.py) cannot
see Mosaic's block-shape and VMEM rules; these compiles can. Sizes are
the ones the chip smoke run (``chip_smoke.py``) drives: the flat vector of
h2o-danube-1.8b at its published widths cut to 2 layers, and the batched
sweeps at each delta width's knee ``batched_b_max``.

The topology is described inside a module fixture (never at import), so
every pytest worker collects the same tests and only the worker that runs
this file loads the TPU compiler. The persistent compilation cache is off
around these compiles: an entry written for a described chip cannot be
read back without one.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.kernels.fedagg import fedagg, ops, sharded
from repro.utils import pytree as pt
from repro.sharding.specs import (FLAT_SCALES_SPEC, FLAT_STACKED_SCALES_SPEC,
                                  FLAT_STACKED_SPEC, FLAT_VEC_SPEC)

BLOCK = fedagg.BLOCK_ROWS * fedagg.LANES
QB = fedagg.QBLOCK
#: h2o-danube-1.8b, published widths, 2 layers (chip_smoke.py phase 2)
DANUBE_2L_PARAMS = 302_789_120
#: its flat vector padded to the kernel BLOCK, and to BLOCK * 4 shards
N_PHASE2 = math.ceil(DANUBE_2L_PARAMS / BLOCK) * BLOCK
N_SHARD4 = math.ceil(DANUBE_2L_PARAMS / (4 * BLOCK)) * 4 * BLOCK
#: batched sweeps at the knees: B stacked full-width vectors exceed one
#: chip's HBM past B ~ 10, so the knee compiles use a 64-block vector
N_KNEE = 64 * BLOCK
KNEES = [(4, jnp.float32), (2, jnp.bfloat16), (1, jnp.int8)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


class TestSingleChip:
    @pytest.mark.parametrize("kernel", ["norms", "axpy", "fused", "norms_q",
                                        "axpy_q"])
    def test_sequential_kernels_at_phase2_length(self, one_chip, kernel):
        n = N_PHASE2
        vec = _sds(one_chip, (n,))
        q = _sds(one_chip, (n,), jnp.int8)
        s = _sds(one_chip, (n // QB,))
        eta = _sds(one_chip, ())
        fn, args = {
            "norms": (fedagg.fedagg_norms, (vec, vec, vec)),
            "axpy": (fedagg.fedagg_axpy, (vec, vec, eta)),
            "fused": (fedagg.fedagg_fused, (vec, vec, vec, eta)),
            "norms_q": (fedagg.fedagg_norms_q, (vec, vec, q, s)),
            "axpy_q": (fedagg.fedagg_axpy_q, (vec, q, s, eta)),
        }[kernel]
        _compile(lambda *a: fn(*a, interpret=False), *args)

    @pytest.mark.parametrize("delta_bytes,dtype", KNEES)
    @pytest.mark.parametrize("n,at_knee", [(N_KNEE, True),
                                           (N_PHASE2, False)])
    def test_batched_kernels(self, one_chip, delta_bytes, dtype, n,
                             at_knee):
        """B = the knee on a short vector (the VMEM-bound case) and B = 2
        on the full phase-2 vector (the HBM-bound case)."""
        b = fedagg.batched_b_max(delta_bytes) if at_knee else 2
        xt = _sds(one_chip, (n,))
        stales = _sds(one_chip, (b, n))
        deltas = _sds(one_chip, (b, n), dtype)
        etas = _sds(one_chip, (b,))
        if dtype == jnp.int8:
            scales = _sds(one_chip, (b, n // QB))
            _compile(lambda *a: fedagg.fedagg_norms_batched_q(
                *a, interpret=False), xt, stales, deltas, scales)
            _compile(lambda *a: fedagg.fedagg_apply_batched_q(
                *a, interpret=False), xt, deltas, scales, etas)
        else:
            _compile(lambda *a: fedagg.fedagg_norms_batched(
                *a, interpret=False), xt, stales, deltas)
            _compile(lambda *a: fedagg.fedagg_apply_batched(
                *a, interpret=False), xt, deltas, etas)

    @pytest.mark.parametrize("direction", ["flatten", "unflatten"])
    def test_staging_keeps_no_model_sized_temporary(self, one_chip,
                                                    direction):
        """The server's flat<->pytree staging at the phase-2 leaf shapes
        writes its result in place: no temporary as large as the flat
        vector beside it (the chip runs near full HBM)."""
        shapes = _danube_2l_leaf_shapes()
        n = sum(math.prod(s) for s in shapes)
        assert n == DANUBE_2L_PARAMS
        if direction == "flatten":
            compiled = pt._flatten.lower(
                [_sds(one_chip, s) for s in shapes], N_PHASE2).compile()
        else:
            compiled = pt._unflatten.lower(
                _sds(one_chip, (N_PHASE2,)), tuple(shapes),
                (np.dtype(np.float32),) * len(shapes)).compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < n * 4 // 2, mem

    def test_in_place_aggregate_writes_into_the_delta(self, one_chip):
        """The ring drain's step at the phase-2 length: x_{t+1} takes the
        donated delta's buffer, with no model-sized temporary beside it."""
        vec = _sds(one_chip, (N_PHASE2,))
        compiled = ops.flat_aggregate_program(True).lower(
            vec, vec, vec, lam=1.0, eps=1.0, cap=0.0,
            interpret=False).compile()
        assert "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= N_PHASE2 * 4, mem
        assert mem.temp_size_in_bytes < N_PHASE2 * 4 // 2, mem


def _danube_2l_leaf_shapes():
    """h2o-danube-1.8b's weight shapes at its published widths, 2 layers."""
    d, kv, ff, vocab = 2560, 640, 6912, 32000
    layer = [(d, d), (d, kv), (d, kv), (d, d), (d, ff), (d, ff), (ff, d),
             (d,), (d,)]
    return [(vocab, d), *layer, *layer, (d,), (d, vocab)]


@pytest.fixture
def four_chip_mesh(topo, monkeypatch):
    """The sharded dispatch builders, pointed at a (pod=1, model=4) mesh
    over the described chips; their caches are cleared around the test so
    no described-chip program outlives it."""
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4),
                ("pod", "model"))
    monkeypatch.setattr(sharded, "fedagg_mesh", lambda shards: mesh)
    builders = (sharded._aggregate, sharded._aggregate_displacement,
                sharded._aggregate_q, sharded._aggregate_displacement_q,
                sharded._norms_batched, sharded._apply_batched,
                sharded._norms_batched_q, sharded._apply_batched_q)
    for b in builders:
        b.cache_clear()
    yield mesh
    for b in builders:
        b.cache_clear()


class TestFourChipMesh:
    @pytest.mark.parametrize("body", ["aggregate", "displacement",
                                      "aggregate_q", "displacement_q",
                                      "batched", "batched_q"])
    def test_sharded_bodies(self, four_chip_mesh, body):
        n = N_SHARD4
        b = 2
        at = lambda spec, shape, dtype=jnp.float32: _sds(
            NamedSharding(four_chip_mesh, spec), shape, dtype)
        rep = jax.sharding.PartitionSpec()
        vec = at(FLAT_VEC_SPEC, (n,))
        q = at(FLAT_VEC_SPEC, (n,), jnp.int8)
        s = at(FLAT_SCALES_SPEC, (n // QB,))
        stack = at(FLAT_STACKED_SPEC, (b, n))
        qstack = at(FLAT_STACKED_SPEC, (b, n), jnp.int8)
        sstack = at(FLAT_STACKED_SCALES_SPEC, (b, n // QB))
        etas = at(rep, (b,))
        scal = (4, 1.0, 1.0, 0.0, False)
        calls = {
            "aggregate": [(sharded._aggregate(*scal), (vec, vec, vec),
                           True)],
            "displacement": [(sharded._aggregate_displacement(*scal),
                              (vec, vec, vec, vec), True)],
            "aggregate_q": [(sharded._aggregate_q(*scal), (vec, vec, q, s),
                             True)],
            "displacement_q": [(sharded._aggregate_displacement_q(*scal),
                                (vec, vec, q, s, vec), True)],
            "batched": [(sharded._norms_batched(4, False),
                         (vec, stack, stack), True),
                        (sharded._apply_batched(4, False),
                         (vec, stack, etas), False)],
            "batched_q": [(sharded._norms_batched_q(4, False),
                           (vec, stack, qstack, sstack), True),
                          (sharded._apply_batched_q(4, False),
                           (vec, qstack, sstack, etas), False)],
        }[body]
        for fn, args, psum in calls:
            text = fn.lower(*args).compile().as_text()
            assert "tpu_custom_call" in text
            # norm partials cross shards in one all-reduce (the psum); the
            # apply sweeps are shard-local
            assert ("all-reduce" in text) == psum
