import dataclasses
import os

import jax
import pytest

# NOTE: do NOT set XLA_FLAGS device-count here — smoke tests and benches must
# see the single real CPU device; only launch/dryrun.py forces 512, and the
# multi-device shard_map suite gets its 8 fake devices either from the
# tier1-multidevice CI job's environment or by re-execing itself in a
# subprocess (see `multidevice` / `multidevice_subprocess_env` below).

#: Fake-device count the shard_map equivalence suite runs under. 8 is a
#: power of two > any tier-1 cohort size, so pods outnumber some client
#: buckets (exercising the pod-count clamp) and divide the others.
MULTIDEVICE_COUNT = 8
MULTIDEVICE_FLAG = (
    f"--xla_force_host_platform_device_count={MULTIDEVICE_COUNT}")


def multidevice_subprocess_env() -> dict:
    """Environment for re-running a test module under 8 fake CPU devices.

    The device-count flag only takes effect before the CPU backend
    initializes, which in a full pytest run happened long ago — hence a
    fresh process. PYTHONPATH gains src/ so the subprocess resolves
    `repro` no matter where pytest was invoked from.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " "
                        + MULTIDEVICE_FLAG).strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    prev = env.get("PYTHONPATH")
    env["PYTHONPATH"] = src + os.pathsep + prev if prev else src
    return env


@pytest.fixture(scope="session")
def multidevice() -> int:
    """Skip unless this process actually sees >= MULTIDEVICE_COUNT devices
    (the tier1-multidevice CI job, or a manual XLA_FLAGS run). Tests that
    only need the sharded CODE PATH run without this fixture — a 1-device
    mesh is valid; tests asserting real multi-pod placement require it."""
    n = jax.device_count()
    if n < MULTIDEVICE_COUNT:
        pytest.skip(
            f"needs {MULTIDEVICE_COUNT} devices, have {n}: run the "
            f"tier1-multidevice CI job or set XLA_FLAGS={MULTIDEVICE_FLAG}")
    return n


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def reduced_f32(arch_id: str, **kw):
    """Reduced smoke config in f32 with the CPU-friendly MoE path."""
    from repro import configs
    cfg = configs.reduced(configs.get_arch(arch_id), **kw)
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl="dense"))
    return dataclasses.replace(cfg, dtype="float32")


def flat_aggregate_tree(x_t, x_stale, delta, **kw):
    """One ``ops.flat_aggregate`` step on pytrees: each flattened through
    one ``FlatSpec`` padded to the kernel BLOCK, the new vector
    unflattened back to ``x_t``'s structure and dtypes."""
    from repro.core.aggregation import AggregationResult
    from repro.kernels.fedagg import ops
    from repro.utils import pytree as pt
    spec = pt.FlatSpec(x_t, block=ops._BLOCK)
    new, scalars = ops.flat_aggregate(
        spec.flatten(x_t), spec.flatten(x_stale), spec.flatten(delta), **kw)
    return AggregationResult(spec.unflatten(new), *scalars)
