"""Model-sharded flat-state equivalence (DESIGN.md §14).

``model_shards > 1`` splits the padded flat global vector (and every
GMIS snapshot) over the ``model`` axis of the (pod, model) mesh; Eq. 5-7
run per-shard with ONE cross-shard psum of the squared-norm partials.
These tests pin that the shard boundary is invisible: identical
simulator event traces and float-tolerance-equal gammas/accuracies vs
the replicated pallas path, on the paper task and a reduced ArchTask,
through the sequential, burst-batched, int8-compressed, and
displacement-GMIS aggregation paths — plus the per-device footprint gain
(peak flat-state bytes ~ 1/shards) the sharding exists to buy.

The compressed pod collective (`cohort._wire_core`) is pinned here too:
under ``cohort_sharded`` + ``delta_compression`` the fan-out's
cross-pod gather moves wire-format blocks with per-pod error-feedback
rows, and must reproduce the loop engine's host-side quantization trace
exactly — including with the wire-form adversary twins and combined
with model sharding (the full 2-D pod x model mesh).

Device topology mirrors test_cohort_sharded.py: placement-asserting
tests take the ``multidevice`` fixture (8 fake devices from the
tier1-multidevice CI job), and ``test_reexec_under_8_fake_devices``
closes the gap on a local 1-device run by re-running this module (plus
the sharded kernel-parity class) in a fresh subprocess.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import MULTIDEVICE_COUNT, multidevice_subprocess_env
from repro import configs
from repro.configs import shapes
from repro.core.budget import plan_cohort
from repro.core.simulator import FederatedSimulation
from repro.core.tasks import arch_task


def trace(res):
    return [(h.iteration, h.client_id, h.lag, h.k_next, h.screen)
            for h in res.history]


def assert_same_run(r1, r2, *, rtol=2e-4, atol=1e-5, acc_rtol=1e-3):
    assert trace(r1) == trace(r2)
    np.testing.assert_allclose([h.gamma for h in r1.history],
                               [h.gamma for h in r2.history],
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose([p.accuracy for p in r1.points],
                               [p.accuracy for p in r2.points],
                               rtol=acc_rtol)


def run_sim(task, fed, *, algorithm="asyncfeded", seed=3,
            batch_window=0.0, max_time=2.0, **run_kw):
    sim = FederatedSimulation(task, fed, algorithm, seed=seed,
                              batch_window=batch_window)
    return sim, sim.run(max_time=max_time, **run_kw)


class TestConfigValidation:
    def test_model_shards_must_be_pow2(self):
        for bad in (0, 3, 6, -2):
            with pytest.raises(ValueError, match="model_shards"):
                dataclasses.replace(configs.SYNTHETIC_1_1.fed,
                                    backend="pallas", model_shards=bad)
        for ok in (1, 2, 4, 8):
            dataclasses.replace(configs.SYNTHETIC_1_1.fed,
                                backend="pallas", model_shards=ok)

    def test_model_shards_is_pallas_only(self):
        with pytest.raises(ValueError, match="pallas"):
            dataclasses.replace(configs.SYNTHETIC_1_1.fed,
                                backend="pytree", model_shards=2)


class TestFootprintLaw:
    """The §14 footprint algebra is pure arithmetic — always runs."""

    def test_flat_state_bytes_scales_inverse_with_shards(self):
        p = 64 * (1 << 20)            # 64 MiB of params, divides evenly
        base = shapes.flat_state_bytes(p, gmis_depth=8)
        for s in (2, 4, 8):
            assert shapes.flat_state_bytes(p, 8, model_shards=s) \
                == base // s

    def test_flat_state_bytes_rounds_up_on_nondividing(self):
        got = shapes.flat_state_bytes(1001, 0, model_shards=4)
        assert got == 2 * 251           # (2 + 0) * ceil(1001/4)

    def test_cohort_footprint_only_divides_param_state(self):
        """Only the per-client param-state term shards; batches and
        activations are replicated per pod."""
        kw = dict(param_bytes=10_000, batch_bytes=64, act_bytes=512,
                  clients=8, k_steps=10)
        full = shapes.cohort_footprint_bytes(**kw)
        half = shapes.cohort_footprint_bytes(**kw, model_shards=2)
        assert half < full
        # the gap is exactly the sharded param-state saving
        param_state = (shapes.PARAM_STATE_COPIES - 1) * 10_000 + 10_000
        assert full - half == 8 * (param_state - -(-param_state // 2))

    def test_plan_cohort_width_grows_with_shards(self):
        """Under a fixed budget, dividing per-client param state by the
        shard count lets the planner fit a wider cohort — the §14
        'planned width' gain."""
        task = arch_task("h2o-danube-1.8b", seq_len=16, global_batch=2,
                         num_layers=1, d_model=64)
        kw = dict(clients=32, k=4, param_bytes=8 << 20,
                  budget_bytes=256 << 20, pods=1)
        w1 = plan_cohort(task, task.fed, **kw).width
        w8 = plan_cohort(task, task.fed, model_shards=8, **kw).width
        assert w8 > w1
        assert plan_cohort(task, task.fed, model_shards=1, **kw).width \
            == w1

    def test_plan_cohort_reads_shards_from_fed(self):
        task = arch_task("h2o-danube-1.8b", seq_len=16, global_batch=2,
                         num_layers=1, d_model=64)
        fed = dataclasses.replace(task.fed, backend="pallas",
                                  model_shards=8)
        kw = dict(clients=32, k=4, param_bytes=8 << 20,
                  budget_bytes=256 << 20, pods=1)
        assert plan_cohort(task, fed, **kw).width \
            == plan_cohort(task, fed, model_shards=8, **kw).width


class TestShardedServerEquivalence:
    """model_shards runs reproduce the replicated pallas event trace to
    float tolerance (psum reorders the norm reduction, nothing else)."""

    @pytest.mark.parametrize("shards", [2, 8])
    def test_sequential_paper_task(self, multidevice, shards):
        task = configs.SYNTHETIC_1_1
        fed_p = dataclasses.replace(task.fed, backend="pallas")
        fed_s = dataclasses.replace(fed_p, model_shards=shards)
        _, r1 = run_sim(task, fed_p, max_time=3.0)
        _, r2 = run_sim(task, fed_s, max_time=3.0)
        assert r1.total_updates == r2.total_updates > 10
        assert_same_run(r1, r2)

    def test_burst_batched_path(self, multidevice):
        """batch_window drives the batched Gram sweep: one psum of the
        (B,)/(B,B) partials, host schedule, shard-local apply."""
        task = configs.SYNTHETIC_1_1
        fed_p = dataclasses.replace(task.fed, backend="pallas")
        fed_s = dataclasses.replace(fed_p, model_shards=4)
        _, r1 = run_sim(task, fed_p, batch_window=0.05, max_time=3.0)
        _, r2 = run_sim(task, fed_s, batch_window=0.05, max_time=3.0)
        assert r1.total_drains == r2.total_drains
        assert_same_run(r1, r2)

    def test_burst_drain_reads_the_gram_sweep_in_a_span(self, multidevice,
                                                        tmp_path):
        """The sharded batched drain resolves its schedule through the
        same host step as the replicated one: each batched drain reads
        the psum'd Gram sweep once, in a ``server.sync`` span inside its
        ``server.kernels`` span."""
        import glob
        from jax.profiler import ProfileData
        task = configs.SYNTHETIC_1_1
        fed = dataclasses.replace(task.fed, backend="pallas", model_shards=4)
        with jax.profiler.trace(str(tmp_path)):
            run_sim(task, fed, batch_window=0.05, max_time=1e9,
                    max_updates=12, eval_every=100)
        path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True)
        spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                  dict(ev.stats))
                 for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:") for line in plane.lines
                 for ev in line.events if ev.name.startswith("server.")]
        within = lambda name, outer: [s for s in spans if s[0] == name
                                      and outer[1] <= s[1]
                                      and s[2] <= outer[2]]
        batched = [s for s in spans if s[0] == "server.drain"
                   and s[3]["path"] == "batched"]
        assert batched
        for d in batched:
            kernels, = within("server.kernels", d)
            assert [s[3]["reads"] for s in within("server.sync", d)] == [4]
            assert len(within("server.sync", kernels)) == 1

    def test_int8_burst(self, multidevice):
        """int8 payloads with their block scales through the sharded
        entry points — scales stay adjacent to their q blocks under the
        contiguous model split."""
        task = configs.SYNTHETIC_1_1
        fed_p = dataclasses.replace(task.fed, backend="pallas",
                                    delta_compression="int8")
        fed_s = dataclasses.replace(fed_p, model_shards=4)
        _, r1 = run_sim(task, fed_p, batch_window=0.05, max_time=3.0)
        _, r2 = run_sim(task, fed_s, batch_window=0.05, max_time=3.0)
        assert_same_run(r1, r2)

    def test_displacement_gmis(self, multidevice):
        """DisplacementGMIS stores model-sharded flat snapshots; the
        displacement entry point must agree with replicated."""
        task = configs.SYNTHETIC_1_1
        fed_p = dataclasses.replace(task.fed, backend="pallas")
        fed_s = dataclasses.replace(fed_p, model_shards=2)
        _, r1 = run_sim(task, fed_p, algorithm="asyncfeded-displacement")
        _, r2 = run_sim(task, fed_s, algorithm="asyncfeded-displacement")
        assert_same_run(r1, r2)

    def test_arch_task_sharded(self, multidevice):
        """The §10 substrate under model sharding: a reduced ArchTask's
        flat state splits the same way the paper MLP's does."""
        tiny = arch_task("h2o-danube-1.8b", seq_len=16, global_batch=2,
                         num_layers=1, d_model=64)
        fed_p = dataclasses.replace(tiny.fed, num_clients=3, k_initial=2,
                                    backend="pallas")
        fed_s = dataclasses.replace(fed_p, model_shards=2)
        _, r1 = run_sim(tiny, fed_p, max_time=float("inf"), max_updates=6)
        _, r2 = run_sim(tiny, fed_s, max_time=float("inf"), max_updates=6)
        assert r1.total_updates == r2.total_updates == 6
        assert_same_run(r1, r2)

    def test_per_device_flat_bytes_shrink(self, multidevice):
        """The point of the exercise: each device addresses ~1/shards of
        the padded flat vector, matching the §14 footprint law."""
        task = configs.SYNTHETIC_1_1
        shards = 8
        fed_s = dataclasses.replace(task.fed, backend="pallas",
                                    model_shards=shards)
        sim, _ = run_sim(task, fed_s, max_time=0.3)
        vec = sim.server._flat.vec
        total = vec.nbytes
        per_dev = {}
        for s in vec.addressable_shards:
            per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
        assert len(per_dev) == shards
        for nbytes in per_dev.values():
            assert nbytes == total // shards
        # and the law predicts the same per-copy size
        assert shapes.flat_state_bytes(total, 0, model_shards=shards) \
            == 2 * (total // shards)


class TestCompressedPodCollectives:
    """cohort_sharded + delta_compression: the fan-out's cross-pod
    gather moves wire-format (int8/bf16) blocks with per-pod
    error-feedback rows, and must reproduce the loop engine's host-side
    quantization byte for byte in the event trace."""

    @pytest.mark.parametrize("mode", ["int8", "bf16"])
    @pytest.mark.parametrize("backend", ["pytree", "pallas"])
    def test_wire_matches_loop(self, multidevice, mode, backend):
        task = configs.SYNTHETIC_1_1
        fed_l = dataclasses.replace(task.fed, backend=backend,
                                    delta_compression=mode,
                                    client_engine="loop")
        fed_s = dataclasses.replace(fed_l, client_engine="cohort_sharded")
        _, r1 = run_sim(task, fed_l, batch_window=0.05)
        _, r2 = run_sim(task, fed_s, batch_window=0.05)
        assert r1.total_updates == r2.total_updates > 10
        assert_same_run(r1, r2)

    def test_wire_with_model_shards(self, multidevice):
        """The full 2-D mesh: pod-sharded clients emitting int8 wire
        blocks into a model-sharded server."""
        task = configs.SYNTHETIC_1_1
        fed_ref = dataclasses.replace(task.fed, backend="pallas",
                                      delta_compression="int8",
                                      client_engine="loop")
        fed_2d = dataclasses.replace(fed_ref,
                                     client_engine="cohort_sharded",
                                     model_shards=2)
        _, r1 = run_sim(task, fed_ref)
        _, r2 = run_sim(task, fed_2d)
        assert_same_run(r1, r2)

    def test_residuals_stay_host_neutral(self, multidevice):
        """Error-feedback rows committed back to clients must be neutral
        host arrays: a residual still committed to this fan-out's pod
        mesh would leak that commitment through the next
        compress_update into server state and clash with the next
        dispatch's differently-sized mesh."""
        task = configs.SYNTHETIC_1_1
        fed = dataclasses.replace(task.fed, backend="pallas",
                                  delta_compression="int8",
                                  client_engine="cohort_sharded")
        sim, _ = run_sim(task, fed, batch_window=0.05, max_time=1.0)
        staged = [c for c in sim.clients if c._residual is not None]
        assert staged, "no client ever staged a residual"
        for c in staged:
            assert isinstance(c._residual, np.ndarray)

    @pytest.mark.parametrize("attack", ["sign-flip", "gaussian-noise",
                                        "scale", "zero"])
    def test_adversary_corrupts_wire_form(self, multidevice, attack):
        """Attacks act on the CompressedDelta the sharded engine emitted;
        sign-flip/scale/zero are exact on wire form, so the attacked
        sharded run still matches the attacked loop run."""
        task = configs.SYNTHETIC_1_1
        fed_l = dataclasses.replace(task.fed, backend="pallas",
                                    delta_compression="int8",
                                    client_engine="loop", attack=attack,
                                    attack_frac=0.3)
        fed_s = dataclasses.replace(fed_l, client_engine="cohort_sharded")
        sim1, r1 = run_sim(task, fed_l, seed=5, batch_window=0.05,
                           max_time=1.5)
        sim2, r2 = run_sim(task, fed_s, seed=5, batch_window=0.05,
                           max_time=1.5)
        assert sim1.adversary.applied > 0
        assert sim2.adversary.applied > 0
        if attack == "gaussian-noise":
            # noise dequantizes the emitted payload and re-quantizes: the
            # device-vs-host quantization of the PRE-noise payload can
            # differ by one rounding level at ties, so the attacked
            # streams (and hence adaptive-K traces) are not identical —
            # only the benign parts of the universe are pinned
            assert r1.total_updates > 10 and r2.total_updates > 10
        elif attack == "zero":
            # the loop engine corrupts BEFORE quantization while the
            # wire path quantizes first and zeroes the wire form, so a
            # corrupted client's error-feedback residual accounts a
            # different payload in each engine; its later honest
            # emissions then differ by residual-sized crumbs.  Zeroed
            # rows additionally make gamma = dist/sqrt(eps-level noise)
            # — ill-conditioned by construction.  Pin the trace (every
            # accept/K decision matches) and the well-conditioned
            # gammas to residual-crumb tolerance.
            assert trace(r1) == trace(r2)
            g1 = np.asarray([h.gamma for h in r1.history])
            g2 = np.asarray([h.gamma for h in r2.history])
            ok = g1 < 1e6
            assert ok.sum() > 5
            np.testing.assert_allclose(g1[ok], g2[ok], atol=0.05)
        else:
            assert_same_run(r1, r2)


class TestShardedCheckpoint:
    """save_flat/restore_flat round-trip the padded flat vector with its
    shard layout; a checkpoint saved under one model_shards restores
    exactly under another (padding is zeros by construction)."""

    def test_cross_layout_restore(self, multidevice, tmp_path):
        task = configs.SYNTHETIC_1_1
        fed_s = dataclasses.replace(task.fed, backend="pallas",
                                    model_shards=4)
        fed_p = dataclasses.replace(task.fed, backend="pallas")
        sim_s, _ = run_sim(task, fed_s, max_time=1.0)
        sim_p, _ = run_sim(task, fed_p, max_time=0.3)
        sim_s.server.save_checkpoint(str(tmp_path), step=1)
        sim_p.server.restore_checkpoint(str(tmp_path), step=1)
        n = sim_p.server._flat.spec.n
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(sim_p.server._flat.vec))[:n],
            np.asarray(jax.device_get(sim_s.server._flat.vec))[:n])


def test_reexec_under_8_fake_devices():
    """On a LOCAL 1-device run, re-run this module plus the sharded
    kernel-parity class in a subprocess forcing 8 fake CPU devices.
    Skips when already multidevice, and in CI (tier1-multidevice covers
    it without doubling the tier1 critical path)."""
    if jax.device_count() >= MULTIDEVICE_COUNT:
        pytest.skip("already running with >= 8 devices")
    if os.environ.get("CI"):
        pytest.skip("CI: 8-device coverage comes from tier1-multidevice")
    kernels = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "test_kernels.py")
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "-x", "-q",
             "-p", "no:cacheprovider", __file__,
             kernels + "::TestFedAggSharded", "-k", "not reexec"],
            env=multidevice_subprocess_env(), capture_output=True,
            text=True, timeout=1500)
    except FileNotFoundError:
        pytest.skip("python executable unavailable for subprocess re-exec")
    except subprocess.TimeoutExpired:
        pytest.fail("multidevice subprocess timed out")
    assert proc.returncode == 0, (
        "multidevice re-exec failed:\n" + proc.stdout[-4000:]
        + "\n" + proc.stderr[-2000:])
