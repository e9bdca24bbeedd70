"""On-chip smoke test of the federated main path.

    python chip_smoke.py             # one TPU chip: phases 1-3
    python chip_smoke.py --chips 4   # four-chip host: the mesh phase only

Everything runs in this one process (a chip serves one process at a
time). Phases, in order; any failure exits non-zero:

1. Device check. The default JAX device must be a TPU; there is no CPU
   fallback.
2. Full-width arch run. h2o-danube-1.8b at its published widths with
   depth cut to 2 layers, trained by 2 clients through
   ``FederatedSimulation`` on the flat-state ``pallas`` server (compiled
   fedagg kernels, window 0), then the same seed on the ``pytree`` server
   (the plain jnp reference). Traces must match and gamma/eta agree.
3. Batched drain and int8 wire path. The ``synthetic-burst`` scenario on
   the ``pallas`` server (the multi-delta Gram-sweep kernels), with f32
   and with int8 deltas, each against the ``pytree`` server.

With ``--chips 4`` only the mesh path runs: phase 2's arch run on the
pod-sharded client engine with the server state model-sharded over four
chips and int8 deltas, against the same seed on one device.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

SEED = 0
#: phase 2: accepted updates per run, and the ArchTask geometry
ARCH_UPDATES = 8
ARCH_SEQ_LEN, ARCH_BATCH = 512, 2
#: phase 3: virtual seconds of the burst scenario
BURST_TIME = 3.0
#: agreement with the pytree reference. Phase 2's client step computes in
#: bf16 (the published dtype), so a last-bit difference in the aggregated
#: f32 model can flip bf16 roundings in later client steps. Phase 3 trains
#: the paper's f32 MLP at full matmul precision; with int8 deltas the same
#: last-bit differences can move a client's delta across a quantization
#: level, which later updates carry forward.
ARCH_RTOL, ARCH_ATOL = 1e-3, 1e-6
BURST_RTOL = {"off": 1e-5, "int8": 1e-3}
BURST_ATOL = 1e-5
#: the mesh phase: f32 compute, so only reduction order differs
#: (tests/test_flat_sharded.py pins this engine x shard comparison at 2e-4)
MESH_RTOL, MESH_ATOL = 2e-4, 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def device_check(chips: int) -> dict:
    """Phase 1: the default device must be a TPU, ``chips`` of them."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU: JAX's default device platform is "
            f"{dev.platform!r} ({len(devices)} device(s))")
    if len(devices) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} TPU devices, "
                         f"JAX sees {len(devices)}")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    log(f"[1] device: {info}")
    return info


# ------------------------------------------------------------ comparison --

def summarize(res) -> dict:
    """The parts of a run the comparisons read."""
    return {
        "trace": [(h.iteration, h.client_id, h.lag, h.k_next, h.screen)
                  for h in res.history],
        "gamma": [h.gamma for h in res.history],
        "eta": [h.eta for h in res.history],
        "losses": [p.loss for p in res.points],
        "updates": res.total_updates,
        "drains": res.total_drains,
        "plan": res.plan,
    }


def max_rel(a, b, atol: float) -> float:
    return max((abs(x - y) / (abs(y) + atol) for x, y in zip(a, b)),
               default=0.0)


def compare(name: str, got: dict, ref: dict, rtol: float,
            atol: float) -> None:
    """Same event trace and update count, gamma/eta within ``rtol``
    (+``atol``), every eval loss finite. Raises on any miss."""
    import numpy as np
    if got["updates"] != ref["updates"] or got["trace"] != ref["trace"]:
        raise AssertionError(
            f"{name}: event traces differ ({got['updates']} vs "
            f"{ref['updates']} updates)")
    for key in ("gamma", "eta"):
        np.testing.assert_allclose(got[key], ref[key], rtol=rtol, atol=atol,
                                   err_msg=f"{name}: {key}")
    for run in (got, ref):
        if not all(math.isfinite(x) for x in run["losses"]):
            raise AssertionError(f"{name}: non-finite eval loss "
                                 f"{run['losses']}")
    log(f"    {name}: {got['updates']} updates in {got['drains']} drains, "
        f"traces equal; max rel diff gamma "
        f"{max_rel(got['gamma'], ref['gamma'], atol):.3e} eta "
        f"{max_rel(got['eta'], ref['eta'], atol):.3e} (rtol {rtol:g}); "
        f"final eval loss {got['losses'][-1]:.6f} vs "
        f"{ref['losses'][-1]:.6f}")


# ------------------------------------------------------------- phase 2 --

def danube_task(num_layers: int = 2, dtype: str = "bfloat16"):
    """h2o-danube-1.8b at its published widths, depth cut to
    ``num_layers``."""
    from repro import configs
    from repro.configs.shapes import TRAIN_4K
    from repro.core.tasks import ArchTask
    cfg = dataclasses.replace(configs.get_arch("h2o-danube-1.8b"),
                              num_layers=num_layers, dtype=dtype)
    shape = dataclasses.replace(TRAIN_4K, seq_len=ARCH_SEQ_LEN,
                                global_batch=ARCH_BATCH)
    # 128-wide attention chunks: the MXU tile, and a quarter of the
    # backward residuals the 32-wide CPU default keeps
    return ArchTask(cfg=cfg, shape=shape, q_chunk=128, kv_chunk=128)


def arch_fed(**changes):
    """2 clients, K fixed at 2 (one client-step compile), window 0, a
    4-deep GMIS ring: (2 + depth) model copies of server state beside one
    client step must fit 16 GB of HBM."""
    from repro.configs.scenarios import ARCH_FED_BASELINE
    knobs = dict(num_clients=2, k_initial=2, k_min=1, k_max=2,
                 gmis_depth=4, batch_window=0.0, client_engine="loop")
    return dataclasses.replace(ARCH_FED_BASELINE, **{**knobs, **changes})


def arch_run(task, fed):
    from repro.core.simulator import FederatedSimulation
    t0 = time.time()
    sim = FederatedSimulation(task, fed, "asyncfeded", seed=SEED)
    res = sim.run(max_time=1e9, max_updates=ARCH_UPDATES)
    return sim, res, time.time() - t0


def phase_arch(dev) -> None:
    from repro.kernels.fedagg import fedagg, ops
    if fedagg.resolve_interpret() is not False:
        raise AssertionError("fedagg kernels would run interpreted on TPU")
    task = danube_task()
    n = task.cfg.param_count()
    fed = arch_fed(backend="pallas")
    gb = n * 4 / 2 ** 30
    log(f"[2] {task.cfg.arch_id} x{task.cfg.num_layers} layers: {n:,} "
        f"params, {gb:.2f} GiB f32; server holds ~(2 + gmis_depth "
        f"{fed.gmis_depth}) x {gb:.2f} = {(2 + fed.gmis_depth) * gb:.2f} "
        f"GiB beside one client step")
    sim, res, secs = arch_run(task, fed)
    vec = sim.server._flat.vec
    text = ops.flat_aggregate_program(True).lower(
        vec, vec, vec, lam=fed.lam, eps=fed.eps, cap=fed.staleness_cap,
        interpret=None).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("flat_aggregate compiled without a Mosaic "
                             "kernel (no tpu_custom_call)")
    got = summarize(res)
    del sim, res, vec
    gc.collect()
    peak = dev.memory_stats()["peak_bytes_in_use"]
    log(f"    pallas: {got['updates']} updates in {secs:.1f} s (compiles "
        f"included); flat_aggregate holds tpu_custom_call; peak HBM "
        f"{peak / 2 ** 30:.2f} GiB")
    sim, res, secs = arch_run(task, arch_fed(backend="pytree"))
    ref = summarize(res)
    del sim, res
    gc.collect()
    log(f"    pytree: {ref['updates']} updates in {secs:.1f} s")
    compare("arch pallas vs pytree", got, ref, ARCH_RTOL, ARCH_ATOL)


# ------------------------------------------------------------- phase 3 --

def burst_run(backend: str, compression: str) -> dict:
    import jax
    from repro import configs
    from repro.core.simulator import FederatedSimulation
    task = configs.SYNTHETIC_BURST
    fed = dataclasses.replace(task.fed, backend=backend,
                              delta_compression=compression)
    with jax.default_matmul_precision("highest"):
        sim = FederatedSimulation(task, fed, "asyncfeded", seed=SEED)
        return summarize(sim.run(max_time=BURST_TIME))


def phase_burst() -> None:
    log(f"[3] synthetic-burst, {BURST_TIME} virtual s, auto window")
    for compression in ("off", "int8"):
        got = burst_run("pallas", compression)
        if got["drains"] >= got["updates"]:
            raise AssertionError(
                f"burst/{compression}: no batched drain happened "
                f"({got['updates']} updates, {got['drains']} drains)")
        ref = burst_run("pytree", compression)
        compare(f"burst pallas vs pytree, deltas {compression}", got, ref,
                BURST_RTOL[compression], BURST_ATOL)


# ------------------------------------------------------- four-chip mesh --

def phase_mesh() -> None:
    """Phase 2's arch run on the (pod x model) mesh against one device."""
    import jax
    # The single-device reference stacks both clients' f32 steps (8.7 GiB
    # by the described-chip compile at 1 layer) beside the server state;
    # at 2 layers that step alone needs more than the chip's HBM, so this
    # phase cuts depth to 1 layer (233M params, the embeddings dominate)
    # and keeps a 2-deep GMIS ring.
    task = danube_task(num_layers=1, dtype="float32")
    common = dict(backend="pallas", delta_compression="int8", gmis_depth=2)
    log(f"[4] {task.cfg.arch_id} x{task.cfg.num_layers} layers, f32, int8 "
        f"deltas: cohort_sharded + model_shards=4 vs cohort on one device")
    with jax.default_matmul_precision("highest"):
        sim, res, secs = arch_run(task, arch_fed(
            client_engine="cohort_sharded", model_shards=4, **common))
        vec = sim.server._flat.vec
        shards = vec.addressable_shards
        sizes = sorted({s.data.shape[0] for s in shards})
        if len(shards) != 4 or sizes != [vec.shape[0] // 4]:
            raise AssertionError(
                f"flat vector of {vec.shape[0]} not split in quarters: "
                f"{[s.data.shape for s in shards]}")
        got = summarize(res)
        log(f"    sharded: {got['updates']} updates in {secs:.1f} s; flat "
            f"vector {vec.shape[0]:,} = 4 x {sizes[0]:,} on "
            f"{sorted(s.device.id for s in shards)}; plan {got['plan']}")
        del sim, res, vec, shards
        gc.collect()
        sim, res, secs = arch_run(task, arch_fed(client_engine="cohort",
                                                 **common))
        ref = summarize(res)
        del sim, res
        gc.collect()
    log(f"    one device: {ref['updates']} updates in {secs:.1f} s; plan "
        f"{ref['plan']}")
    compare("mesh vs one device", got, ref, MESH_RTOL, MESH_ATOL)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)
    info = device_check(args.chips)
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.utils.xla import enable_compile_cache
    log(f"    compile cache: {enable_compile_cache()}")
    import jax
    t0 = time.time()
    if args.chips == 4:
        phase_mesh()
    else:
        phase_arch(jax.devices()[0])
        phase_burst()
    log(f"all phases passed in {time.time() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
