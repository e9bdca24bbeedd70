"""One run of one cell: set-up, the measured window, the reference check.

Set-up makes the weights on the device and the client data from the seed,
warms every program shape the traffic can reach (``pb_warm``), builds one
``FederatedSimulation(task, fed, "asyncfeded")`` on the flat-state
``pallas`` server and runs it (``run(max_time=<huge>)``): the first drains
are the warm-up stretch; the drains after them, for ``seconds`` of wall
time, are the window (``pb_window``). Once the window has closed and the
program's state is freed, the plain reference (``pb_reference``) replays
the run's first drains on their own arrival schedule, and every local
round and drain that set-up ran through the window's programs (each K,
client bucket and burst size the traffic can reach), and ``pb_check``
compares.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

import pb_check
import pb_device
import pb_models
import pb_spec

#: bytes a model element takes on the wire, by delta compression mode
WIRE_BYTES = {"off": 4, "bf16": 2, "int8": 1}
QBLOCK = 1024                  # elements per int8 scale
#: updates of the run's first drains the reference follows (rounded up to
#: whole drains)
CAPTURE_UPDATES = 3


def derive_seeds(seed: int) -> dict:
    """Independent streams for the weights, the data and the updates of
    set-up's drains, from one seed of any size."""
    ss = np.random.SeedSequence(int(seed))
    w, d, u = ss.spawn(3)
    return {"weights": int(w.generate_state(1)[0] & 0x7FFFFFFF),
            "data": int(d.generate_state(1)[0]),
            "updates": int(u.generate_state(1)[0] & 0x7FFFFFFF)}


def enable_compile_cache(root: str) -> str:
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def fed_config(cell: pb_spec.Cell):
    from repro.configs.base import FedConfig
    knobs = {**cell.config["fed"], **cell.traffic["fed"],
             "num_clients": cell.traffic["clients"]}
    if "behavior_params" in knobs:
        knobs["behavior_params"] = tuple(sorted(
            knobs["behavior_params"].items()))
    return FedConfig(**knobs, backend="pallas")


def least_drain_bytes(n: int, b: int, compression: str) -> float:
    """Bytes one drain of ``b`` updates must move at the least: read the
    model and write it back once, read each update's stale model (f32)
    and its delta at wire width (plus int8 block scales)."""
    per_update = 4 + WIRE_BYTES[compression]
    if compression == "int8":
        per_update += 4 / QBLOCK
    return n * (8 + b * per_update)


def least_drain_flops(n: int, b: int) -> float:
    """Eq. 5-7 FLOPs of one drain: two squared norms and an AXPY per
    update (7 per element), and the pairwise products that fold B
    updates into one sweep (2 per element and pair)."""
    return n * (7 * b + 2 * b * (b - 1))


def plant_server(server, fault: str) -> None:
    """A server step that returns its state unchanged, for the fault
    tests."""
    if fault == "state_unchanged":
        agg = server._agg
        for name in ("flat_aggregate", "flat_aggregate_batched"):
            orig = agg[name]
            agg[name] = (lambda x_t, *a, _o=orig, **kw:
                         (x_t, *_o(x_t, *a, **kw)[1:]))


def plant(sim, fault: str) -> None:
    """Break the timed path underneath, for the fault tests: a server
    step that returns its state unchanged, or the first leaf of every
    update of the first fan-out (every client's first round, so the
    first arrival's too) altered where the client produces it."""
    if fault == "state_unchanged":
        plant_server(sim.server, fault)
    elif fault == "altered_delta":
        import jax
        orig = sim._run_locals
        done = []

        def run_locals(jobs):
            out = orig(jobs)
            if not done:
                for upd in out:
                    leaves, tdef = jax.tree.flatten(upd.delta)
                    upd.delta = jax.tree.unflatten(
                        tdef, [2.0 * leaves[0], *leaves[1:]])
                done.append(True)
            return out
        sim._run_locals = run_locals
    elif fault not in ("", "half_batch", "altered_round"):
        raise ValueError(f"unknown fault {fault!r}")


@contextlib.contextmanager
def planted_round(fault: str, k: int):
    """With ``altered_round``, every local round of ``k`` steps returns its
    update's first leaf doubled, as a miscompiled program for one K would:
    rounds of every other K, and the run's own warm-up drains where they
    train with another K, stay sound."""
    if fault != "altered_round":
        yield
        return
    import jax
    from repro.core.client import Client
    orig = Client.run_local

    def run_local(self, params, kk, it, prox_mu=0.0):
        upd, loss = orig(self, params, kk, it, prox_mu)
        if kk == k:
            leaves, tdef = jax.tree.flatten(upd.delta)
            upd.delta = jax.tree.unflatten(tdef, [2.0 * leaves[0],
                                                  *leaves[1:]])
        return upd, loss
    Client.run_local = run_local
    try:
        yield
    finally:
        Client.run_local = orig


def run_cell(cell: pb_spec.Cell, seed: int, seconds: float, trace: bool,
             t_process: float, root: str, *, device_check: bool = True,
             fault: str = "", variants=()) -> dict:
    """Run the cell once and return the result line's object. ``variants``
    are extra ``(mode, fault)`` reference replays, each reported with the
    numbers it reads against the reference (the control readings). The
    configuration's ``matmul_precision`` holds for the whole run."""
    import jax
    precision = cell.config.get("matmul_precision")
    k_last = int(cell.traffic["fed"].get("k_max",
                                         cell.config["fed"]["k_initial"]))
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()), planted_round(fault, k_last):
        return _run_cell(cell, seed, seconds, trace, t_process, root,
                         device_check, fault, variants)


def _run_cell(cell, seed, seconds, trace, t_process, root, device_check,
              fault, variants):
    import jax
    if device_check:
        device = pb_device.check(cell.chips)
        peaks = pb_device.peaks(device["kind"])
    else:
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
        peaks = None
    if device_check:
        enable_compile_cache(root)
    sys.path.insert(0, os.path.join(root, "src"))
    from repro.core.simulator import FederatedSimulation
    import pb_reference
    import pb_warm
    import pb_window

    cfg, traffic = cell.config, cell.traffic
    pb_window.listen_for_compiles()
    model, adapter = pb_models.model(cfg), pb_models.adapter(cfg)
    seeds = derive_seeds(seed)
    fed = fed_config(cell)
    weights = model.make_weights(cfg, seeds["weights"])
    data = model.make_data(cfg, traffic, seeds["data"])
    pb_models.stage_inputs(cell.name, cfg, traffic, weights, data)
    task = adapter.program_task(cell.name, cfg, traffic, fed,
                                fault="half_batch" if fault == "half_batch"
                                else "")
    warmed, setup_out = pb_warm.warm(
        task, fed, traffic, weights, data, seeds["updates"],
        plant=(lambda server: plant_server(server, fault)))
    del weights
    trace_dir = tempfile.mkdtemp(prefix="pbtrace-") if trace else None

    def start_trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    sim = FederatedSimulation(task, fed, "asyncfeded",
                              seed=traffic["arrival_seed"],
                              heterogeneity=traffic["heterogeneity"])
    plant(sim, fault)
    win = pb_window.Window(sim, seconds=seconds,
                           warmup_drains=traffic["warmup_drains"],
                           capture_updates=CAPTURE_UPDATES, annotate=trace,
                           on_open=start_trace if trace else None)
    try:
        sim.run(max_time=1e18, eval_every=traffic["eval_every"])
        raise RuntimeError("the simulation ended before its window closed")
    except pb_window.StopWindow:
        pass
    finally:
        win.detach()
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        import pb_trace
        reduced = pb_trace.reduce(*pb_trace.read(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = pb_device.memory_peak_bytes(
        jax.devices()[:cell.chips])

    # ---------------------------------------------------- window numbers --
    records = win.window_records()
    sizes = [b for _, _, b in win.server_spans]
    server_s = [t1 - t0 for t0, t1, _ in win.server_spans]
    client_s = sum(t1 - t0 for t0, t1, _ in win.client_spans)
    n = model.param_count(cfg)
    comp = fed.delta_compression
    least = [max(least_drain_bytes(n, b, comp) / peaks["hbm_bytes_per_s"],
                 least_drain_flops(n, b) / peaks["bf16_flops_per_s"])
             if peaks else None for b in sizes]
    ks = [r.k_used for r in records]
    run = SimpleNamespace(
        window_s=win.window_s, updates=len(records), drains=len(sizes),
        server_s=server_s, client_s=client_s, drain_sizes=sizes,
        compiles=win.compiles_in_window,
        flops=sum(ks) * model.step_flops(cfg, traffic),
        least_agg_s=least,
        least_agg_bytes=[least_drain_bytes(n, b, comp) for b in sizes],
        peaks=peaks, chips=cell.chips, trace=reduced)
    counters = traffic_counters(run, records, device)
    spans = span_summary(server_s, win.client_spans, win.gc_pauses)
    setup_s = win.t_start - t_process

    # ----------------------------------------------------- capture, free --
    cap = win.capture
    hist = sim.server.history[:sum(cap.drain_sizes)]
    arrivals, prog_losses, seen = [], [], collections.Counter()
    for r in hist:
        arrivals.append(pb_reference.Arrival(
            r.client_id, r.iteration - 1 - r.lag, r.k_used))
        prog_losses.append(cap.losses[r.client_id][seen[r.client_id]])
        seen[r.client_id] += 1
    drains, i = [], 0
    for b in cap.drain_sizes:
        drains.append(arrivals[i:i + b])
        i += b
    prog_delta, prog_x_end = cap.first_delta_norms, cap.x_end
    coverage = unchecked(records, sizes, drains, setup_out)
    del sim, win, task, cap, hist
    pb_models.INPUTS.pop(cell.name, None)
    gc.collect()

    # ---------------------------------------------------------- reference --
    t_ref = time.perf_counter()
    x1 = model.make_weights(cfg, seeds["weights"])
    prog = SimpleNamespace(
        losses=prog_losses, first_delta=prog_delta,
        change=np.asarray(pb_reference.change_norms(
            jax.device_put(prog_x_end), x1)),
        rounds=[(l, n) for _, _, l, n in setup_out["rounds"]],
        drains=[n for _, n in setup_out["drains"]])
    del prog_x_end, x1
    fed_d = {**cfg["fed"], **traffic["fed"]}
    round_spec = [(c, k) for c, k, _, _ in setup_out["rounds"]]
    drain_spec = [b for b, _ in setup_out["drains"]]

    def reference(mode="f32", vfault=""):
        rep = pb_reference.replay(model, cfg, traffic, fed_d,
                                  seeds["weights"], data[0], drains,
                                  mode=mode, fault=vfault)
        return rep._replace(
            rounds=pb_reference.replay_rounds(
                model, cfg, traffic, fed_d, seeds["weights"], data[0],
                round_spec, mode=mode, fault=vfault),
            drains=(pb_reference.replay_drains(
                model, cfg, fed_d, seeds["weights"], seeds["updates"],
                drain_spec) if drain_spec else []))

    rep = reference()
    values = pb_check.numbers(prog, rep)
    worst = {"loss_gap": pb_check.worst_update(prog.losses, rep.losses),
             "grad_gap": pb_check.worst_leaf(prog.first_delta,
                                             rep.first_delta,
                                             rep.first_delta),
             "change_gap": pb_check.worst_leaf(prog.change, rep.change,
                                               rep.first_delta)}
    if rep.rounds:
        gaps = pb_check.round_gaps(prog.rounds, rep.rounds)
        worst["round_gap"] = round_spec[int(np.argmax(gaps))]
    readings = {}
    for mode, vfault in variants:
        readings[f"{mode}/{vfault or 'none'}"] = pb_check.numbers(
            reference(mode, vfault), rep)
    ref_s = time.perf_counter() - t_ref
    correct = pb_check.verdict(values, cell.limits)

    # ------------------------------------------------------------- result --
    failed = sum(1 for r in records if not math.isfinite(r.gamma))
    result = {"correct": bool(correct), "attempted": run.updates,
              "failed": failed}
    if trace:
        result["metrics"] = per_layer_metrics(cell, run)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        result["metrics"] = end_to_end_metrics(cell, run, setup_s)
    result["device"] = device
    result["checks"] = {k: {"value": values[k], "limit": cell.limits[k]}
                        for k in pb_check.ordered(values)}
    info = {"counters": counters, "warmed": warmed,
            "spans": spans,
            "reference_s": ref_s, "setup_s": setup_s, "worst": worst,
            "replayed": {"updates": len(arrivals), "drains": len(drains),
                         "max_B": max(map(len, drains)),
                         "K": sorted({a.k for a in arrivals}),
                         "setup_rounds": len(round_spec),
                         "setup_drains": len(drain_spec)},
            "unchecked": coverage,
            "first": {"arrivals": [list(a) for a in arrivals[:3]],
                      "program_losses": prog_losses[:3],
                      "reference_losses": rep.losses[:3],
                      "gammas": rep.gammas[:3], "etas": rep.etas[:3]},
            "readings": readings}
    return {"result": result, "info": info,
            "check_lines": pb_check.lines(values, cell.limits)}


def unchecked(records, window_sizes, drains, setup_out) -> dict:
    """The K and burst sizes the window ran that neither set-up's checked
    rounds and drains nor the warm-up drains covered (empty when every
    program the window ran was compared)."""
    ks = {k for _, k, _, _ in setup_out["rounds"]}
    ks |= {a.k for d in drains for a in d}
    bs = {b for b, _ in setup_out["drains"]} | {len(d) for d in drains}
    return {"K": sorted({r.k_used for r in records} - ks),
            "B": sorted(set(window_sizes) - bs)}


def traffic_counters(run, records, device) -> dict:
    """What a later change could move instead of the speed."""
    ks = collections.Counter(r.k_used for r in records)
    return {
        "drains": run.drains, "updates": run.updates,
        "mean_B": run.updates / max(run.drains, 1),
        "max_B": max(run.drain_sizes, default=0),
        "K_hist": dict(sorted(ks.items())),
        "max_lag": max((r.lag for r in records), default=0),
        "peak_hbm_bytes": device["memory_peak_bytes"],
        "compiles_in_window": run.compiles,
    }


def span_summary(server_s, client_spans, gc_pauses) -> dict:
    """The shape of the window's latencies, in ms: what moves a tail."""
    client = [t1 - t0 for t0, t1, _ in client_spans]
    ms = lambda xs, q: float(np.percentile(xs, q)) * 1e3 if xs else 0.0
    return {"server_p50": ms(server_s, 50), "server_p95": ms(server_s, 95),
            "server_p99": ms(server_s, 99), "server_max": ms(server_s, 100),
            "client_p50": ms(client, 50), "client_max": ms(client, 100),
            "gc_collections": len(gc_pauses),
            "gc_ms": sum(gc_pauses) * 1e3,
            "gc_max_ms": max(gc_pauses, default=0.0) * 1e3}


def end_to_end_metrics(cell, run, setup_s: float) -> dict:
    values = {
        "updates_per_s": run.updates / run.window_s,
        "agg_p95_ms": float(np.percentile(run.server_s, 95)) * 1e3,
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer_metrics(cell, run) -> dict:
    out = {}
    for m in cell.per_layer:
        v = pb_spec.reader(cell.root, m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(args, t_process: float, root: str) -> int:
    cell = pb_spec.resolve(root, args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_process, root)
    except pb_device.NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    info = out["info"]
    c = info["counters"]
    print(f"traffic drains={c['drains']} updates={c['updates']} "
          f"mean_B={c['mean_B']!r} max_B={c['max_B']} K_hist={c['K_hist']} "
          f"max_lag={c['max_lag']} peak_hbm_bytes={c['peak_hbm_bytes']} "
          f"compiles_in_window={c['compiles_in_window']}")
    print(f"setup warmed={info['warmed']} setup_s={info['setup_s']!r} "
          f"reference_s={info['reference_s']!r}")
    print("spans " + " ".join(f"{k}={v!r}" for k, v in info["spans"].items()))
    print("replay " + json.dumps({k: info[k] for k in
                                  ("replayed", "unchecked", "worst",
                                   "first")}))
    print(json.dumps(out["result"]), flush=True)
    for line in out["check_lines"]:
        print(line, file=sys.stderr)
    return 0
