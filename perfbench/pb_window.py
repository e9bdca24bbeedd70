"""Drives one ``FederatedSimulation`` through set-up and the measured window.

The program is not edited. Two of its calls are wrapped on the instance,
and are the coupling points a later change must keep:

* ``server.on_update_batch(updates)`` -- one drain of the server. The
  wrapper times it until the replies' ``params`` are ready on the device
  (the latency a client waits for its next model), opens the window after
  the warm-up drains, and ends the run when the window is over by raising
  :class:`StopWindow` before the next drain.
* ``sim._run_locals(jobs)`` -- the client fan-out, timed until its deltas
  are ready.

During warm-up the first drains are also recorded for the reference:
their arrivals, the first update's leaf norms, the clients' local losses
(from ``Client.run_local`` and ``cohort.run_cohort``, wrapped until the
capture is complete) and the model after them.
"""
from __future__ import annotations

import gc
import time
from typing import List, Optional

import jax
import numpy as np

import pb_reference

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

#: (host time, event) of every program the process compiled or loaded
_COMPILES: List[float] = []
_LISTENING = False


def listen_for_compiles() -> None:
    global _LISTENING
    if not _LISTENING:
        def on_event(event, duration, **kw):
            if event == BACKEND_COMPILE:
                _COMPILES.append(time.perf_counter())
        jax.monitoring.register_event_duration_secs_listener(on_event)
        _LISTENING = True


class StopWindow(Exception):
    """Raised from the drain wrapper once the window is over."""


class Capture:
    """What the reference replays: the first drains of the run."""

    def __init__(self, updates: int):
        self.updates = updates
        self.drain_sizes: List[int] = []
        self.first_delta_norms: Optional[np.ndarray] = None
        self.x_end = None
        self.losses: dict = {}          # client -> local losses by round
        self.done = False

    def record_loss(self, client: int, loss: float) -> None:
        self.losses.setdefault(client, []).append(float(loss))


class Window:
    def __init__(self, sim, *, seconds: float, warmup_drains: int,
                 capture_updates: int, annotate: bool = False,
                 on_open=None):
        self.sim = sim
        self.seconds = float(seconds)
        self.warmup_drains = int(warmup_drains)
        self.annotate = annotate
        self.on_open = on_open
        self.capture = Capture(capture_updates)
        self.state = "warm"
        self.drains = 0
        self.updates = 0
        self.t_start = self.t_end = None
        self.hist_start = None
        self.compiles_before = 0
        self.server_spans: List[tuple] = []     # (t0, t1, updates)
        self.client_spans: List[tuple] = []     # (t0, t1, jobs)
        self.gc_pauses: List[float] = []        # collections in the window
        self._gc_t0 = 0.0
        self._window_note = None
        server = sim.server
        self._drain = server.on_update_batch
        server.on_update_batch = self._on_update_batch
        self._fan_out = sim._run_locals
        sim._run_locals = self._run_locals
        self._wrap_losses()

    # ------------------------------------------------------------ window --
    def _note(self, name):
        return jax.profiler.TraceAnnotation(name) if self.annotate else None

    def _open(self) -> None:
        if self.on_open is not None:
            self.on_open()
        self._window_note = self._note("pb.window")
        if self._window_note is not None:
            self._window_note.__enter__()
        self.hist_start = len(self.sim.server.history)
        self.compiles_before = len(_COMPILES)
        gc.callbacks.append(self._on_gc)
        self.state = "window"
        self.t_start = time.perf_counter()

    def _close(self, now: float) -> None:
        self.t_end = now
        self.compiles_in_window = len(_COMPILES) - self.compiles_before
        gc.callbacks.remove(self._on_gc)
        if self._window_note is not None:
            self._window_note.__exit__(None, None, None)
        self.state = "done"

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_pauses.append(time.perf_counter() - self._gc_t0)

    def _on_update_batch(self, upds):
        now = time.perf_counter()
        if (self.state == "warm" and self.drains >= self.warmup_drains
                and self.capture.done):
            self._open()
        elif self.state == "window" and now - self.t_start >= self.seconds:
            self._close(now)
            raise StopWindow
        if self.state == "warm" and not self.capture.done:
            self._capture_before(upds)
        note = self._note("pb.server") if self.state == "window" else None
        t0 = time.perf_counter()
        if note is not None:
            with note:
                replies = self._drain(upds)
                jax.block_until_ready([r.params for r in replies])
        else:
            replies = self._drain(upds)
            jax.block_until_ready([r.params for r in replies])
        t1 = time.perf_counter()
        self.drains += 1
        self.updates += len(upds)
        if self.state == "window":
            self.server_spans.append((t0, t1, len(upds)))
        elif not self.capture.done:
            self._capture_after(len(upds))
        return replies

    def _run_locals(self, jobs):
        note = self._note("pb.client") if self.state == "window" else None
        t0 = time.perf_counter()
        if note is not None:
            with note:
                out = self._fan_out(jobs)
                jax.block_until_ready([u.delta for u in out])
        else:
            out = self._fan_out(jobs)
            jax.block_until_ready([u.delta for u in out])
        if self.state == "window":
            self.client_spans.append((t0, time.perf_counter(), len(jobs)))
        return out

    # ----------------------------------------------------------- capture --
    def _capture_before(self, upds) -> None:
        if self.capture.first_delta_norms is None:
            self.capture.first_delta_norms = np.asarray(
                pb_reference.leaf_norms(upds[0].delta))

    def _capture_after(self, size: int) -> None:
        cap = self.capture
        cap.drain_sizes.append(size)
        if sum(cap.drain_sizes) >= cap.updates:
            cap.x_end = pb_reference.to_host(self.sim.server.params)
            cap.done = True
            self._unwrap_losses()

    def _wrap_losses(self) -> None:
        from repro.core import cohort
        cap = self.capture
        for c in self.sim.clients:
            orig = c.run_local

            def run_local(params, k, it, prox_mu=0.0, _orig=orig, _c=c):
                upd, loss = _orig(params, k, it, prox_mu)
                cap.record_loss(_c.client_id, loss)
                return upd, loss
            c.run_local = run_local
        self._run_cohort = cohort.run_cohort

        def run_cohort(task, clients, *a, **kw):
            out = self._run_cohort(task, clients, *a, **kw)
            for c, (_, loss) in zip(clients, out):
                cap.record_loss(c.client_id, loss)
            return out
        cohort.run_cohort = run_cohort

    def _unwrap_losses(self) -> None:
        from repro.core import cohort
        for c in self.sim.clients:
            vars(c).pop("run_local", None)
        cohort.run_cohort = self._run_cohort

    def detach(self) -> None:
        """Give the program back its own calls."""
        if not self.capture.done:
            self._unwrap_losses()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        vars(self.sim.server).pop("on_update_batch", None)
        vars(self.sim).pop("_run_locals", None)

    # ------------------------------------------------------------ result --
    @property
    def window_s(self) -> float:
        return self.t_end - self.t_start

    def window_updates(self) -> int:
        return sum(b for _, _, b in self.server_spans)

    def window_records(self):
        """Server history records of the updates aggregated in the window."""
        h = self.sim.server.history
        return h[self.hist_start:self.hist_start + self.window_updates()]
