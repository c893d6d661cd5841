"""Async serving loop: admission thread + generate loop over one engine,
supervised by a watchdog.

``AsyncGanServer`` turns the synchronous ``GanServeEngine`` core into an
open-loop service.  ``submit`` is non-blocking: it enqueues the request
into the engine's shared FIFO (or rejects it outright — bounded in-flight
queue full, or the target arch quarantined by its circuit breaker;
backpressure surfaces to the caller as a reasoned ``GanServeRejected``
from ``GanFuture.result()``, never as silent unbounded queue growth).
Three daemon threads drive the engine:

  admission  moves pending requests into free slot rows (strict FIFO),
             refilling the pool while the accelerator works — admission
             overlaps generation because ``_dispatch`` frees the rows
             under the lock *before* running the per-arch generates
  generate   dispatches the shared batch whenever its batching window
             closes (earliest deadline expired, pool full, or an
             immediate-service request aboard)
  watchdog   supervises the other two: a dead loop thread (an exception
             escaped the engine's isolation boundary — a bug, not a
             request failure) FAILS the affected in-flight futures with
             ``GanServeError`` (never strands them) and restarts the
             loop, up to ``max_restarts`` times; past the budget the
             server marks itself failed and resolves everything queued

Completion is event-based: the generate loop stamps the SLO times and
fires each request's event; ``GanFuture.result()`` waits, checking
``healthy()`` so a dead, unrestartable server raises instead of hanging.
While a server is attached (``engine._driver``), futures never self-drive
the engine, so there is exactly one dispatch path.  ``health()`` exposes
thread liveness, restart counts and the engine's per-arch breaker state.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

import jax

from repro import obs
from repro.serve.engine import GanFuture, GanRequest, GanServeEngine, _now_ms
from repro.serve.faults import GanServeError


class AsyncGanServer:
    """Threaded driver for a ``GanServeEngine``.

    ``max_queue`` bounds the in-flight population (pending + admitted);
    submissions beyond it are rejected immediately.  ``poll_interval_ms``
    is the idle sleep of both loops — the latency floor for an empty
    engine, kept small (default 1 ms) since both loops do O(queue) work
    per wake.  ``watchdog`` (default on) supervises the loop threads and
    restarts a dead one up to ``max_restarts`` times, failing — not
    stranding — the futures whose dispatch state died with it.  Use as a
    context manager, or ``start()`` / ``stop()``.
    """

    def __init__(self, engine: GanServeEngine, *, max_queue: int = 64,
                 poll_interval_ms: float = 1.0, watchdog: bool = True,
                 watchdog_interval_ms: float = 20.0, max_restarts: int = 3):
        self.engine = engine
        self.max_queue = int(max_queue)
        self.poll_interval_s = poll_interval_ms / 1e3
        self.watchdog_enabled = bool(watchdog)
        self.watchdog_interval_s = watchdog_interval_ms / 1e3
        self.max_restarts = int(max_restarts)
        self.rejected_count = 0
        self.restart_count = 0
        self.wedged: list[str] = []
        self._failed = False
        self._stop = threading.Event()
        self._draining = True
        self._workers: dict[str, threading.Thread] = {}
        self._watchdog_thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------ lifecycle
    def _spawn_worker(self, name: str) -> None:
        target = {"admission": self._admission_loop,
                  "generate": self._generate_loop}[name]
        t = threading.Thread(target=target, name=f"gan-serve-{name}",
                             daemon=True)
        self._workers[name] = t
        t.start()

    def start(self) -> "AsyncGanServer":
        if self._workers:
            raise RuntimeError("server already started")
        self.engine._driver = self
        self._stop.clear()
        self._failed = False
        for name in ("admission", "generate"):
            self._spawn_worker(name)
        if self.watchdog_enabled:
            self._watchdog_thread = threading.Thread(
                target=self._watchdog_loop, name="gan-serve-watchdog",
                daemon=True,
            )
            self._watchdog_thread.start()
        return self

    def stop(self, *, drain: bool = True, timeout: float = 60.0) -> None:
        """Stop the loops.  ``drain=True`` serves everything already
        submitted first; ``drain=False`` rejects all in-flight requests
        (their futures raise ``GanServeRejected``) so no caller hangs.

        A loop thread that does not exit within ``timeout`` (wedged — e.g.
        stuck inside a hung generate) is NOT papered over: the in-flight
        futures are failed with ``GanServeError`` so no caller hangs, the
        thread names land in ``self.wedged``, and ``RuntimeError`` is
        raised — a shutdown that leaves live threads behind must never
        read as clean."""
        self._draining = drain
        self._stop.set()
        for t in self._workers.values():
            t.join(timeout)
        if self._watchdog_thread is not None:
            self._watchdog_thread.join(timeout)
            self._watchdog_thread = None
        wedged = [n for n, t in self._workers.items() if t.is_alive()]
        self._workers = {}
        eng = self.engine
        if wedged:
            self.wedged = wedged
            self._failed = True
            with eng._lock:
                leftovers = (
                    list(eng._inflight) + list(eng.active) + list(eng._pending)
                )
                eng._inflight = []
                eng._pending.clear()
                eng.active, eng.rows_used = [], 0
                eng._window_deadline, eng._immediate = None, False
            stranded = [r for r in leftovers if not r.resolved]
            eng._fail_requests(stranded, GanServeError(
                f"server stopped with wedged thread(s) {wedged}; "
                "request state unknown", kind="stop_wedged",
            ))
            eng._driver = None
            raise RuntimeError(
                f"AsyncGanServer.stop(): thread(s) {wedged} still alive "
                f"after {timeout}s join; {len(stranded)} in-flight "
                "future(s) failed instead of stranded"
            )
        if not drain:
            with eng._lock:
                leftovers = list(eng._pending) + list(eng.active)
                eng._pending.clear()
                eng.active, eng.rows_used = [], 0
                eng._window_deadline, eng._immediate = None, False
            dropped = [r for r in leftovers if not r.resolved]
            for req in dropped:
                req.rejected = True
                req.reject_reason = "server stopped without drain"
                req.event.set()
            self.rejected_count += len(dropped)
        self.engine._driver = None

    def __enter__(self) -> "AsyncGanServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop(drain=True)

    # ------------------------------------------------------------- frontend
    def submit(self, z: jax.Array, *, arch: Optional[str] = None,
               deadline_ms: Optional[float] = None) -> GanFuture:
        """Non-blocking submit.  Oversized requests raise ValueError (a
        caller error); a full in-flight queue — or a quarantined target
        arch — rejects the request: the returned future is already done
        and ``result()`` raises a reasoned ``GanServeRejected``."""
        eng = self.engine
        arch_r = eng._resolve_arch(arch)
        if int(z.shape[0]) > eng.batch:
            raise ValueError(
                f"request batch {int(z.shape[0])} > engine max bucket {eng.batch}"
            )
        req = GanRequest(rid=next(eng._rid), z=z, arch=arch_r,
                         deadline_ms=deadline_ms, t_submit=_now_ms())
        ok, reason = eng.archs[arch_r].breaker.allow_submit()
        if not ok:
            req.rejected = True
            req.reject_reason = f"arch {arch_r!r}: {reason}"
        elif self._failed:
            req.rejected = True
            req.reject_reason = "server failed (restart budget exhausted)"
        else:
            with eng._lock:
                if len(eng._pending) + len(eng.active) >= self.max_queue:
                    req.rejected = True
                    req.reject_reason = (
                        f"inbound queue full (max_queue={self.max_queue})"
                    )
                else:
                    eng._pending.append(req)
        if req.rejected:
            self.rejected_count += 1
            req.event.set()
        return GanFuture(req, eng)

    # --------------------------------------------------------------- health
    def healthy(self) -> bool:
        """True while submitted work can still complete: the loop threads
        are alive, or a live watchdog will restart any that died.  False
        means futures waiting on this server must fail, not hang."""
        if self._failed:
            return False
        wd = self._watchdog_thread
        if wd is not None and wd.is_alive():
            return True  # dead workers get restarted
        return all(t.is_alive() for t in self._workers.values())

    def health(self) -> dict:
        """Supervision + engine state in one report: thread liveness,
        restart/wedge accounting, and the engine's per-arch circuit-breaker
        counters."""
        return {
            "threads": {n: t.is_alive() for n, t in self._workers.items()},
            "restarts": self.restart_count,
            "wedged": list(self.wedged),
            "failed": self._failed,
            "rejected": self.rejected_count,
            "archs": self.engine.health(),
        }

    # ---------------------------------------------------------------- loops
    def _idle(self) -> bool:
        eng = self.engine
        with eng._lock:
            return not eng._pending and not eng.active

    def _admission_loop(self) -> None:
        eng = self.engine
        while True:
            with eng._lock:
                eng._admit_pending()
            if self._stop.is_set() and (not self._draining or self._idle()):
                return
            time.sleep(self.poll_interval_s)

    def _generate_loop(self) -> None:
        eng = self.engine
        while True:
            drain_now = self._stop.is_set() and self._draining
            with eng._lock:
                ready = bool(eng.active) and (
                    drain_now or not eng.window_open()
                )
            if ready:
                eng._dispatch()
                continue
            if self._stop.is_set() and (not self._draining or self._idle()):
                return
            with obs.span("gan.serve.poll"):
                time.sleep(self.poll_interval_s)

    # ------------------------------------------------------------- watchdog
    def _on_worker_death(self, name: str) -> None:
        """A loop thread died (an exception escaped the engine's isolation
        boundary).  Fail — never strand — every request whose dispatch
        state died with it (mid-dispatch snapshot + admitted batch), then
        restart the loop; past ``max_restarts`` the server marks itself
        failed and resolves the pending queue too."""
        eng = self.engine
        self.restart_count += 1
        exhausted = self.restart_count > self.max_restarts
        with eng._lock:
            affected = list(eng._inflight) + list(eng.active)
            eng._inflight = []
            eng.active, eng.rows_used = [], 0
            eng._window_deadline, eng._immediate = None, False
            dead_pending = []
            if exhausted:
                dead_pending = list(eng._pending)
                eng._pending.clear()
        eng._fail_requests(
            [r for r in affected if not r.resolved],
            GanServeError(
                f"serve {name} loop died; in-flight request state discarded",
                kind="loop_dead",
            ),
        )
        if exhausted:
            eng._fail_requests(
                [r for r in dead_pending if not r.resolved],
                GanServeError(
                    f"serve {name} loop died and the restart budget "
                    f"({self.max_restarts}) is exhausted", kind="loop_dead",
                ),
            )
            self._failed = True
            return
        self._spawn_worker(name)

    def _watchdog_loop(self) -> None:
        while not self._stop.wait(self.watchdog_interval_s):
            for name in ("admission", "generate"):
                t = self._workers.get(name)
                if t is None or t.is_alive() or self._stop.is_set():
                    continue
                self._on_worker_death(name)
                if self._failed:
                    return
