"""Serving engines.

``ServeEngine`` — continuous-batching LM engine (slot-based, vLLM-style
scheduling adapted to fixed-shape JAX: a fixed pool of B slots over a shared
max_len cache; arrivals fill free slots via per-slot prefill-into-cache,
finished sequences free their slot).

``GanServeEngine`` — batched image-generation service over the Winograd
DeConv generator.  Weights are prepacked into the Winograd domain ONCE at
construction (kernels.ops.prepack), so a serving call runs only the fused
engine: no G-transform or weight pack ever executes on the request path.

Fixed shapes keep everything jit-cacheable: one prefill_one signature, one
decode signature, one generate signature per serving bucket — reused
forever, no recompilation as traffic varies.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
import warnings
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import GANConfig, LMConfig
from repro.models import lm as LM
from repro.serve.faults import (
    CircuitBreaker,
    FaultPlan,
    GanServeError,
    InjectedFault,
)


@dataclasses.dataclass
class Request:
    rid: int
    tokens: list[int]  # prompt
    max_new: int = 16
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    """Greedy-decoding engine with B slots and a shared ring of caches.

    The cache is allocated once at (B, max_len); per-slot prefill writes a
    single slot's rows via dynamic_update_slice on the batch dim, so admitting
    a request never reshapes or re-jits anything.
    """

    def __init__(self, params, cfg: LMConfig, *, slots: int = 4, max_len: int = 256,
                 prompt_len: int = 32):
        self.params, self.cfg = params, cfg
        self.B, self.max_len, self.prompt_len = slots, max_len, prompt_len
        self.cache = LM.init_cache(cfg, slots, max_len, jnp.float32)
        self.pos = [0] * slots  # tokens in each slot's cache
        self.active: list[Optional[Request]] = [None] * slots
        self.last_tok = jnp.zeros((slots, 1), jnp.int32)

        cfg_pad = cfg

        @jax.jit
        def prefill_one(params, tokens):  # tokens (1, prompt_len)
            return LM.prefill(params, cfg_pad, {"tokens": tokens}, q_chunk=64,
                              max_len=max_len)

        @jax.jit
        def decode(params, cache, toks, lens):
            # per-slot cache_len: decode each slot at its own position.
            # Our decode_step takes a scalar cache_len; serve with per-slot
            # positions via vmap over the batch dim.
            def one(cache_b, tok_b, len_b):
                # cache_b leaves are (n_super, ...); reinsert batch at axis 1
                c1 = jax.tree.map(lambda x: x[:, None], cache_b)
                lg, c2 = LM.decode_step(params, cfg_pad, c1, tok_b[None], len_b)
                return jax.tree.map(lambda x: x[:, 0], c2), lg[0]

            # move the slot axis to the front of every cache leaf (it is
            # axis 1: leaves are (n_super, B, ...))
            cache_sw = jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), cache)
            new_sw, lg = jax.vmap(one, in_axes=(0, 0, 0))(cache_sw, toks, lens)
            new_cache = jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), new_sw)
            return lg, new_cache

        self._prefill_one = prefill_one
        self._decode = decode

    # ------------------------------------------------------------- admission
    def try_admit(self, req: Request) -> bool:
        for s in range(self.B):
            if self.active[s] is None:
                toks = (req.tokens + [0] * self.prompt_len)[: self.prompt_len]
                logits, cache1 = self._prefill_one(
                    self.params, jnp.asarray([toks], jnp.int32)
                )
                # copy slot s rows from the fresh single-row cache
                def put(big, small):
                    return jax.lax.dynamic_update_slice_in_dim(big, small, s, axis=1)

                self.cache = jax.tree.map(put, self.cache, cache1)
                self.pos[s] = min(len(req.tokens), self.prompt_len)
                self.active[s] = req
                first = int(jnp.argmax(logits[0]))
                req.out.append(first)  # the prefill-step prediction
                self.last_tok = self.last_tok.at[s, 0].set(first)
                return True
        return False

    # ----------------------------------------------------------------- step
    def step(self) -> list[Request]:
        """One decode step for every active slot; returns finished requests."""
        if not any(a is not None for a in self.active):
            return []
        lens = jnp.asarray([self.pos[s] for s in range(self.B)], jnp.int32)
        logits, self.cache = self._decode(self.params, self.cache, self.last_tok, lens)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        finished = []
        for s, req in enumerate(self.active):
            if req is None:
                continue
            tok = int(nxt[s])
            req.out.append(tok)
            self.pos[s] += 1
            self.last_tok = self.last_tok.at[s, 0].set(tok)
            if len(req.out) >= req.max_new or self.pos[s] >= self.max_len - 1:
                req.done = True
                finished.append(req)
                self.active[s] = None
                self.pos[s] = 0
        return finished

    def run(self, requests: list[Request]) -> list[Request]:
        """Drive a workload to completion (simple arrival loop)."""
        pending = list(requests)
        done: list[Request] = []
        while pending or any(a is not None for a in self.active):
            while pending and self.try_admit(pending[0]):
                pending.pop(0)
            done.extend(self.step())
        return done


# ------------------------------------------------------------------- GAN
class GanServeRejected(RuntimeError):
    """The request was refused admission — bounded inbound queue full, or
    the target arch is quarantined by its circuit breaker.  The message
    carries the reason."""


def _now_ms(now: Optional[float] = None) -> float:
    return time.monotonic() * 1e3 if now is None else now


@dataclasses.dataclass
class GanRequest:
    """One image-generation request: a batch of latents (or images for
    image-to-image models) that must be served together.  Carries the
    resident arch it targets plus the four SLO stamps (ms, monotonic
    clock) that ``serve.metrics`` turns into queue-wait / batch-wait /
    compute / end-to-end components."""

    rid: int
    z: jax.Array
    arch: Optional[str] = None
    deadline_ms: Optional[float] = None
    out: Optional[jax.Array] = None
    done: bool = False
    rejected: bool = False
    failed: bool = False
    error: Optional[BaseException] = None
    reject_reason: Optional[str] = None
    attempts: int = 0
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_dispatch: Optional[float] = None
    t_done: Optional[float] = None
    event: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False, compare=False
    )

    @property
    def size(self) -> int:
        return int(self.z.shape[0])

    @property
    def resolved(self) -> bool:
        """Every request ends in exactly one of three states: served
        (``done``), rejected, or failed — the serve stack's no-hang
        invariant is that this eventually becomes True for every submit."""
        return self.done or self.rejected or self.failed

    @property
    def timing(self) -> Optional[dict]:
        """SLO components (ms) once served; None while in flight."""
        from repro.serve import metrics as M

        return M.request_timing(self)


class GanFuture:
    """Handle for a submitted request: poll with ``done()``, block with
    ``result(timeout=)``.

    With an async driver attached (``serve.loop.AsyncGanServer``) the
    server's generate loop fulfills the future and ``result`` just waits on
    its completion event; without one, ``result`` drives the engine itself —
    admitting pending requests and serving batching windows as they close —
    so synchronous callers never hand-roll an admit/poll/step loop."""

    def __init__(self, request: "GanRequest", engine: "GanServeEngine"):
        self.request = request
        self._engine = engine

    def done(self) -> bool:
        return self.request.resolved

    def _wait_on_driver(self, timeout: Optional[float]) -> None:
        """Wait for the async server to fulfil the request — but observe
        driver death instead of stranding: if the server detaches mid-wait
        we fall back to self-driving, and if its generate/admission loop
        has died with no restart coming (watchdog off or exhausted) the
        wait fails with ``GanServeError`` rather than hanging forever
        (including ``result(timeout=None)``)."""
        req = self.request
        t_end = None if timeout is None else time.monotonic() + timeout
        while not req.resolved:
            wait = 0.05
            if t_end is not None:
                wait = min(wait, max(0.0, t_end - time.monotonic()))
            if req.event.wait(wait):
                return
            if t_end is not None and time.monotonic() >= t_end:
                raise TimeoutError(
                    f"request {req.rid} not served within {timeout}s"
                )
            drv = self._engine._driver
            if drv is None:
                # server stopped/detached while we waited: drive ourselves
                remaining = None if t_end is None else \
                    max(0.0, t_end - time.monotonic())
                self._engine._drive_until(req, remaining)
                return
            if not drv.healthy():
                req.failed = True
                req.error = GanServeError(
                    f"request {req.rid}: serving loop died and will not "
                    "restart", arch=req.arch, kind="loop_dead",
                )
                req.event.set()
                return

    def result(self, timeout: Optional[float] = None) -> jax.Array:
        req = self.request
        if not self.done():
            if self._engine is not None and self._engine._driver is not None:
                self._wait_on_driver(timeout)
            else:
                self._engine._drive_until(req, timeout)
        if req.rejected:
            raise GanServeRejected(
                req.reject_reason
                or f"request {req.rid} rejected (inbound queue full)"
            )
        if req.failed:
            raise req.error if req.error is not None else GanServeError(
                f"request {req.rid} failed", arch=req.arch
            )
        return req.out

    def exception(self) -> Optional[BaseException]:
        """The carried failure (``GanServeError``) or rejection, or None
        while in flight / on success — without raising."""
        req = self.request
        if req.failed:
            return req.error
        if req.rejected:
            return GanServeRejected(
                req.reject_reason
                or f"request {req.rid} rejected (inbound queue full)"
            )
        return None


class _Resident:
    """One arch resident in the engine process: its serve config (the
    prepacked / chained impl substituted), the packed (C, N, M) weights —
    G-transform paid once here — and the jit'd generate whose cache holds
    one executable per serving bucket, reused forever."""

    def __init__(self, arch: str, gen_params, cfg: GANConfig, *,
                 chained: bool, mesh):
        from repro.models import gan as G

        impl = G.serve_impl(cfg.deconv_impl, chained=chained)
        self.arch = arch
        self.cfg = dataclasses.replace(cfg, deconv_impl=impl)
        if G.uses_prepacked(impl):
            self.params = G.prepack_generator(gen_params, cfg, mesh=mesh)
        elif mesh is not None:
            from repro.parallel import sharding as SH

            gsp, _, _ = SH.gan_param_specs(self.cfg, mesh)
            self.params = jax.device_put(gen_params, SH.named(mesh, gsp))
        else:
            self.params = gen_params
        cfg_packed = self.cfg

        @jax.jit
        def _generate(params, z):
            img, _ = G.generator_apply(params, cfg_packed, z, training=False)
            return img

        self._generate = _generate
        self.bucket_counts: dict[int, int] = {}
        self.served = 0
        # failure-isolation state (tentpole): final-outcome breaker plus
        # attempt-level counters the metrics summarize per arch
        self.breaker = CircuitBreaker()
        self.failures = 0   # dispatches that ultimately failed (post-retry)
        self.retries = 0    # extra generate attempts spent on recovery
        self.nan_trips = 0  # NaN-guard detections (poisoned batches)

    def health_ok(self) -> bool:
        """Resident health hook (``models.gan.params_finite``): a resident
        whose packed weights have gone non-finite can never produce a good
        batch, so the half-open probe refuses to re-admit it."""
        from repro.models import gan as G

        return G.params_finite(self.params)


class GanServeEngine:
    """Multi-tenant image-generation service over prepacked Winograd-domain
    weights.

    **Residency.** Each served arch pays the G-transform + zero-skipping
    pack exactly once at construction (``models.gan.prepack_generator``)
    and stays resident: packed (C, N, M) weights plus a per-bucket jit
    cache per arch.  Pass a single model the legacy way —
    ``GanServeEngine(params, cfg)`` — or several at once:
    ``GanServeEngine(models={"dcgan": (params, cfg), "artgan": (...)})``
    (values may also be ``models.gan.PrepackedGenerator`` registry entries,
    or plain arch-id strings resolved from
    ``models.gan.get_prepacked_generator``).  For the pallas impls each
    resident runs its generator as ONE cell-to-cell chained pipeline
    (``chained=False`` opts back into per-layer).

    **Scheduling.** One shared request queue feeds one shared pool of
    ``batch`` slot rows; admission is strict FIFO (a request that doesn't
    fit the free rows blocks the queue head — order fairness over packing).
    A dispatch serves every admitted request, grouped into per-arch
    bucketed batches: requests are padded up to the smallest of the fixed
    ``buckets`` ladder (default powers of two up to ``batch``), so a
    size-1 request runs the batch-1 executable while the jit signature
    count stays bounded.

    **Batching windows.** ``deadline_ms`` admits into a bounded window:
    the request tolerates up to that much coalescing delay, and the batch
    dispatches when the EARLIEST admitted deadline expires, the pool
    fills, or a no-deadline (immediate) request joins — a mixed batch
    honors its most impatient member.

    **Drive surface.** ``submit(z, arch=..., deadline_ms=...)`` returns a
    ``GanFuture``; ``.result()`` drives the engine synchronously, or waits
    on the async server's generate loop when one is attached
    (``serve.loop.AsyncGanServer``).  The pre-futures three-method surface
    (``try_admit`` / ``poll`` / ``step``) survives as thin deprecated
    wrappers over the same admission/dispatch core.

    Params may arrive raw, already packed, or packed-and-sharded (straight
    out of a mesh training run — already-``ww`` leaves pass through
    ``prepack_generator`` untouched); ``mesh`` re-places them per
    ``parallel.sharding.gan_param_specs`` at construction.
    """

    def __init__(self, gen_params=None, cfg: Optional[GANConfig] = None, *,
                 models=None, batch: int = 8,
                 buckets: Optional[tuple[int, ...]] = None, mesh=None,
                 chained: bool = True, max_retries: int = 2,
                 backoff_ms: float = 2.0, backoff_cap_ms: float = 50.0,
                 breaker_threshold: int = 3, breaker_cooldown_ms: float = 250.0,
                 nan_guard: bool = False,
                 fault_plan: Optional[FaultPlan] = None):
        from repro.models import gan as G

        if models is None:
            if gen_params is None or cfg is None:
                raise ValueError(
                    "pass (gen_params, cfg) or models={arch: (params, cfg)}"
                )
            models = {cfg.arch_id or "default": (gen_params, cfg)}
        elif gen_params is not None or cfg is not None:
            raise ValueError("pass (gen_params, cfg) OR models=, not both")

        if buckets is None:
            buckets, b = [], 1
            while b < batch:
                buckets.append(b)
                b *= 2
        # batch is always a bucket: explicit bucket lists refine the padding
        # ladder but never shrink the maximum serveable request
        self.buckets = tuple(sorted({int(b) for b in buckets} | {int(batch)}))
        self.batch = self.buckets[-1]

        self.archs: dict[str, _Resident] = {}
        for arch, spec in models.items():
            if isinstance(spec, str):
                spec = G.get_prepacked_generator(spec)
            if isinstance(spec, G.PrepackedGenerator):
                res = _Resident(arch, spec.params, spec.cfg,
                                chained=chained, mesh=mesh)
            else:
                p, c = spec
                res = _Resident(arch, p, c, chained=chained, mesh=mesh)
            self.archs[arch] = res
        self.default_arch = next(iter(self.archs))

        # legacy single-model aliases (cfg/params/bucket_counts of the
        # default resident; bucket_counts is the SAME dict object)
        default = self.archs[self.default_arch]
        self.cfg = default.cfg
        self.params = default.params
        self.bucket_counts = default.bucket_counts

        self.served = 0
        self._lock = threading.RLock()
        self._pending: deque = deque()  # submitted, awaiting free rows
        self.active: list[GanRequest] = []  # admitted, not yet dispatched
        self.rows_used = 0
        # earliest absolute deadline (ms) among admitted requests; None while
        # any admitted request wants immediate service (the FIFO default)
        self._window_deadline: Optional[float] = None
        self._immediate = False
        self._rid = itertools.count()
        self._driver = None  # serve.loop.AsyncGanServer attaches here
        # per-dispatch admission order (rids), for equivalence tests/debug
        self.dispatch_log: list[tuple[int, ...]] = []

        # ------------------------------------------- failure semantics
        # retry budget: a failed per-arch generate is retried with capped
        # exponential backoff, never past a request's absolute deadline
        # (t_submit + deadline_ms); exhausted budgets carry GanServeError
        # into the futures.  Each resident gets its own circuit breaker.
        self.max_retries = int(max_retries)
        self.backoff_ms = float(backoff_ms)
        self.backoff_cap_ms = float(backoff_cap_ms)
        self.nan_guard = bool(nan_guard)
        self.fault_plan = fault_plan
        for res in self.archs.values():
            res.breaker = CircuitBreaker(
                threshold=breaker_threshold, cooldown_ms=breaker_cooldown_ms
            )
        # requests snapshotted out of ``active`` by an in-progress dispatch:
        # the watchdog fails these (instead of stranding them) if the
        # generate thread dies mid-dispatch
        self._inflight: list[GanRequest] = []

    # ------------------------------------------------------------- routing
    def _resolve_arch(self, arch: Optional[str]) -> str:
        if arch is None:
            if len(self.archs) == 1:
                return self.default_arch
            raise ValueError(
                "arch= is required on a multi-model engine "
                f"(resident: {sorted(self.archs)})"
            )
        if arch not in self.archs:
            raise KeyError(
                f"arch {arch!r} not resident (resident: {sorted(self.archs)})"
            )
        return arch

    def bucket_for(self, b: int) -> int:
        """Smallest serving bucket that fits a size-``b`` request."""
        for k in self.buckets:
            if k >= b:
                return k
        raise ValueError(f"request batch {b} > engine max bucket {self.buckets[-1]}")

    def generate(self, z: jax.Array, arch: Optional[str] = None) -> jax.Array:
        """z: (b, z_dim) latents (or (b, H, W, 3) images for image-to-image
        models), b <= max bucket.  Returns the b generated images from the
        named resident (or the only one)."""
        res = self.archs[self._resolve_arch(arch)]
        b = z.shape[0]
        k = self.bucket_for(b)
        res.bucket_counts[k] = res.bucket_counts.get(k, 0) + 1
        z_pad = jnp.pad(z, ((0, k - b),) + ((0, 0),) * (z.ndim - 1))
        imgs = res._generate(res.params, z_pad)
        res.served += b
        self.served += b
        return imgs[:b]

    # ------------------------------------------------------- admission core
    def _admit(self, req: GanRequest, *, deadline_ms: Optional[float] = None,
               now: Optional[float] = None) -> bool:
        """FIFO admission into the shared row pool; False when the free rows
        can't fit the request (a request larger than the whole pool is a
        caller error).  ``deadline_ms`` opens/joins the batching window;
        ``now`` (ms) overrides the wall clock for tests and simulators."""
        if req.size > self.batch:
            raise ValueError(
                f"request batch {req.size} > engine max bucket {self.batch}"
            )
        if self.rows_used + req.size > self.batch:
            return False
        req.arch = self._resolve_arch(req.arch)
        t = _now_ms(now)
        if req.t_submit is None:
            req.t_submit = t
        req.t_admit = t
        if deadline_ms is None:
            deadline_ms = req.deadline_ms
        self.active.append(req)
        self.rows_used += req.size
        if deadline_ms is None:
            self._immediate = True
        else:
            self._window_deadline = (
                t + deadline_ms if self._window_deadline is None
                else min(self._window_deadline, t + deadline_ms)
            )
        return True

    def _admit_pending(self, now: Optional[float] = None) -> int:
        """Move submitted requests into the row pool, strict FIFO: stop at
        the first one that doesn't fit (it blocks the queue head)."""
        n = 0
        while self._pending:
            req = self._pending[0]
            if self.rows_used + req.size > self.batch:
                break
            self._pending.popleft()
            self._admit(req, now=now)
            n += 1
        return n

    def window_open(self, now: Optional[float] = None) -> bool:
        """True while the batching window is still collecting: some rows are
        admitted, none demanded immediate service, the pool has free rows,
        and the earliest deadline has not expired."""
        if not self.active or self._immediate or self.rows_used >= self.batch:
            return False
        if self._window_deadline is None:
            return False  # nothing admitted a deadline: serve right away
        return _now_ms(now) < self._window_deadline

    # -------------------------------------------------------- dispatch core
    def _dispatch(self, now: Optional[float] = None) -> list[GanRequest]:
        """Serve every admitted request: snapshot the batch and free the
        rows under the lock (admission can refill the pool while the
        accelerator works), then run ONE bucketed generate per resident
        arch aboard, split the rows back per request, stamp the SLO times
        and fire the completion events.  Returns the finished requests in
        admission order.

        Failure isolation: each arch's generate runs behind its own
        try/except + retry loop (``_serve_arch``) — a failing arch marks
        only ITS requests with a carried ``GanServeError`` while the other
        archs in the same dispatch complete normally.  No exception ever
        escapes a dispatch to kill the driving thread."""
        with self._lock:
            if not self.active:
                return []
            batch_reqs = [r for r in self.active if not r.resolved]
            self.active, self.rows_used = [], 0
            self._window_deadline, self._immediate = None, False
            if not batch_reqs:
                return []
            self.dispatch_log.append(tuple(r.rid for r in batch_reqs))
            dispatch_idx = len(self.dispatch_log) - 1
            self._inflight = batch_reqs
        t_disp = _now_ms(now)
        for r in batch_reqs:
            r.t_dispatch = t_disp
        by_arch: dict[str, list[GanRequest]] = {}
        for r in batch_reqs:
            by_arch.setdefault(r.arch, []).append(r)
        for arch, reqs in by_arch.items():
            self._serve_arch(arch, reqs, dispatch_idx, now)
        with self._lock:
            self._inflight = []
        return batch_reqs

    def _fail_requests(self, reqs: list[GanRequest], err: BaseException,
                       now: Optional[float] = None) -> None:
        """Carry ``err`` into the requests' futures: mark failed, stamp
        t_done, fire the events — a failure resolves, it never strands."""
        t = _now_ms(now)
        for r in reqs:
            r.error = err
            r.failed = True
            r.t_done = t
            r.event.set()

    def _serve_arch(self, arch: str, reqs: list[GanRequest],
                    dispatch_idx: int, now: Optional[float] = None) -> None:
        """One resident's share of a dispatch, under the full failure
        contract: fault injection (``FaultPlan``), optional NaN/Inf output
        guard, capped exponential-backoff retries that never run past a
        request's absolute deadline (t_submit + deadline_ms), and circuit-
        breaker accounting on the final outcome.  Total isolation: no
        exception escapes to the caller.  In a profiler session the
        assembly, generate and completion of each attempt are
        ``gan.serve.*`` spans (``repro.obs``) tagged with the dispatch
        index, its rows and its bucket."""
        res = self.archs[arch]
        pending = list(reqs)
        attempt = 0
        while True:
            plan = self.fault_plan
            for r in pending:
                r.attempts += 1
            b = sum(r.size for r in pending)
            k = self.bucket_for(b)
            tags = dict(dispatch=dispatch_idx, rows=b, bucket=k)
            try:
                fault = None if plan is None else plan.draw(
                    arch=arch, rids=tuple(r.rid for r in pending),
                    dispatch_idx=dispatch_idx, attempt=attempt,
                )
                if fault == "delay":
                    time.sleep(plan.delay_ms / 1e3)
                elif fault == "raise":
                    raise InjectedFault(
                        f"injected fault (arch={arch}, "
                        f"dispatch={dispatch_idx}, attempt={attempt})"
                    )
                with obs.span("gan.serve.assemble", **tags):
                    z_all = jnp.concatenate([r.z for r in pending], axis=0)
                    z_pad = jnp.pad(
                        z_all, ((0, k - b),) + ((0, 0),) * (z_all.ndim - 1)
                    )
                with obs.span("gan.serve.generate", **tags):
                    imgs = res._generate(res.params, z_pad)
                    jax.block_until_ready(imgs)  # honest compute stamp
                    if fault == "nan":
                        imgs = jnp.full_like(imgs, jnp.nan)
                    if self.nan_guard and not bool(jnp.all(jnp.isfinite(imgs))):
                        res.nan_trips += 1
                        raise GanServeError(
                            f"arch {arch}: non-finite values in generated batch",
                            arch=arch, kind="nan", attempts=attempt + 1,
                        )
            except Exception as e:  # isolation boundary — nothing escapes
                retry_ok = attempt < self.max_retries
                backoff_ms = min(
                    self.backoff_ms * (2 ** attempt), self.backoff_cap_ms
                )
                t = _now_ms(now)
                survivors, dropped = [], []
                for r in pending:
                    dl = None if r.deadline_ms is None else \
                        (r.t_submit or t) + r.deadline_ms
                    if retry_ok and (dl is None or t + backoff_ms <= dl):
                        survivors.append(r)
                    else:
                        dropped.append(r)
                kind = getattr(e, "kind", "exception")
                if dropped:
                    self._fail_requests(dropped, GanServeError(
                        f"arch {arch}: dispatch failed after "
                        f"{attempt + 1} attempt(s): {e}",
                        arch=arch, kind=(kind if not retry_ok else "deadline"),
                        attempts=attempt + 1, cause=e,
                    ), now)
                if not survivors:
                    res.failures += 1
                    res.breaker.on_failure(now)
                    return
                res.retries += 1
                attempt += 1
                pending = survivors
                if now is None:
                    time.sleep(backoff_ms / 1e3)
                continue
            # success: resident health gates half-open re-admission — a
            # probe through poisoned weights must not close the breaker
            if res.breaker.state == "half_open" and not res.health_ok():
                res.failures += 1
                res.breaker.on_failure(now)
                self._fail_requests(pending, GanServeError(
                    f"arch {arch}: resident weights are non-finite",
                    arch=arch, kind="weights", attempts=attempt + 1,
                ), now)
                return
            res.bucket_counts[k] = res.bucket_counts.get(k, 0) + 1
            res.served += b
            self.served += b
            with obs.span("gan.serve.complete", **tags):
                t_done = _now_ms(now)
                row = 0
                for r in pending:
                    r.out = imgs[row : row + r.size]
                    row += r.size
                    r.t_done = t_done
                    r.done = True
                    r.event.set()
            res.breaker.on_success()
            return

    # ------------------------------------------------------------- health
    def health(self) -> dict:
        """Per-arch serve health: circuit-breaker state + failure/retry
        counters — the rows ``serve.metrics.summarize(counters=...)``
        merges into its per-arch table."""
        return {
            arch: {
                **res.breaker.counters(),
                "failures": res.failures,
                "retries": res.retries,
                "nan_trips": res.nan_trips,
                "served": res.served,
            }
            for arch, res in self.archs.items()
        }

    # -------------------------------------------------------- futures API
    def submit(self, z: jax.Array, *, arch: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               now: Optional[float] = None) -> GanFuture:
        """Submit a request and get a ``GanFuture`` back.

        The request joins the shared FIFO queue and claims slot rows as
        soon as they're free; generation happens when its batching window
        closes — driven by ``GanFuture.result()`` for synchronous callers,
        or by the ``AsyncGanServer`` generate loop when one is attached.
        ``deadline_ms`` bounds the coalescing delay this request tolerates
        (omit it to demand immediate service at the next dispatch).

        A quarantined arch (circuit breaker open after K consecutive
        dispatch failures) fast-rejects with a reasoned
        ``GanServeRejected`` instead of queueing work that would fail."""
        arch_r = self._resolve_arch(arch)
        if int(z.shape[0]) > self.batch:
            raise ValueError(
                f"request batch {int(z.shape[0])} > engine max bucket {self.batch}"
            )
        ok, reason = self.archs[arch_r].breaker.allow_submit(now)
        if not ok:
            raise GanServeRejected(f"arch {arch_r!r}: {reason}")
        req = GanRequest(
            rid=next(self._rid), z=z, arch=arch_r, deadline_ms=deadline_ms,
            t_submit=_now_ms(now),
        )
        with self._lock:
            self._pending.append(req)
            self._admit_pending(now)
        return GanFuture(req, self)

    def _drive_until(self, req: GanRequest, timeout: Optional[float] = None):
        """Synchronous drive loop behind ``GanFuture.result()``: admit
        pending requests and dispatch batches as their windows close, until
        ``req`` completes (sleeping out still-open deadline windows)."""
        t_end = None if timeout is None else time.monotonic() + timeout
        while not req.resolved:
            with self._lock:
                self._admit_pending()
                open_ = self.window_open()
                ready = bool(self.active) and not open_
                window_wait_s = (
                    max(0.0, self._window_deadline / 1e3 - time.monotonic())
                    if open_ and self._window_deadline is not None else None
                )
            if ready:
                self._dispatch()
                continue
            if req.resolved:
                break
            if t_end is not None and time.monotonic() >= t_end:
                raise TimeoutError(
                    f"request {req.rid} not served within {timeout}s"
                )
            # window still open (sleep it out) or another thread owns the
            # batch: yield briefly, bounded so timeouts stay responsive
            wait = 0.0005 if window_wait_s is None else window_wait_s
            if t_end is not None:
                wait = min(wait, max(0.0, t_end - time.monotonic()))
            time.sleep(min(wait, 0.05))

    # --------------------------------------------------- deprecated surface
    def try_admit(self, req: GanRequest, *, deadline_ms: Optional[float] = None,
                  now: Optional[float] = None) -> bool:
        """Deprecated: use ``submit`` (futures API).  Thin wrapper over the
        admission core — claim ``req.size`` free slot rows for the next
        dispatch's shared batch; False when the pool can't fit the request.

        ``deadline_ms`` admits into a bounded batching window: the request
        tolerates up to that much coalescing delay, and ``poll`` serves the
        shared batch when the EARLIEST admitted deadline expires (or the
        pool fills) rather than unconditionally.  Without it the request
        demands immediate service and the next ``poll`` fires regardless —
        a mixed batch honors its most impatient member.  ``now`` (ms)
        overrides the wall clock, for tests and simulated drivers."""
        warnings.warn(
            "GanServeEngine.try_admit is deprecated; use submit(z, arch=..., "
            "deadline_ms=...) -> GanFuture", DeprecationWarning, stacklevel=2,
        )
        with self._lock:
            return self._admit(req, deadline_ms=deadline_ms, now=now)

    def poll(self, now: Optional[float] = None) -> list[GanRequest]:
        """Deprecated: use ``submit(...).result()``.  Serve the admitted
        batch iff its window has closed (deadline expired, pool full, or an
        immediate-service request is aboard); [] while the window is open."""
        warnings.warn(
            "GanServeEngine.poll is deprecated; GanFuture.result() (or "
            "serve.loop.AsyncGanServer) drives the engine",
            DeprecationWarning, stacklevel=2,
        )
        with self._lock:
            if not self.active or self.window_open(now):
                return []
        return self._dispatch(now)

    def step(self) -> list[GanRequest]:
        """Deprecated: use ``submit(...).result()``.  Serve every admitted
        request unconditionally (one bucketed generate per resident arch
        aboard) and free all slots; returns the finished requests."""
        warnings.warn(
            "GanServeEngine.step is deprecated; GanFuture.result() (or "
            "serve.loop.AsyncGanServer) drives the engine",
            DeprecationWarning, stacklevel=2,
        )
        return self._dispatch()

    def run(self, requests: list[jax.Array], *,
            arch: Optional[str] = None) -> list[jax.Array]:
        """Serve a queue of variable-size latent batches through the FIFO
        scheduler; outputs come back in request order.  (Futures under the
        hood: same admission order and bucket counts as the pre-futures
        admit/step loop.)"""
        futs = [self.submit(z, arch=arch) for z in requests]
        return [f.result() for f in futs]
