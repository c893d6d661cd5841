"""Profiler traces: taking them, and reducing one to the per-layer numbers.

``Tracer`` starts JAX's profiler (host spans on, Python tracer off) into a
directory of the checkout, marks the measured window with a ``bench.window``
span, and hands the ``.xplane.pb`` to ``reduce``.

``reduce`` reads the trace with ``jax.profiler.ProfileData`` alone:

* device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
  event per operation run on the device;
* busy time is the union of those events inside the window, averaged over
  the chips that ran anything; idle share is 1 - busy / window;
* Mosaic-kernel time is the summed duration of the operations that are
  Pallas kernels (``is_kernel``);
* the top device operations are summed by name; the idle gaps between
  device operations are labelled by the innermost event of a Python thread
  covering the gap's midpoint (the benchmark's own ``bench.*`` spans around
  step calls, hooks, submits and waits, and JAX's dispatch events such as
  ``PjitFunction(step)``), and the idle time is summed by label.
"""
from __future__ import annotations

import glob
import shutil
from pathlib import Path

import numpy as np

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
TOP = 10


def is_kernel(name: str, stats: dict) -> bool:
    """True for an operation that is a Pallas (Mosaic) kernel."""
    text = " ".join([name, *(str(v) for v in stats.values())]).lower()
    return "tpu_custom_call" in text or "mosaic" in text


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except (TypeError, ValueError):
        return {}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def short_name(name: str) -> str:
    """``%fused_engine.16 = f32[128,6,6,4,512]{...} custom-call(...)`` ->
    ``fused_engine.16 f32[128,6,6,4,512]``."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    return f"{lhs.lstrip('%')} {rhs.split('{', 1)[0].split(' ', 1)[0]}"


def _label_gaps(gaps: list[tuple[float, float]], host: list[tuple[float, float, str]]) -> list:
    """Each gap's label: the innermost host event of a Python thread that
    covers its midpoint (the benchmark's ``bench.*`` spans and JAX's own
    dispatch events), or ``"no host span"``."""
    if not gaps:
        return []
    mids = np.array([(a + b) / 2 for a, b in gaps])
    order = np.argsort(mids)
    smid = mids[order]
    label = np.full(len(gaps), -1)
    events = sorted((e for e in host if e[2] != WINDOW), key=lambda e: -(e[1] - e[0]))
    for i, (s, e, _) in enumerate(events):
        lo, hi = np.searchsorted(smid, s), np.searchsorted(smid, e, side="right")
        label[lo:hi] = i
    out = [None] * len(gaps)
    for k, j in zip(order, label):
        out[k] = events[j][2] if j >= 0 else "no host span"
    return out


def reduce_profile(pd) -> dict:
    """Busy and window seconds, Mosaic-kernel seconds, the top device
    operations and the idle time by what the host was doing, of one
    ``ProfileData``."""
    host_lines, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[len("/device:TPU:"):].isdigit():
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(e.start_ns, e.start_ns + e.duration_ns, e.name, _stats(e))
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_lines.append([(e.start_ns, e.start_ns + e.duration_ns, e.name)
                                   for e in line.events])
    python = [ev for line in host_lines
              if any(n.startswith(SPAN_PREFIX) or n.startswith("PjitFunction(") for _, _, n in line)
              for ev in line]
    windows = [(a, b) for a, b, n in python if n == WINDOW]
    devices = [ops for ops in devices if ops]
    if not devices:
        raise ValueError("the trace holds no device operation")
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(a for ops in devices for a, _, _, _ in ops)
        hi = max(b for ops in devices for _, b, _, _ in ops)
    busy = kernel = 0.0
    by_name: dict[str, float] = {}
    idle: dict[str, list] = {}
    used = 0
    for ops in devices:
        iv = _union(_clip([(a, b) for a, b, _, _ in ops], lo, hi))
        if not iv:
            continue
        used += 1
        busy += sum(b - a for a, b in iv)
        for a, b, name, st in ops:
            d = min(b, hi) - max(a, lo)
            if d <= 0:
                continue
            key = short_name(name)
            by_name[key] = by_name.get(key, 0.0) + d
            if is_kernel(name, st):
                kernel += d
        edges = [lo, *[x for ab in iv for x in ab], hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for (a, b), label in zip(gaps, _label_gaps(gaps, python)):
            acc = idle.setdefault(label, [0.0, 0])
            acc[0] += b - a
            acc[1] += 1
    used = max(used, 1)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    gaps_by = sorted(idle.items(), key=lambda kv: -kv[1][0])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / used * 1e-9,
        "kernel_s": kernel / used * 1e-9,
        "chips": used,
        "device_ops": [[n, t / used * 1e-9] for n, t in top],
        "idle_gaps": [[f"{label} ({n} gaps)", t / used * 1e-9] for label, (t, n) in gaps_by],
    }


def reduce_file(path) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)))


class Tracer:
    """Profiler control for one run; every call is a no-op when disabled."""

    def __init__(self, directory: Path, enabled: bool):
        self.dir, self.enabled = Path(directory), enabled
        self._window = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def window_begin(self) -> None:
        if self.enabled:
            import jax

            self._window = jax.profiler.TraceAnnotation(WINDOW)
            self._window.__enter__()

    def window_end(self) -> None:
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None

    def stop(self) -> None:
        if self.enabled:
            import jax

            jax.profiler.stop_trace()

    def reduce(self) -> dict:
        paths = glob.glob(str(self.dir / "**" / "*.xplane.pb"), recursive=True)
        if not paths:
            raise FileNotFoundError(f"no profiler trace under {self.dir}")
        try:
            return reduce_file(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
