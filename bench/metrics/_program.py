"""What the program recorded about its own layers in a traced run: the
spans and compile counts of ``repro.obs``, which record only while the
profiler session (the traced window) is on.  The readers run after the
driver, in its process.  A program without ``repro.obs``, or a run in which
no span of the layer was recorded, reads as nothing (``None``)."""
from __future__ import annotations

from bench import system


def snapshot() -> dict | None:
    """``repro.obs.snapshot()``, or None where the program has no ``obs``."""
    system.import_program()
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot()


def span_ms(name: str):
    """Mean duration of span ``name``, ms, with its count and total seconds."""
    span = ((snapshot() or {}).get("spans") or {}).get(name)
    if not span or not span["count"]:
        return None
    return {"value": 1e3 * span["total_s"] / span["count"], "count": span["count"],
            "total_s": span["total_s"]}


def compiles(prefix: str):
    """Programs compiled while the spans recorded, by the innermost span each
    compiled under (``by_span``), with the count and total seconds of each
    span of the layer (names starting with ``prefix``) for the coverage of
    the window."""
    snap = snapshot()
    spans = {k: v for k, v in (snap or {}).get("spans", {}).items() if k.startswith(prefix)}
    if not spans:
        return None
    by = snap["compiles"]
    n = sum(c["count"] for c in by.values())
    return {"value": n, "count": n, "total_s": sum(c["total_s"] for c in by.values()),
            "by_span": {k: c["count"] for k, c in by.items()},
            "spans": {k: [v["count"], v["total_s"]] for k, v in spans.items()}}
