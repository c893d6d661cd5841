"""The readers of the program's own spans and compile counter
(``bench/metrics/_program.py``): on the snapshots of CPU rehearsals of a
training and a serving cell, each run inside a profiler session as a traced
run is, and on an empty snapshot or a program without ``repro.obs``, which
read as nothing."""
import sys

import jax
import pytest

from bench import run as RUN
from bench.drivers import train as TRAIN_DRIVER
from bench.tests.rehearse import CpuContext
import repro
from repro import obs

SEED = 2**31 + 303
SPANS = {
    "data_ms.train": ("train", "gan.train.data"),
    "sync_ms.train": ("train", "gan.train.sync"),
    "assemble_ms.offline": ("serve", "gan.serve.assemble"),
    "generate_ms.offline": ("serve", "gan.serve.generate"),
}
COMPILES = {"compiles.train": ("train", "gan.train."), "compiles.offline": ("serve", "gan.serve.")}
READERS = [*SPANS, *COMPILES]


@pytest.fixture(scope="module")
def snapshots(tmp_path_factory):
    """``obs.snapshot()`` after the program's part of a training rehearsal and
    after a whole serving rehearsal, each inside a profiler session."""
    runs = {
        "train": (CpuContext("dcgan.train.b128", SEED, 0.5, params={"batch": 4}),
                  TRAIN_DRIVER.run_program),
        "serve": (CpuContext("artgan.serve.offline", SEED, 1.0),
                  RUN.load_module("drivers", "serve").run),
    }
    out = {}
    for kind, (ctx, run) in runs.items():
        obs.reset()
        with jax.profiler.trace(str(tmp_path_factory.mktemp(kind))):
            run(ctx)
        out[kind] = obs.snapshot()
    obs.reset()
    return out


def _read(name: str):
    return RUN.load_module("metrics", name).read({})


@pytest.mark.parametrize("name", list(SPANS))
def test_span_readers_on_a_rehearsed_snapshot(snapshots, monkeypatch, name):
    kind, span = SPANS[name]
    monkeypatch.setattr(obs, "snapshot", lambda: snapshots[kind])
    got = _read(name)
    rec = snapshots[kind]["spans"][span]
    assert got["count"] == rec["count"] >= 1
    assert got["total_s"] == rec["total_s"] > 0
    assert got["value"] == pytest.approx(1e3 * rec["total_s"] / rec["count"])


@pytest.mark.parametrize("name", list(COMPILES))
def test_compile_readers_on_a_rehearsed_snapshot(snapshots, monkeypatch, name):
    kind, prefix = COMPILES[name]
    monkeypatch.setattr(obs, "snapshot", lambda: snapshots[kind])
    got = _read(name)
    by = snapshots[kind]["compiles"]
    assert got["value"] == got["count"] == sum(c["count"] for c in by.values())
    assert got["by_span"] == {k: c["count"] for k, c in by.items()}
    assert got["spans"] and all(k.startswith(prefix) for k in got["spans"])
    if kind == "train":
        # one data, step and sync span per step of the loop
        counts = {k: c for k, (c, _) in got["spans"].items()}
        assert set(counts) == {"gan.train.data", "gan.train.step", "gan.train.sync"}
        assert len(set(counts.values())) == 1
    else:
        assert {"gan.serve.assemble", "gan.serve.generate", "gan.serve.complete",
                "gan.serve.poll"} <= set(got["spans"])


def test_the_train_cells_line_carries_the_program_metrics(snapshots, monkeypatch):
    monkeypatch.setattr(obs, "snapshot", lambda: snapshots["train"])
    ctx = CpuContext("dcgan.train.b128", SEED, 0.5, trace=True)
    bench, *_ = RUN.load_cell(ctx.name)
    out = {"correct": True, "attempted": 1, "failed": 0, "checks": {}, "memory_peak_bytes": None,
           "window_s": 1.0, "work": {"model_flops": 1.0, "engine_passes": []},
           "trace": {"busy_s": 0.9, "window_s": 1.0, "kernel_s": 0.0, "chips": 1,
                     "device_ops": [], "idle_gaps": []}}
    line = RUN.assemble(bench, ctx, out, {"platform": "cpu", "kind": "cpu", "count": 1},
                        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    m = line["metrics"]
    assert {"data_ms.train", "sync_ms.train", "compiles.train"} <= set(m)
    assert m["data_ms.train"]["unit"] == "ms" and m["compiles.train"]["unit"] == "programs"


@pytest.mark.parametrize("name", READERS)
def test_readers_on_an_empty_snapshot_read_nothing(monkeypatch, name):
    monkeypatch.setattr(obs, "snapshot", lambda: {"spans": {}, "compiles": {}})
    assert _read(name) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_of_a_program_without_obs_read_nothing(monkeypatch, name):
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # the import fails
    assert _read(name) is None
