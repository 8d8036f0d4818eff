"""The readers of the program's own spans and counters: ``dispatch_us`` and
``idle_dispatch`` on made-up traces with hand-computed answers and on the
recorded trace (which holds no program span), and ``pick_ms`` on the
program's metrics registry."""
from __future__ import annotations

import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import program_spans  # noqa: E402
import trace_reduce as tr  # noqa: E402
from trace_reduce import Span  # noqa: E402

FIXTURE = HERE / "testdata" / "stencil25_3calls.xplane.pb"
METRICS = HERE / "metrics"


def reader(name: str):
    return harness.load_module(METRICS / f"{name}.py", f"metric_{name}")


def fake_run(seconds: float | None, cell: str = "stencil25.ensemble"):
    trace = None if seconds is None else SimpleNamespace(seconds=seconds)
    return SimpleNamespace(cell=SimpleNamespace(name=cell), trace=trace)


def synthetic() -> tr.Trace:
    """A 10 s window with device ops leaving idle gaps (1.5, 2.5), (4.5, 6)
    and (9.5, 10); three calls inside it, one after it, and other host spans."""
    return tr.Trace(
        device_ops={"/device:TPU:0": [Span("k", -1.0, 1.5), Span("k", 2.5, 4.5), Span("pad", 6.0, 9.5),
                                      Span("k", 10.5, 12.0)]},
        host_spans=[Span("window", 0.0, 10.0), Span("stencil25.call", 1.0, 2.25),
                    Span("enqueue", 3.0, 6.0), Span("stencil25.call", 4.0, 5.5),
                    Span("stencil25.call", 7.0, 7.5), Span("stencil25.call", 10.2, 10.4)],
    )


@pytest.fixture
def loaded(monkeypatch):
    """Serve ``trace`` to the readers as the run's window trace, counting loads."""
    loads = []

    def serve(trace):
        def load(run):
            loads.append(run)
            return trace
        monkeypatch.setattr(program_spans, "load", load)
        monkeypatch.setattr(program_spans, "_last", (None, None))
        return loads
    return serve


def test_dispatch_and_idle_attribution_by_hand(loaded):
    loads = loaded(synthetic())
    run = fake_run(10.0)
    # calls of 1.25, 1.5 and 0.5 s inside the window; the 0.2 s call after it is left out
    assert reader("dispatch_us").read(run) == pytest.approx((1.25 + 1.5 + 0.5) / 3 * 1e6)
    # idle 1 + 1.5 + 0.5 = 3 s; inside calls (1.5, 2.25) and (4.5, 5.5): 0.75 + 1 s
    assert reader("idle_dispatch").read(run) == pytest.approx(1.75 / 3.0 * 100.0)
    assert len(loads) == 1  # one load per run, both readers


def test_idle_attribution_sums_over_chips(loaded):
    trace = synthetic()
    trace.device_ops["/device:TPU:1"] = [Span("k", 0.0, 10.0)]  # never idle
    trace.device_ops["/device:TPU:2"] = [Span("k", 0.0, 4.0), Span("k", 5.0, 10.0)]  # idle 4-5
    loaded(trace)
    # idle 3 + 0 + 1 s, inside calls 1.75 + 0 + 1 s
    assert reader("idle_dispatch").read(fake_run(10.0)) == pytest.approx(2.75 / 4.0 * 100.0)


def test_overlap_of_interval_lists():
    assert program_spans.overlap([(0.0, 1.0), (2.0, 5.0)], [(0.5, 2.5), (3.0, 4.0), (4.5, 9.0)]) == 2.5
    assert program_spans.overlap([], [(0.0, 1.0)]) == 0.0


@pytest.mark.parametrize("name", ["dispatch_us", "idle_dispatch"])
def test_no_program_spans_read_none(loaded, name):
    trace = synthetic()
    loaded(trace)
    # an untraced run, and a trace of another run (its window lasts otherwise)
    assert reader(name).read(fake_run(None)) is None
    assert reader(name).read(fake_run(9.0)) is None
    # a window with no call inside it, as from a program that opens no span
    trace.host_spans = [s for s in trace.host_spans if not s.name.endswith(".call")]
    loaded(trace)
    assert reader(name).read(fake_run(10.0)) is None


@pytest.mark.parametrize("name", ["dispatch_us", "idle_dispatch"])
def test_recorded_trace_without_program_spans_reads_none(tmp_path, monkeypatch, name):
    """The trace recorded on a v5e from the program before it had spans: the
    readers find its window and its device ops, and no call."""
    profile = tmp_path / "stencil25.ensemble" / "window" / "plugins" / "profile" / "recorded"
    profile.mkdir(parents=True)
    shutil.copy(FIXTURE, profile / FIXTURE.name)
    monkeypatch.setattr(program_spans, "TRACES", tmp_path)
    monkeypatch.setattr(program_spans, "_last", (None, None))
    seconds = tr.window(tr.load(FIXTURE, {"window"}), "window").seconds
    trace = program_spans.load(fake_run(seconds))
    assert trace.device_ops and [s.name for s in trace.host_spans] == ["window"]
    assert reader(name).read(fake_run(seconds)) is None
    assert reader(name).read(fake_run(seconds, cell="stencil25.bulk")) is None  # no trace there


def test_pick_ms_reads_the_entry_picks(monkeypatch):
    from repro.obs import metrics

    monkeypatch.setattr(metrics, "_REGISTRY", metrics.MetricsRegistry())
    pick_ms = reader("pick_ms")
    assert pick_ms.read(fake_run(None)) is None  # no pick observed
    metrics.histogram("estimator.pick_seconds.other").observe(9.0)  # another series
    assert pick_ms.read(fake_run(None)) is None
    metrics.histogram("estimator.pick_seconds", entry="stencil25").observe(0.010)
    metrics.histogram("estimator.pick_seconds", entry="stencil25").observe(0.030)
    metrics.histogram("estimator.pick_seconds", entry="lbm_step").observe(0.050)
    assert pick_ms.read(fake_run(10.0)) == pytest.approx(30.0)
