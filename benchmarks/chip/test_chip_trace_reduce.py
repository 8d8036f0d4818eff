"""The trace reduction, on a trace recorded on one TPU v5e and on made-up spans.

``testdata/stencil25_3calls.xplane.pb``: three calls of the jitted stencil25
entry point on a 32x32x512 f32 field, each inside ``enqueue`` and ``wait``
annotations and followed by a 2 ms host sleep, all inside one ``window``
annotation (``jax.profiler`` with host tracer level 1, python tracer off).
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import trace_reduce as tr  # noqa: E402
from trace_reduce import Span  # noqa: E402

FIXTURE = HERE / "testdata" / "stencil25_3calls.xplane.pb"
STENCIL = json.loads((HERE / "configs" / "stencil25-r4-f32" / "config.json").read_text())


@pytest.fixture(scope="module")
def recorded():
    return tr.load(FIXTURE, {"window", "enqueue", "wait"})


def test_recorded_trace_has_one_chip_and_the_host_spans(recorded):
    assert list(recorded.device_ops) == ["/device:TPU:0"]
    names = [s.name for s in recorded.host_spans]
    assert names.count("window") == 1 and names.count("enqueue") == 3 and names.count("wait") == 3


def test_recorded_window_finds_three_kernel_calls(recorded):
    w = tr.window(recorded, "window")
    kernel = {n: c for n, c in w.op_counts.items() if tr.re.search(STENCIL["kernel_pattern"], n)}
    assert list(kernel.values()) == [3]
    assert list(kernel) == ["%stencil25.1 custom-call f32[32,32,512]"]
    k = tr.matching(w.op_seconds, STENCIL["kernel_pattern"])
    assert 0 < k < w.busy_s < w.seconds
    assert w.busy_s <= sum(w.op_seconds.values()) + 1e-12
    # the host sleeps 2 ms between calls, outside enqueue and wait: the
    # device idles there, and the gap is named for no host span
    label, seconds, at = w.idle_gaps[0]
    assert seconds >= 0.002 and 0 < at < w.seconds
    assert sum(1 for g in w.idle_gaps if g[1] >= 0.002 and g[0] == "none") >= 2
    top = tr.top_ops(w.op_seconds, 3)
    assert top[0][0] == "%stencil25.1 custom-call f32[32,32,512]" and len(top) == 3


def test_short_name_keeps_name_opcode_and_shape():
    hlo = ('%lbm_step.1 = (f32[15,256,256,256]{3,2,1,0:T(8,128)}, f32[256,256,256]{2,1,0:T(8,128)}) '
           'custom-call(f32[15,256,256,258]{3,2,1,0:T(8,128)} %pad_maximum_fusion), '
           'custom_call_target="tpu_custom_call"')
    assert tr.short_name(hlo) == "%lbm_step.1 custom-call (f32[15,256,256,256], f32[256,256,256])"
    assert tr.short_name("broadcast_multiply_fusion") == "broadcast_multiply_fusion"


def test_union_gaps_and_host_activity():
    spans = [Span("a", 0.0, 1.0), Span("b", 0.5, 2.0), Span("c", 3.0, 4.0), Span("d", 3.5, 3.6)]
    busy = tr.union(spans)
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.gaps(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert tr.clip(spans, 0.75, 3.2) == [Span("a", 0.75, 1.0), Span("b", 0.75, 2.0), Span("c", 3.0, 3.2)]
    host = [Span("enqueue", 0.0, 1.0), Span("wait", 1.0, 2.5), Span("inner", 2.0, 2.2)]
    assert tr.host_activity(host, 0.5) == "enqueue"
    assert tr.host_activity(host, 2.1) == "inner"
    assert tr.host_activity(host, 2.4) == "wait"
    assert tr.host_activity(host, 3.0) == "none"


def test_window_averages_busy_over_chips_and_clips_to_the_span():
    trace = tr.Trace(
        device_ops={"/device:TPU:0": [Span("k", 0.0, 2.0)], "/device:TPU:1": [Span("k", 1.0, 1.5)]},
        host_spans=[Span("window", 1.0, 3.0), Span("wait", 1.5, 3.0)],
    )
    w = tr.window(trace, "window")
    assert w.seconds == 2.0 and w.busy_s == pytest.approx((1.0 + 0.5) / 2)
    assert w.op_counts == {"k": 2} and w.op_seconds["k"] == pytest.approx(1.5)
    assert w.idle_gaps[0][:2] == ("wait", 1.5)
    with pytest.raises(ValueError, match="no host span"):
        tr.window(trace, "ladder")
