"""The trace reduction (``bench/trace.py``) on traces recorded on a TPU
v5e: a 1024^2 SO2DR plan (d=4, 8 steps, k_on=4) of box2d1r on the
``pallas_db`` kernel and of box2d4r on the ``mxu`` kernel, traced over
one solve.  The profiler wrote each trace twice, as ``.xplane.pb`` and
as Chrome-format ``.trace.json.gz``; the JSON is read here without the
reduction's code, as a second witness."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
KERNELS = r"^jit_(fused_stencil_band(_db)?|banded_fused_stencil)\("
TRACES = ["so2dr_box2d1r_1024", "so2dr_box2d4r_1024"]


def test_union_and_gaps():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert merged == [(0, 3), (5, 8), (10, 11)]
    assert trace.gaps(merged, (-1, 12)) == [(-1, 0), (3, 5), (8, 10),
                                            (11, 12)]
    assert trace.gaps([], (0, 4)) == [(0, 4)]


def _json_witness(name):
    """Window, busy union and kernel time straight from the JSON."""
    events = json.load(gzip.open(DATA / f"{name}.trace.json.gz"))["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e["name"] == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
               if e.get("ph") == "M" and e["name"] == "thread_name"}
    spans = [e for e in events if e.get("ph") == "X"]
    win = next(e for e in spans if e["name"] == "bench_window")
    w0, w1 = win["ts"], win["ts"] + win["dur"]
    mods = [e for e in spans if procs[e["pid"]] == "/device:TPU:0"
            and threads[(e["pid"], e["tid"])] == "XLA Modules"
            and w0 <= e["ts"] and e["ts"] + e["dur"] <= w1]
    busy, end = 0.0, w0
    for e in sorted(mods, key=lambda e: e["ts"]):
        s, t = max(e["ts"], end), e["ts"] + e["dur"]
        if t > s:
            busy += t - s
            end = t
    kern = [e for e in mods if e["name"].startswith(
        ("jit_fused_stencil_band", "jit_banded_fused_stencil"))]
    return {"window_s": (w1 - w0) / 1e6, "busy_s": busy / 1e6,
            "kernel_s": sum(e["dur"] for e in kern) / 1e6,
            "kernel_calls": len(kern)}


@pytest.mark.parametrize("name", TRACES)
def test_recorded_trace_matches_json_witness(name):
    s = trace.reduce_trace(str(DATA / f"{name}.xplane.pb"), "bench_window",
                           KERNELS)
    w = _json_witness(name)
    assert s.chips == 1
    assert s.kernel_calls == w["kernel_calls"] == 8   # 4 chunks x 2 calls
    for key in ("window_s", "busy_s", "kernel_s"):
        assert getattr(s, key) == pytest.approx(w[key], rel=1e-4, abs=1e-8)
    assert 0 < s.kernel_s <= s.busy_s < s.window_s


@pytest.mark.parametrize("name", TRACES)
def test_recorded_trace_breakdown(name):
    s = trace.reduce_trace(str(DATA / f"{name}.xplane.pb"), "bench_window",
                           KERNELS)
    assert 0 < len(s.top_ops) <= trace.TOP
    assert 0 < len(s.idle_gaps) <= trace.TOP
    times = [t for _, t in s.top_ops]
    assert times == sorted(times, reverse=True)
    # the fused-step program's own custom call takes most device time
    assert s.top_ops[0][0].startswith(
        ("jit_fused_stencil_band_db/", "jit_banded_fused_stencil/"))
    gaps = [t for _, t in s.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= s.window_s - s.busy_s + 1e-9
    assert all(isinstance(n, str) and n for n, _ in s.idle_gaps)


def test_kernel_pattern_that_matches_nothing_reads_zero_calls():
    s = trace.reduce_trace(str(DATA / f"{TRACES[0]}.xplane.pb"),
                           "bench_window", r"^jit_no_such_kernel\(")
    assert s.kernel_calls == 0 and s.kernel_s == 0.0
    assert s.busy_s > 0


def test_missing_window_span_is_an_error():
    with pytest.raises(ValueError, match="no host span"):
        trace.reduce_trace(str(DATA / f"{TRACES[0]}.xplane.pb"),
                           "no_such_span", KERNELS)
