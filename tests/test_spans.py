"""The executor's spans (:class:`repro.core.lower.SpanRecorder`): each
bound op and each phase of the fused step, of the barrier, of a
streamed write-back and of the domain's banded copy is one profiler
host event carrying ``run``, ``round`` and its site, flat on its thread,
and its host seconds and count land in ``ExecStats`` under the same
name.

The solves are traced with JAX's own profiler on the CPU and read back
from the ``.xplane.pb`` it writes, as the benchmark reads a chip's."""
import glob
import importlib
import json
from collections import Counter

import jax
import numpy as np
import pytest

from repro import compile_plan, get_stencil
from repro.core.executor import DoubleBufferedExecutor
from repro.core.lower import _streamed_d2h, lower
from repro.kernels.dispatch import DispatchPolicy
from repro.serve.scheduler import ScheduledJob, run_interleaved

from _subproc import run_fake_device_subprocess

BARRIER = ("HostCommit.drain", "D2H.pull", "D2H.scatter")
# the domain's copy goes in bands of this many bytes: a few rows each
BAND_BYTES = 1024


@pytest.fixture(autouse=True)
def stream_small_boxes(monkeypatch):
    """The solves here are small: boxes of any size may stream, and the
    domain is copied in several bands, so the write-back threads' and
    the copy thread's spans are made."""
    lower_mod = importlib.import_module("repro.core.lower")
    monkeypatch.setattr(lower_mod, "STREAM_MIN_BYTES", 0)
    monkeypatch.setattr(lower_mod, "HOST_COPY_BAND_BYTES", BAND_BYTES)


def _bands(plan):
    """The bands of the domain's copy for a solve of ``plan``."""
    row_bytes = plan.itemsize * int(np.prod(plan.shape[1:]))
    rows = max(1, BAND_BYTES // row_bytes)
    return -(-plan.shape[0] // rows)


def _solve(Y=62, X=40, n=8, d=3, k_off=4, k_on=2, seed=0):
    """An SO2DR solve of 2 rounds over 3 chunks whose bands pad up to a
    shape bucket."""
    plan = compile_plan("so2dr", get_stencil("box2d1r"), Y, X, n, d, k_off,
                        k_on)
    x = np.random.default_rng(seed).random((Y, X), dtype=np.float32)
    return plan, x


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; return the program's spans in
    start order as ``(name, start_ns, end_ns, stats)``: the host events
    that carry a ``run`` stat."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    assert len(paths) == 1, paths
    return _program_spans(ProfileData.from_file(paths[0]).planes)


def _program_spans(planes):
    """The spans as ``(name, start_ns, end_ns, stats)``; ``stats`` also
    holds the index of the thread's line under ``"line"``."""
    spans = []
    for p in planes:
        if p.name != "/host:CPU":
            continue
        for i, line in enumerate(p.lines):
            for e in line.events:
                stats = dict(e.stats)
                if "run" in stats:
                    stats["line"] = i
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, stats))
    return sorted(spans, key=lambda s: s[1])


def _assert_flat(spans):
    """On each thread, no program span's interval contains (or
    overlaps) another's."""
    for line in {s["line"] for *_, s in spans}:
        mine = [sp for sp in spans if sp[3]["line"] == line]
        for a, b in zip(mine, mine[1:]):
            assert a[2] <= b[1], f"{a[0]} {a[3]} overlaps {b[0]} {b[3]}"


def _expected(plan, site="chunk"):
    """The leaf spans a solve of ``plan`` makes, by ``(name, round,
    site)``, pad and crop and ``HostCopy.wait`` aside: a streamed D2H box
    waits for the device in a ``D2H.wait`` of its own, a held one in the
    barrier's drain; the domain is copied in ``HostCopy.band`` spans, at
    no site."""
    streamed = _streamed_d2h(plan.ops)
    exp = Counter({("Execute.validate", -1, -1): 1,
                   ("HostCopy.band", -1, -1): _bands(plan)})
    for op in plan.ops:
        name = type(op).__name__
        if name == "HostCommit":
            exp["HostCommit.drain", op.round, -1] += 1
            continue
        at = (op.round, getattr(op, site))
        span = "FusedKernel.call" if name == "FusedKernel" else name
        exp[(span,) + at] += 1
        if name == "D2H":
            exp[("D2H.pull",) + at] += 1
            exp[("D2H.scatter",) + at] += 1
            if id(op) in streamed:
                exp[("D2H.wait",) + at] += 1
    return exp


def _on_issuing_thread(spans):
    """The spans on the thread that ran the plan's ops (the one with the
    domain's copy)."""
    line = next(s["line"] for n, _, _, s in spans if n == "Execute.validate")
    return [sp for sp in spans if sp[3]["line"] == line]


def _found(spans, site="chunk"):
    return Counter((n, s["round"], s[site]) for n, _, _, s in spans
                   if n not in ("FusedKernel.pad", "FusedKernel.crop",
                                "HostCopy.wait"))


def _check_copy_waits(spans, plan):
    """A ``HostCopy.wait`` is made only where a write or a barrier waited
    for the copy: at most once at a D2H box's site or at a barrier's."""
    sites = Counter((op.round, op.chunk) for op in plan.ops
                    if type(op).__name__ == "D2H")
    sites.update((op.round, -1) for op in plan.ops
                 if type(op).__name__ == "HostCommit")
    waits = Counter((s["round"], s["chunk"]) for n, _, _, s in spans
                    if n == "HostCopy.wait")
    assert not waits - sites, (waits, sites)


def _check_padding(spans):
    """Pad and crop come in pairs, each at the site of a fused call."""
    calls = {(s["round"], s["chunk"]) for n, _, _, s in spans
             if n == "FusedKernel.call"}
    pads = Counter((s["round"], s["chunk"]) for n, _, _, s in spans
                   if n == "FusedKernel.pad")
    crops = Counter((s["round"], s["chunk"]) for n, _, _, s in spans
                    if n == "FusedKernel.crop")
    assert pads and pads == crops
    assert set(pads) <= calls


def _check_stats(es, plan):
    counts = plan.op_counts()
    n_streamed = len(_streamed_d2h(plan.ops))
    assert set(es.op_wall_s) == set(es.op_counts)
    assert es.op_counts["FusedKernel.call"] == counts["FusedKernel"]
    assert es.kernel_calls == counts["FusedKernel"]
    assert es.op_counts["D2H.pull"] == es.op_counts["D2H.scatter"] \
        == counts["D2H"]
    assert es.op_counts.get("D2H.wait", 0) == n_streamed
    assert es.op_counts["HostCommit.drain"] == counts["HostCommit"]
    assert es.op_counts["Execute.validate"] == 1
    assert es.op_counts["HostCopy.band"] == _bands(plan)
    assert es.op_counts.get("HostCopy.wait", 0) \
        <= counts["D2H"] + counts["HostCommit"]
    if not n_streamed:
        # every box is written back in the barrier, on the issuing thread
        assert sum(es.op_wall_s[k] for k in BARRIER) \
            <= es.op_wall_s["HostCommit"]


def test_double_buffered_solve_spans(tmp_path):
    plan, x = _solve()
    counts = plan.op_counts()
    assert counts["HostCommit"] >= 2 and counts["H2D"] >= 3
    ex = DoubleBufferedExecutor(policy=DispatchPolicy())
    want, _ = ex.execute(plan, x)      # compiles outside the trace
    spans = _traced(tmp_path, lambda: ex.execute(plan, x))
    _assert_flat(spans)
    assert len({s["run"] for _, _, _, s in spans}) == 1
    assert _found(spans) == _expected(plan)
    _check_copy_waits(spans, plan)
    _check_padding(spans)
    # the op classes with phases are counted, but make no span of their own
    traced = ex.exec_stats
    assert traced.op_counts == dict(
        Counter(n for n, _, _, _ in spans)
        + Counter(FusedKernel=counts["FusedKernel"],
                  HostCommit=counts["HostCommit"]))
    # the streamed write-backs run off the issuing thread, which keeps
    # only the held boxes' pulls
    streamed = len(_streamed_d2h(plan.ops))
    assert streamed == 2 * counts["HostCommit"]
    issuing = Counter(n for n, *_ in _on_issuing_thread(spans))
    assert "D2H.wait" not in issuing and "HostCopy.band" not in issuing
    assert issuing["D2H.pull"] == counts["D2H"] - streamed
    assert issuing["Execute.validate"] == 1

    got, _ = ex.execute(plan, x)       # no profiler running
    np.testing.assert_array_equal(got, want)
    _check_stats(ex.exec_stats, plan)
    # how often a write waits for the copy is a matter of timing
    untraced = dict(ex.exec_stats.op_counts)
    untraced.pop("HostCopy.wait", None)
    assert untraced == {k: v for k, v in traced.op_counts.items()
                        if k != "HostCopy.wait"}
    assert ex.exec_stats.op_wall_s["HostCommit"] <= ex.exec_stats.wall_s


def test_held_boxes_write_back_inside_the_barrier(tmp_path):
    """``naive_tb`` re-reads each box's rows for the next chunk, so every
    box is held for the barrier, which pulls and scatters them on the
    issuing thread, inside its own seconds; only the domain's copy runs
    on another thread."""
    plan = compile_plan("naive_tb", get_stencil("box2d1r"), 62, 40, 8, 3,
                        4, 2)
    x = np.random.default_rng(4).random((62, 40), dtype=np.float32)
    assert not _streamed_d2h(plan.ops)
    ex = DoubleBufferedExecutor(policy=DispatchPolicy())
    ex.execute(plan, x)
    spans = _traced(tmp_path, lambda: ex.execute(plan, x))
    _assert_flat(spans)
    assert _on_issuing_thread(spans) == [
        sp for sp in spans if sp[0] != "HostCopy.band"]
    assert _found(spans) == _expected(plan)
    _check_copy_waits(spans, plan)
    _check_stats(ex.exec_stats, plan)


def test_interleaved_jobs_spans(tmp_path):
    plans = [_solve(), _solve(Y=66, X=48, seed=1)]
    compiled = [lower(p) for p, _ in plans]
    jobs = [ScheduledJob(job_id=10 + i, compiled=c, x=x, predicted_s=0.0)
            for i, (c, (_, x)) in enumerate(zip(compiled, plans))]
    run_interleaved(jobs)              # compiles outside the trace
    spans = _traced(tmp_path, lambda: run_interleaved(jobs))
    _assert_flat(spans)
    runs = set()
    for job, (plan, _) in zip(jobs, plans):
        mine = [s for s in spans if s[3]["job"] == job.job_id]
        assert len({s["run"] for _, _, _, s in mine}) == 1
        runs |= {s["run"] for _, _, _, s in mine}
        assert _found(mine) == _expected(plan)
        _check_copy_waits(mine, plan)
        _check_padding(mine)
    assert len(runs) == 2
    assert {s["job"] for _, _, _, s in spans} == {10, 11}

    for (job, host, stats, _, fault), (plan, _) in zip(run_interleaved(jobs),
                                                        plans):
        assert fault is None and host is not None
        _check_stats(stats, plan)


SHARDED = """
import json
import jax
import numpy as np
from repro.core.executor import ShardedSimExecutor
from repro.core.shard import compile_sharded

assert len(jax.devices()) == 4
plan = compile_sharded("box2d1r", 48, 48, 4, 2, (2, 2))
x = np.random.default_rng(0).random((48, 48), dtype=np.float32)
ex = ShardedSimExecutor()
ex.execute(plan, x)
untraced = ex.exec_stats
jax.profiler.start_trace({out!r})
ex.execute(plan, x)
jax.profiler.stop_trace()
ops = [(type(op).__name__, op.round, op.rank)
       for _, phase in plan.phases() for op in phase]
with open({out!r} + "/result.json", "w") as f:
    json.dump({{"op_counts": untraced.op_counts,
               "op_wall_s": untraced.op_wall_s,
               "kernel_calls": untraced.kernel_calls, "ops": ops}}, f)
print("SHARDED_OK")
"""


def test_sharded_sim_spans(tmp_path):
    from jax.profiler import ProfileData

    out = str(tmp_path)
    run_fake_device_subprocess(SHARDED.format(out=out), "SHARDED_OK",
                               n_devices=4)
    res = json.load(open(f"{out}/result.json"))
    paths = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    spans = _program_spans(ProfileData.from_file(paths[0]).planes)
    _assert_flat(spans)
    assert len({s["run"] for _, _, _, s in spans}) == 1
    exp = Counter({("Execute.validate", -1, -1): 1})
    for name, rnd, rank in res["ops"]:
        exp[name, rnd, rank] += 1
        if name == "ShardStore":
            exp["D2H.pull", rnd, rank] += 1
            exp["D2H.scatter", rnd, rank] += 1
    drain = [s for s in spans if s[0] == "HostCommit.drain"]
    assert len(drain) == 1      # one barrier, at the end of the plan
    assert _found([s for s in spans if s[0] != "HostCommit.drain"],
                  "rank") == exp

    counts, wall = res["op_counts"], res["op_wall_s"]
    assert set(wall) == set(counts)
    assert counts == dict(Counter(n for n, _, _, _ in spans))
    stores = sum(1 for name, _, _ in res["ops"] if name == "ShardStore")
    assert counts["D2H.pull"] == counts["D2H.scatter"] == stores == 4
    assert res["kernel_calls"] == counts["ShardKernel"] > 0


def test_recorder_meta_and_counts():
    """Each recorder is a run of its own; a span counts under its name."""
    from repro.core.lower import SpanRecorder

    a, b = SpanRecorder(), SpanRecorder(job=3)
    assert a.meta["run"] != b.meta["run"]
    assert b.meta == {"run": b.meta["run"], "round": -1, "chunk": -1,
                      "job": 3}
    with a.span("D2H.pull"):
        pass
    a.at(2, 5)
    assert a.meta["round"] == 2 and a.meta["chunk"] == 5
    assert dict(a.count) == {"D2H.pull": 1} and not b.count
    assert a.wall["D2H.pull"] >= 0.0


def test_codec_barrier_decodes_in_its_own_span(tmp_path):
    from repro.core.executor import EagerExecutor

    plan = compile_plan("so2dr", get_stencil("box2d1r"), 62, 40, 8, 3, 4, 2,
                        codec="zrle")
    x = np.random.default_rng(2).random((62, 40), dtype=np.float32)
    ex = EagerExecutor(policy=DispatchPolicy())
    ex.execute(plan, x)
    spans = _traced(tmp_path, lambda: ex.execute(plan, x))
    _assert_flat(spans)
    n = Counter(name for name, _, _, _ in spans)
    assert n["D2H.decode"] == n["D2H.pull"] == plan.op_counts()["D2H"]
    assert n["Compress"] == plan.op_counts()["Compress"]


def test_hierarchical_shard_kernel_leaves_spans_to_its_nested_plan(tmp_path):
    """A hierarchical ShardKernel runs a nested plan; it is timed but
    makes no span, so the nested plan's spans stay leaves."""
    from repro.core.executor import ShardedSimExecutor
    from repro.core.hierarchy import compile_hierarchical

    plan = compile_hierarchical("star2d1r", 48, 48, 8, 2, (2, 2),
                                inner_engine="so2dr", inner_d=3)
    x = np.random.default_rng(3).random((48, 48), dtype=np.float32)
    ex = ShardedSimExecutor()
    ex.execute(plan, x)
    spans = _traced(tmp_path, lambda: ex.execute(plan, x))
    _assert_flat(spans)
    names = Counter(n for n, _, _, _ in spans)
    es = ex.exec_stats
    assert "ShardKernel" not in names and es.op_counts["ShardKernel"] > 0
    # each ShardKernel ran one nested plan: a run of its own, in chunks
    inner = {s["run"] for n, _, _, s in spans if "chunk" in s}
    assert len(inner) == es.op_counts["ShardKernel"]
    assert names["FusedKernel.call"] > 0
