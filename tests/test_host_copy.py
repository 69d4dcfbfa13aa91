"""The domain's copy into a run's host array
(:class:`repro.core.lower._HostCopy`): a domain of more than one band is
copied in row bands on a thread of its own, under the device's work,
while reads before the first barrier go to the caller's array.

* A solve with the banded copy is bitwise the solve with the copy made
  inline, up front, on every engine, with and without the prefetch, and
  with a transfer codec; the caller's array is left as it was.
* A write-back waits for the copy to pass its rows, so a slow copy
  never lands on a box written back before it; ``on_commit`` sees each
  round complete.
* No copy thread outlives a run, on the normal, fault and service paths.
"""
import importlib
import sys
import threading
import time

import numpy as np
import pytest

from repro import compile_plan, get_stencil
from repro.core.faults import KERNEL_FAULT, FaultPlan, FaultTrigger
from repro.core.lower import SlotPool, lower
from repro.core.oocore import ENGINES, compile_box_plan
from repro.core.recovery import PlanExecutionError
from repro.kernels.dispatch import DispatchPolicy
from repro.serve import ScheduledJob, StencilJob, StencilService, \
    run_interleaved

lower_mod = importlib.import_module("repro.core.lower")
ROWS = 4          # rows of a band in the banded solves
INLINE = 1 << 40  # a band size that copies every test domain inline


@pytest.fixture(autouse=True)
def stream_small_boxes(monkeypatch):
    """Boxes of any size may stream, so write-backs race the copy."""
    monkeypatch.setattr(lower_mod, "STREAM_MIN_BYTES", 0)


def _plan(engine, codec=None):
    st = get_stencil("box2d1r")
    if engine == "box_tb":
        return compile_box_plan(st, (40, 40), 8, (2, 2), 4, 2, codec=codec)
    return compile_plan(engine, st, 62, 40, 8, 3, 4, 2, codec=codec)


def _domain(plan, seed=0):
    return np.random.default_rng(seed).random(plan.shape, dtype=np.float32)


def _bands(monkeypatch, plan, rows=ROWS):
    """Copy in bands of ``rows`` rows; return the number of bands."""
    row_bytes = plan.itemsize * int(np.prod(plan.shape[1:]))
    monkeypatch.setattr(lower_mod, "HOST_COPY_BAND_BYTES", rows * row_bytes)
    return -(-plan.shape[0] // rows)


def _inline(monkeypatch):
    monkeypatch.setattr(lower_mod, "HOST_COPY_BAND_BYTES", INLINE)


def _slow_copy(monkeypatch, seconds=0.02):
    """Each band of the copy takes at least ``seconds``."""
    copy = lower_mod._HostCopy._copy

    def slow(self, lo, hi):
        time.sleep(seconds)
        copy(self, lo, hi)

    monkeypatch.setattr(lower_mod._HostCopy, "_copy", slow)


def _copy_threads():
    return [t for t in threading.enumerate() if t.name == "hostcopy"]


@pytest.mark.parametrize("pipeline", [False, True])
@pytest.mark.parametrize("engine,codec", [(e, None) for e in sorted(ENGINES)]
                         + [("so2dr", "zrle"), ("resreu", "bf16")])
def test_banded_copy_solve_is_bitwise_the_inline_one(engine, codec, pipeline,
                                                     monkeypatch):
    plan = _plan(engine, codec)
    x = _domain(plan)
    compiled = lower(plan)
    _inline(monkeypatch)
    want, _, inline = compiled.execute(x, pipeline=pipeline)
    assert "HostCopy.band" not in inline.op_counts
    bands = _bands(monkeypatch, plan)
    got, _, es = compiled.execute(x, pipeline=pipeline)
    assert es.op_counts["HostCopy.band"] == bands
    assert es.op_counts["Execute.validate"] == 1
    np.testing.assert_array_equal(got, want)
    assert not _copy_threads()


def test_callers_array_is_unchanged(monkeypatch):
    plan = _plan("so2dr")
    x = _domain(plan, 1)
    before = x.copy()
    _bands(monkeypatch, plan)
    _slow_copy(monkeypatch, 0.002)
    out, _, _ = lower(plan).execute(x, pipeline=True)
    np.testing.assert_array_equal(x, before)
    assert not np.shares_memory(out, x)
    assert not np.array_equal(out, x)


def test_write_backs_wait_for_a_slow_copy(monkeypatch):
    """Bands of two rows, each slowed: the streamed boxes are ready long
    before the copy reaches their rows, so they wait for it, and the
    result is still bitwise.  With the wait taken out, the copy lands on
    boxes already written back and the result differs."""
    plan = _plan("so2dr")
    x = _domain(plan, 2)
    compiled = lower(plan)
    _inline(monkeypatch)
    want, _, _ = compiled.execute(x, pipeline=True)
    _bands(monkeypatch, plan, rows=2)
    _slow_copy(monkeypatch)
    got, _, es = compiled.execute(x, pipeline=True)
    np.testing.assert_array_equal(got, want)
    assert es.op_counts["HostCopy.wait"] >= 1
    assert es.op_wall_s["HostCopy.wait"] > 0.0

    monkeypatch.setattr(lower_mod._HostCopy, "wait",
                        lambda self, row, rec: None)
    bad, _, _ = compiled.execute(x, pipeline=True)
    assert not np.array_equal(bad, want)
    assert not _copy_threads()


def test_one_row_bands_under_fast_thread_switches(monkeypatch):
    """Stress: a band per row and a thread switch every microsecond, so
    the copy, the write-back threads and the issuing thread interleave
    finely; every solve stays bitwise and leaves no thread."""
    plan = _plan("so2dr")
    x = _domain(plan, 6)
    compiled = lower(plan)
    _inline(monkeypatch)
    want, _, _ = compiled.execute(x, pipeline=True)
    _bands(monkeypatch, plan, rows=1)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(5):
            got, _, es = compiled.execute(x, pipeline=True)
            np.testing.assert_array_equal(got, want)
            assert es.op_counts["HostCopy.band"] == plan.shape[0]
    finally:
        sys.setswitchinterval(interval)
    assert not _copy_threads()


def test_on_commit_sees_each_round_complete(monkeypatch):
    plan = _plan("so2dr")
    x = _domain(plan, 3)
    compiled = lower(plan)

    def rounds():
        seen = []
        host, _, _ = compiled.execute(
            x, pipeline=True,
            on_commit=lambda rnd, h: seen.append((rnd, h.copy())))
        return host, seen

    _inline(monkeypatch)
    _, want = rounds()
    _bands(monkeypatch, plan)
    _slow_copy(monkeypatch, 0.005)
    host, seen = rounds()
    assert [r for r, _ in seen] == [r for r, _ in want] == [0, 1]
    for (_, a), (_, b) in zip(seen, want):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(seen[-1][1], host)


def test_terminal_fault_in_round_0_leaves_no_copy_thread(monkeypatch):
    """A kernel fault while the slowed copy still runs: the run dies
    typed with no round committed, its copy stopped and its slots back
    in the pool."""
    plan = _plan("so2dr")
    _bands(monkeypatch, plan, rows=1)
    _slow_copy(monkeypatch)
    pool = SlotPool()
    faults = FaultPlan([FaultTrigger(round=0, chunk=1,
                                     op_class="FusedKernel",
                                     kind=KERNEL_FAULT)])
    with pytest.raises(PlanExecutionError) as err:
        lower(plan).execute(_domain(plan), pipeline=True, slot_pool=pool,
                            injector=faults.injector())
    assert err.value.last_committed_round == -1
    assert not _copy_threads()
    pool.assert_balanced()


def test_interleaved_service_jobs_stay_bitwise(monkeypatch):
    policy = DispatchPolicy(impl="reference")
    jobs = [StencilJob(shape=(66, 66), stencil="box2d1r", steps=8, d=4,
                       s_tb=4, k_on=2),
            StencilJob(shape=(50, 66), stencil="gradient2d", steps=8, d=4,
                       s_tb=4, k_on=2, codec="zrle")]
    rng = np.random.default_rng(4)
    xs = [rng.standard_normal(j.shape).astype(np.float32) for j in jobs]

    def flush():
        svc = StencilService(policy=policy)
        ids = [svc.submit(j, x) for j, x in zip(jobs, xs)]
        results = {r.job_id: r for r in svc.flush()}
        return [results[i] for i in ids]

    _inline(monkeypatch)
    want = flush()
    monkeypatch.setattr(lower_mod, "HOST_COPY_BAND_BYTES", 3 * 66 * 4)
    _slow_copy(monkeypatch, 0.002)
    got = flush()
    for a, b in zip(got, want):
        assert a.status == b.status == "ok"
        assert a.exec_stats.op_counts["HostCopy.band"] > 1
        np.testing.assert_array_equal(a.out, b.out)
    assert not _copy_threads()


def test_faulted_interleaved_job_stops_its_copy(monkeypatch):
    """A job isolated by a terminal fault stops its copy at once; the
    other job's result is bitwise its solve alone."""
    plan = _plan("so2dr")
    x = _domain(plan, 5)
    _inline(monkeypatch)
    want, _, _ = lower(plan).execute(x, pipeline=True)
    _bands(monkeypatch, plan, rows=1)
    _slow_copy(monkeypatch, 0.005)
    pool = SlotPool()
    faults = FaultPlan([FaultTrigger(round=0, chunk=0, op_class="FusedKernel",
                                     kind=KERNEL_FAULT)])
    jobs = [ScheduledJob(job_id=1, compiled=lower(plan), x=x, predicted_s=0.0,
                         injector=faults.injector()),
            ScheduledJob(job_id=2, compiled=lower(plan), x=x,
                         predicted_s=0.0)]
    (_, dead, _, _, fault), (_, out, _, _, ok) = run_interleaved(jobs, pool)
    assert dead is None and isinstance(fault, PlanExecutionError)
    assert ok is None
    np.testing.assert_array_equal(out, want)
    assert not _copy_threads()
    pool.assert_balanced()
