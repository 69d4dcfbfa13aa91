"""Streamed write-back (:class:`repro.core.lower._Runtime`): a D2H box
that no later host read of its round touches is pulled and scattered
into the host array on a write-back thread as soon as it is ready, and
the round's barrier waits for it.

* Which boxes stream is decided by the plan's boxes alone
  (:func:`repro.core.lower._streamed_d2h`).
* A solve that streams is bitwise equal to one that holds every box for
  the barrier, on every engine, with and without the prefetch, and with
  a transfer codec.
* ``on_commit`` sees each round complete; no write-back thread and no
  slot lease outlives a run, on the normal, fault and exception paths;
  a write-back's exception is raised on the caller's thread.
"""
import importlib
import threading

import numpy as np
import pytest

from repro import compile_plan, get_stencil
from repro.core.faults import KERNEL_FAULT, FaultPlan, FaultTrigger
from repro.core.lower import SlotPool, _streamed_d2h, lower
from repro.core.oocore import compile_box_plan
from repro.core.plan import D2H, Compress, FusedKernel, H2D, HostCommit
from repro.core.recovery import PlanExecutionError

lower_mod = importlib.import_module("repro.core.lower")
ENGINES = ["so2dr", "resreu", "naive_tb", "box_tb", "incore"]
MIN_BYTES = lower_mod.STREAM_MIN_BYTES


@pytest.fixture(autouse=True)
def stream_small_boxes(monkeypatch):
    """The plans here are small: boxes of any size may stream, so the
    rule's geometry decides (the size floor has a test of its own)."""
    monkeypatch.setattr(lower_mod, "STREAM_MIN_BYTES", 0)


def _plan(engine, codec=None):
    st = get_stencil("box2d1r")
    if engine == "box_tb":
        return compile_box_plan(st, (40, 40), 8, (2, 2), 4, 2, codec=codec)
    return compile_plan(engine, st, 62, 40, 8, 3, 4, 2, codec=codec)


def _domain(plan, seed=0):
    return np.random.default_rng(seed).random(plan.shape, dtype=np.float32)


def _held(monkeypatch):
    """Lower every plan with no box streaming: each waits for its
    barrier, as before streaming."""
    monkeypatch.setattr(lower_mod, "_streamed_d2h", lambda ops: set())


def _writeback_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("writeback")]


def _d2h_sites(plan):
    streamed = _streamed_d2h(plan.ops)
    return [(op.round, op.chunk, id(op) in streamed)
            for op in plan.ops if isinstance(op, D2H)]


def test_rule_streams_so2dr_and_resreu_boxes_under_later_kernels():
    """Region sharing hands chunk ``i+1`` its overlap from HBM, so no
    later host read touches a box: every box streams but each round's
    last, which no kernel follows."""
    for engine in ("so2dr", "resreu"):
        sites = _d2h_sites(_plan(engine))
        assert sites == [(r, c, c < 2) for r in (0, 1) for c in range(3)]


def test_rule_holds_boxes_a_later_read_touches():
    """``naive_tb`` re-reads chunk ``i``'s rows for chunk ``i+1``'s
    apron, and ``box_tb``'s aprons reach into the neighbouring tiles:
    every box waits for the barrier.  In core the one box has no kernel
    after it to hide under."""
    for engine in ("naive_tb", "box_tb", "incore"):
        sites = _d2h_sites(_plan(engine))
        assert sites and not any(s for *_, s in sites), (engine, sites)


def test_boxes_under_the_size_floor_stay_at_the_barrier(monkeypatch):
    """Below ``STREAM_MIN_BYTES`` a box is held whatever its geometry;
    the same plan at a size whose boxes pass the floor streams."""
    monkeypatch.setattr(lower_mod, "STREAM_MIN_BYTES", MIN_BYTES)
    assert not _streamed_d2h(_plan("so2dr").ops)
    big = compile_plan("so2dr", get_stencil("box2d1r"), 1538, 2048, 8, 3,
                       4, 2)
    sizes = [op.nbytes for op in big.ops if isinstance(op, D2H)]
    assert min(sizes) >= MIN_BYTES
    assert [s for *_, s in _d2h_sites(big)] == [True, True, False] * 2


@pytest.mark.parametrize("engine", ENGINES)
def test_rule_matches_the_plans_boxes(engine):
    """Checked op by op against the plan: a streamed box has a kernel
    after it in its round and no later host read of its round that
    overlaps it; a held one lacks one of the two."""
    ops = _plan(engine).ops
    streamed = _streamed_d2h(ops)

    def overlap(a, b):
        return all(max(a.lo[i], b.lo[i]) < min(a.hi[i], b.hi[i])
                   for i in range(a.ndim))

    for i, op in enumerate(ops):
        if not isinstance(op, D2H):
            continue
        later = []
        for nxt in ops[i + 1:]:
            if isinstance(nxt, HostCommit):
                break
            later.append(nxt)
        reads = [o.box for o in later if isinstance(o, H2D) or (
            isinstance(o, Compress) and o.direction == "h2d")]
        ok = (any(isinstance(o, FusedKernel) for o in later)
              and not any(overlap(op.box, b) for b in reads))
        assert (id(op) in streamed) == ok, (engine, op)


@pytest.mark.parametrize("pipeline", [False, True])
@pytest.mark.parametrize("engine,codec", [(e, None) for e in ENGINES]
                         + [("so2dr", "zrle"), ("resreu", "bf16")])
def test_streamed_solve_is_bitwise_the_held_one(engine, codec, pipeline,
                                                monkeypatch):
    plan = _plan(engine, codec)
    x = _domain(plan)
    got, _, es = lower(plan).execute(x, pipeline=pipeline)
    assert es.op_counts.get("D2H.wait", 0) == len(_streamed_d2h(plan.ops))
    _held(monkeypatch)
    want, _, held = lower(plan).execute(x, pipeline=pipeline)
    assert "D2H.wait" not in held.op_counts
    np.testing.assert_array_equal(got, want)
    assert not _writeback_threads()


def test_on_commit_sees_each_round_complete(monkeypatch):
    plan = _plan("so2dr")
    x = _domain(plan, 1)

    def rounds(compiled):
        seen = []
        host, _, _ = compiled.execute(
            x, pipeline=True,
            on_commit=lambda rnd, h: seen.append((rnd, h.copy())))
        return host, seen

    host, seen = rounds(lower(plan))
    _held(monkeypatch)
    _, want = rounds(lower(plan))
    assert [r for r, _ in seen] == [r for r, _ in want] == [0, 1]
    for (_, a), (_, b) in zip(seen, want):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(seen[-1][1], host)


@pytest.mark.parametrize("chunk", [1, 2])
def test_terminal_fault_mid_round_leaves_no_thread_or_lease(chunk):
    """A kernel fault in round 1 after some of its boxes streamed: the
    run dies typed, at the last committed round, with its write-back
    pool stopped and its slots back in the pool."""
    plan = _plan("so2dr")
    pool = SlotPool()
    faults = FaultPlan([FaultTrigger(round=1, chunk=chunk,
                                     op_class="FusedKernel",
                                     kind=KERNEL_FAULT)])
    with pytest.raises(PlanExecutionError) as err:
        lower(plan).execute(_domain(plan), pipeline=True, slot_pool=pool,
                            injector=faults.injector())
    assert err.value.last_committed_round == 0
    assert not _writeback_threads()
    pool.assert_balanced()


def test_write_back_exception_is_raised_on_the_caller(monkeypatch):
    plan = _plan("so2dr")
    write_back = lower_mod._write_back

    def failing(host, sl, rows, codec_name, rec):
        if rec.meta["chunk"] == 1:
            raise OSError("host array gone")
        write_back(host, sl, rows, codec_name, rec)

    monkeypatch.setattr(lower_mod, "_write_back", failing)
    pool = SlotPool()
    committed = []
    with pytest.raises(OSError, match="host array gone"):
        lower(plan).execute(_domain(plan), slot_pool=pool,
                            on_commit=lambda rnd, h: committed.append(rnd))
    assert committed == []          # raised at round 0's barrier
    assert not _writeback_threads()
    pool.assert_balanced()
