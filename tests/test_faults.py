"""Fault tolerance: deterministic injection, checkpoint/resume, elastic
re-planning, service degradation.

The load-bearing property (the crash matrix): a terminal fault injected
at *any* round of *any* engine x executor x codec, recovered through
``run_with_recovery`` + ``PlanCheckpointer``, produces a host array
bit-identical to the uninterrupted run — ``HostCommit`` barriers are
exact recovery points because registers and buffers never cross one.
Everything runs with zero devices except one 8-fake-device subprocess
case exercising rank loss on the real ``shard_map`` backend.
"""
import numpy as np
import pytest

from _subproc import run_fake_device_subprocess

from repro.core.executor import (
    DoubleBufferedExecutor, EagerExecutor, ShardedSimExecutor,
)
from repro.core.faults import (
    KERNEL_FAULT, RANK_LOSS, SLOT_EXHAUSTED, TRANSIENT_TRANSFER,
    FaultPlan, FaultTrigger, RetryPolicy, TransientTransferError,
)
from repro.core.lower import SlotPool, lower
from repro.core.oocore import compile_box_plan, compile_plan
from repro.core.recovery import (
    PlanCheckpointer, PlanExecutionError, plan_fingerprint, resume_plan,
    run_with_recovery,
)
from repro.core.shard import compile_sharded
from repro.core.stencil import get_stencil
from repro.checkpoint import CheckpointManager
from repro.launch.elastic import (
    ElasticReport, replan_sharded, run_elastic_sharded, shrink_mesh,
)
from repro.serve import StencilJob, StencilService

RNG = np.random.default_rng(11)
NO_WAIT = RetryPolicy(sleep=lambda s: None)


def _domain(Y=32, X=16):
    return RNG.standard_normal((Y, X)).astype(np.float32)


def _plan(engine="so2dr", codec=None, Y=32, X=16, n=8, d=2, k_off=4,
          k_on=2):
    st = get_stencil("star2d1r")
    if engine == "box_tb":
        return compile_box_plan(st, (Y, X), n, (2, 1), k_off, k_on,
                                codec=codec)
    return compile_plan(engine, st, Y, X, n, d, k_off, k_on, codec=codec)


def _rounds(plan):
    return sorted({op.round for op in plan.ops})


def _make_executor(name):
    return {"eager": EagerExecutor,
            "double_buffered": DoubleBufferedExecutor}[name]()


# ------------------------------------------------------- crash matrix


@pytest.mark.parametrize("executor", ["eager", "double_buffered"])
@pytest.mark.parametrize(
    "engine", ["incore", "naive_tb", "resreu", "so2dr", "box_tb"])
def test_crash_at_every_round_resumes_bit_identical(
        engine, executor, tmp_path):
    """Terminal kernel fault at each round -> checkpointed resume ->
    bit-identical to the uninterrupted run (every engine x executor)."""
    plan = _plan(engine)
    x = _domain()
    ref, _ = EagerExecutor().execute(plan, x)
    for rnd in _rounds(plan):
        mgr = CheckpointManager(str(tmp_path / f"{engine}_{rnd}"))
        faults = FaultPlan([FaultTrigger(round=rnd, chunk=None,
                                         op_class="*", kind=KERNEL_FAULT)])
        ex = _make_executor(executor)
        host, _ = run_with_recovery(
            plan, x, executor=ex, faults=faults,
            checkpoint=PlanCheckpointer(mgr, plan))
        np.testing.assert_array_equal(host, ref), (engine, executor, rnd)
        assert ex.exec_stats.resumes == 1
        assert ex.exec_stats.faults_injected == 1


@pytest.mark.parametrize("executor", ["eager", "double_buffered"])
def test_crash_matrix_with_compression_codec(executor, tmp_path):
    """The resume property holds through the zrle transfer codec —
    Compress/Decompress ops carry rounds like every other op."""
    plan = _plan("so2dr", codec="zrle")
    x = _domain()
    ref, _ = EagerExecutor().execute(plan, x)
    for rnd in _rounds(plan):
        mgr = CheckpointManager(str(tmp_path / f"zrle_{rnd}"))
        faults = FaultPlan([FaultTrigger(round=rnd, chunk=None,
                                         op_class="*", kind=KERNEL_FAULT)])
        ex = _make_executor(executor)
        host, _ = run_with_recovery(
            plan, x, executor=ex, faults=faults,
            checkpoint=PlanCheckpointer(mgr, plan))
        np.testing.assert_array_equal(host, ref)
        assert ex.exec_stats.resumes == 1


def test_sharded_sim_crash_and_recovery(tmp_path):
    """A sharded plan dies typed (it commits host state only at the
    end, so last_committed_round=-1) and run_with_recovery restarts it
    from scratch to the bit-identical answer."""
    plan = compile_sharded(get_stencil("star2d1r"), 48, 32, 8, 2, (4, 2))
    x = RNG.standard_normal((48, 32)).astype(np.float32)
    ref, _ = ShardedSimExecutor().execute(plan, x)
    faults = FaultPlan([FaultTrigger(round=2, chunk=5, op_class="*",
                                     kind=KERNEL_FAULT)])
    with pytest.raises(PlanExecutionError) as ei:
        ShardedSimExecutor().execute(plan, x, injector=faults.injector())
    assert ei.value.last_committed_round == -1
    ex = ShardedSimExecutor()
    mgr = CheckpointManager(str(tmp_path))
    host, _ = run_with_recovery(plan, x, executor=ex, faults=faults,
                                checkpoint=PlanCheckpointer(mgr, plan))
    np.testing.assert_array_equal(host, ref)
    assert ex.exec_stats.resumes == 1


# ------------------------------------------- injection + retry mechanics


def test_seeded_fault_plans_are_deterministic():
    plan = _plan()
    a = FaultPlan.seeded(17, plan, n_faults=4,
                         kinds=(TRANSIENT_TRANSFER, KERNEL_FAULT),
                         op_classes=("H2D", "FusedKernel"))
    b = FaultPlan.seeded(17, plan, n_faults=4,
                         kinds=(TRANSIENT_TRANSFER, KERNEL_FAULT),
                         op_classes=("H2D", "FusedKernel"))
    assert a.triggers == b.triggers
    keys = {k for k, _ in plan.stages() if k is not None}
    for t in a.triggers:                 # sites drawn from real geometry
        assert (t.round, t.chunk) in keys
    c = FaultPlan.seeded(18, plan, n_faults=4,
                         kinds=(TRANSIENT_TRANSFER, KERNEL_FAULT),
                         op_classes=("H2D", "FusedKernel"))
    assert a.triggers != c.triggers      # seed actually matters


def test_transient_fault_absorbed_by_retry():
    """A transient trigger with count <= max_retries never surfaces:
    the stage loop retries in place and the output stays bitwise."""
    plan = _plan()
    x = _domain()
    ref, _ = EagerExecutor().execute(plan, x)
    faults = FaultPlan([FaultTrigger(round=0, chunk=0, op_class="H2D",
                                     kind=TRANSIENT_TRANSFER, count=2)])
    ex = EagerExecutor()
    host, _ = run_with_recovery(plan, x, executor=ex, faults=faults,
                                retry=NO_WAIT)
    np.testing.assert_array_equal(host, ref)
    assert ex.exec_stats.faults_injected == 2
    assert ex.exec_stats.retries == 2
    assert ex.exec_stats.resumes == 0


def test_retry_exhaustion_surfaces_typed_error():
    """A transient fault persisting past the retry budget becomes a
    terminal PlanExecutionError carrying the transient cause."""
    plan = _plan()
    faults = FaultPlan([FaultTrigger(round=0, chunk=0, op_class="H2D",
                                     kind=TRANSIENT_TRANSFER, count=10)])
    injector = faults.injector()
    with pytest.raises(PlanExecutionError) as ei:
        run_with_recovery(plan, _domain(), faults=injector, retry=NO_WAIT)
    assert isinstance(ei.value.fault, TransientTransferError)
    assert ei.value.last_committed_round == -1
    assert injector.retries == NO_WAIT.max_retries
    assert injector.faults_injected == NO_WAIT.max_retries + 1


def test_clean_run_with_injector_is_invisible():
    """An armed injector whose triggers never fire changes nothing:
    zero fault counters, bit-identical output."""
    plan = _plan()
    x = _domain()
    ref, _ = EagerExecutor().execute(plan, x)
    ex = EagerExecutor()
    host, _ = ex.execute(plan, x, injector=FaultPlan([]).injector())
    np.testing.assert_array_equal(host, ref)
    assert ex.exec_stats.faults_injected == 0
    assert ex.exec_stats.retries == 0


# ----------------------------------------------------- slot-lease leaks


def test_slot_pool_drains_after_faulted_run():
    """The slot-lease leak regression: a run killed mid-stage still
    returns every leased slot to the pool (try/finally in execute)."""
    pool = SlotPool()
    plan = _plan()
    compiled = lower(plan)
    faults = FaultPlan([FaultTrigger(round=1, chunk=0, op_class="*",
                                     kind=SLOT_EXHAUSTED)])
    with pytest.raises(PlanExecutionError):
        compiled.execute(_domain(), slot_pool=pool,
                         injector=faults.injector())
    assert pool.in_use == 0
    assert pool.leases == 1
    compiled.execute(_domain(), slot_pool=pool)       # pool still healthy
    assert pool.in_use == 0 and pool.reuses == 1


# ------------------------------------------------- resume-plan algebra


def test_resume_plan_structure():
    plan = _plan()
    assert resume_plan(plan, 0) is plan
    cont = resume_plan(plan, 1)
    assert min(op.round for op in cont.ops) == 1
    assert cont.exact_elements == plan.exact_elements // 2  # half the steps
    assert plan_fingerprint(cont) != plan_fingerprint(plan)


def test_checkpointer_ignores_foreign_fingerprints(tmp_path):
    """A snapshot taken under one plan is never resumed into another."""
    mgr = CheckpointManager(str(tmp_path))
    plan_a, plan_b = _plan("so2dr"), _plan("resreu")
    ck_a = PlanCheckpointer(mgr, plan_a)
    ck_a.on_commit(0, _domain())
    assert ck_a.latest() is not None
    assert PlanCheckpointer(mgr, plan_b).latest() is None


def test_checkpoint_cadence(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=10)
    ck = PlanCheckpointer(mgr, _plan(), every=2)
    for rnd in range(4):
        ck.on_commit(rnd, _domain())
    assert ck.saves == 2                       # rounds 0 and 2 only
    rnd, _ = ck.latest()
    assert rnd == 2


# --------------------------------------------------- elastic re-planning


def test_elastic_rank_loss_replans_within_one_round():
    """Rank loss on a (4,2) mesh: re-plan to (3,2) on the survivors,
    finish within exactly one extra round of transfers, match the
    fault-free answer."""
    plan = compile_sharded(get_stencil("star2d1r"), 48, 32, 8, 2, (4, 2))
    x = RNG.standard_normal((48, 32)).astype(np.float32)
    ref, _ = ShardedSimExecutor().execute(plan, x)

    out, rep = run_elastic_sharded(plan, x)    # fault-free: bitwise
    np.testing.assert_array_equal(out, ref)
    assert rep.extra_rounds == 0 and rep.replans == 0

    faults = FaultPlan([FaultTrigger(round=1, chunk=3, op_class="*",
                                     kind=RANK_LOSS)])
    out, rep = run_elastic_sharded(plan, x, faults=faults)
    assert isinstance(rep, ElasticReport)
    assert rep.replans == 1 and rep.extra_rounds == 1
    assert rep.mesh_history == ((4, 2), (3, 2))
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_elastic_survives_successive_preemptions():
    plan = compile_sharded(get_stencil("star2d1r"), 48, 32, 8, 2, (4, 2))
    x = RNG.standard_normal((48, 32)).astype(np.float32)
    ref, _ = ShardedSimExecutor().execute(plan, x)
    faults = FaultPlan([
        FaultTrigger(round=0, chunk=0, op_class="*", kind=RANK_LOSS),
        FaultTrigger(round=2, chunk=1, op_class="*", kind=RANK_LOSS)])
    out, rep = run_elastic_sharded(plan, x, faults=faults)
    assert rep.mesh_history == ((4, 2), (3, 2), (2, 2))
    assert rep.extra_rounds == 2
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_elastic_terminal_fault_and_mesh_algebra():
    plan = compile_sharded(get_stencil("star2d1r"), 48, 32, 8, 2, (4, 2))
    x = RNG.standard_normal((48, 32)).astype(np.float32)
    faults = FaultPlan([FaultTrigger(round=2, chunk=None, op_class="*",
                                     kind=KERNEL_FAULT)])
    with pytest.raises(PlanExecutionError) as ei:
        run_elastic_sharded(plan, x, faults=faults)
    assert ei.value.last_committed_round == 1  # rounds 0-1 stored

    assert shrink_mesh((4, 2), 7) == (3, 2)
    assert shrink_mesh((1, 4), 0) == (1, 3)
    with pytest.raises(ValueError):
        shrink_mesh((1, 1), 0)
    cont = replan_sharded(plan, 2)
    assert cont.rounds == 2 and cont.mesh_shape == (4, 2)
    with pytest.raises(ValueError):
        replan_sharded(plan, plan.rounds)      # nothing left to do


_ELASTIC_SUBPROC = r"""
import numpy as np
from repro.core.executor import ShardMapExecutor, ShardedSimExecutor
from repro.core.faults import FaultPlan, FaultTrigger, RANK_LOSS
from repro.core.shard import compile_sharded
from repro.core.stencil import get_stencil
from repro.launch.elastic import run_elastic_sharded

plan = compile_sharded(get_stencil("box2d1r"), 48, 32, 8, 2, (4, 2))
x = np.random.default_rng(3).standard_normal((48, 32)).astype(np.float32)
ref, _ = ShardedSimExecutor().execute(plan, x)
faults = FaultPlan([FaultTrigger(round=1, chunk=6, op_class="*",
                                 kind=RANK_LOSS)])
out, rep = run_elastic_sharded(
    plan, x, faults=faults,
    executor_factory=lambda mesh_shape: ShardMapExecutor())
assert rep.replans == 1 and rep.extra_rounds == 1, rep
assert rep.mesh_history == ((4, 2), (3, 2)), rep
assert np.abs(out - ref).max() < 1e-5
print("ELASTIC_SHARD_MAP_OK")
"""


def test_elastic_rank_loss_on_shard_map_backend_subprocess():
    """8 fake devices: the same preemption story through the real
    shard_map backend — injection is probed per rank before dispatch
    (one fused program is all-or-nothing), the re-planned (3,2) mesh
    uses 6 of the 8 devices."""
    run_fake_device_subprocess(_ELASTIC_SUBPROC, "ELASTIC_SHARD_MAP_OK")


# -------------------------------------------------- service degradation


def test_service_isolates_failed_job():
    """One poisoned job in a flush batch: it comes back failed with the
    typed fault, every survivor is bit-identical to its solo run, and
    the slot pool fully drains."""
    x = np.arange(32 * 16, dtype=np.float32).reshape(32, 16) / 7.0
    faults = FaultPlan([FaultTrigger(round=1, chunk=0, op_class="*",
                                     kind=KERNEL_FAULT)])

    ref_svc = StencilService()
    for _ in range(3):
        ref_svc.submit(StencilJob(shape=(32, 16), stencil="star2d1r",
                                  steps=8, s_tb=4), x)
    ref = {r.job_id: r.out for r in ref_svc.flush()}

    svc = StencilService()
    for i in range(3):
        svc.submit(StencilJob(shape=(32, 16), stencil="star2d1r",
                              steps=8, s_tb=4,
                              faults=faults if i == 1 else None,
                              retry=NO_WAIT), x)
    results = {r.job_id: r for r in svc.flush()}
    assert len(results) == 3
    assert results[1].status == "failed" and results[1].out is None
    assert isinstance(results[1].fault, PlanExecutionError)
    assert results[1].fault.last_committed_round == 0
    for jid in (0, 2):
        assert results[jid].status == "ok" and results[jid].fault is None
        np.testing.assert_array_equal(results[jid].out, ref[jid])
    assert svc.slot_pool.in_use == 0
    stats = svc.service_stats()
    assert stats["jobs_failed"] == 1 and stats["jobs_completed"] == 2


def test_service_transient_faults_retried_transparently():
    x = _domain()
    ref_svc = StencilService()
    ref = ref_svc.run_solo(StencilJob(shape=(32, 16), stencil="star2d1r",
                                      steps=8, s_tb=4), x)
    svc = StencilService()
    faults = FaultPlan([FaultTrigger(round=0, chunk=0, op_class="H2D",
                                     kind=TRANSIENT_TRANSFER, count=2)])
    svc.submit(StencilJob(shape=(32, 16), stencil="star2d1r", steps=8,
                          s_tb=4, faults=faults, retry=NO_WAIT), x)
    res, = svc.flush()
    assert res.status == "ok"
    assert res.exec_stats.faults_injected == 2
    assert res.exec_stats.retries == 2
    np.testing.assert_array_equal(res.out, ref.out)
