"""Kernel-backed compiled execution: lowering, dispatch, and the cache.

The executors must (a) be bit-identical to each other, to an unbucketed
lowering and to a one-job interleaved walk for every engine, and lie on
the oracle, (b) drive the real Pallas kernels (interpret mode) through
the dispatch registry and still match the oracle, and (c) compile at most one kernel per *shape bucket* — not per chunk x round
— with the counters to prove it in :class:`repro.core.lower.ExecStats`.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.compress import CODECS
from repro.core.executor import DoubleBufferedExecutor, EagerExecutor
from repro.core.faults import KERNEL_FAULT, FaultPlan, FaultTrigger
from repro.core.lower import KernelCache, lower
from repro.core.oocore import ENGINES, compile_plan
from repro.core.recovery import PlanExecutionError
from repro.core.reference import run_reference
from repro.core.stencil import get_stencil
from repro.kernels.dispatch import (
    DispatchPolicy, KERNEL_IMPLS, modeled_kernel_time, select_kernel,
)
from repro.serve.scheduler import ScheduledJob, run_interleaved

RNG = np.random.default_rng(31)


def _domain(st, rows, cols=40):
    Y, X = rows + 2 * st.radius, cols + 2 * st.radius
    return RNG.standard_normal((Y, X)).astype(np.float32)


def _plan(engine, st, x, n=4, d=2, k_off=2, k_on=2, codec=None):
    d_eff = 1 if engine == "incore" else d
    return compile_plan(engine, st, x.shape[0], x.shape[1], n, d_eff,
                        k_off, k_on, codec=codec)


# ------------------------------------------------- executors vs each other


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_executors_bitwise_match_each_other(engine):
    """Lowering is a pure compilation step and pipelining a pure
    reordering: the eager and the double-buffered walk, and a lowering
    without shape buckets, agree to the bit, and all lie on the oracle."""
    st = get_stencil("box2d2r")
    x = _domain(st, rows=48)
    plan = _plan(engine, st, x, n=8, d=4, k_off=4)
    eager_out, eager_stats = EagerExecutor().execute(plan, x)
    db_out, db_stats = DoubleBufferedExecutor().execute(plan, x)
    flat_out, flat_stats = EagerExecutor(
        policy=DispatchPolicy(bucket=False)).execute(plan, x)
    np.testing.assert_array_equal(eager_out, db_out)
    np.testing.assert_array_equal(eager_out, flat_out)
    assert eager_stats == db_stats == flat_stats
    ref = np.asarray(run_reference(jnp.asarray(x), st, plan.n))
    scale = np.abs(ref).max() + 1e-6
    assert np.abs(eager_out - ref).max() / scale < 1e-5


@pytest.mark.parametrize("n", [8, 0])
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_solo_run_is_a_one_job_interleaved_run(engine, n):
    """One job through the service's interleaved walk is the executor's
    pipelined solve: the same host bits and the same counters, also for
    a plan of no steps (most engines' plans then have no stage at all)."""
    st = get_stencil("box2d2r")
    x = _domain(st, rows=48)
    plan = _plan(engine, st, x, n=n, d=4, k_off=4)
    solo_host, _, solo = lower(plan).execute(x, pipeline=True)
    job = ScheduledJob(job_id=7, compiled=lower(plan), x=x, predicted_s=0.0)
    (_, host, stats, latency, fault), = run_interleaved([job])
    assert fault is None and latency == stats.wall_s
    np.testing.assert_array_equal(host, solo_host)
    assert stats.executor == "pipelined"
    for field in ("op_counts", "kernel_compiles", "kernel_cache_hits",
                  "stage_count"):
        assert getattr(stats, field) == getattr(solo, field), field


def test_solo_and_interleaved_fault_alike():
    """A terminal injected fault raises from the solo run and comes back
    from the interleaved walk as the same typed error."""
    st = get_stencil("box2d1r")
    x = _domain(st, rows=48)
    plan = _plan("so2dr", st, x, n=8, d=4, k_off=4)
    faults = FaultPlan([FaultTrigger(round=1, chunk=2,
                                     op_class="FusedKernel",
                                     kind=KERNEL_FAULT)])
    with pytest.raises(PlanExecutionError) as err:
        lower(plan).execute(x, pipeline=True, injector=faults.injector())
    job = ScheduledJob(job_id=3, compiled=lower(plan), x=x, predicted_s=0.0,
                       injector=faults.injector())
    (_, host, stats, _, fault), = run_interleaved([job])
    assert host is None and isinstance(fault, PlanExecutionError)
    solo = err.value
    assert (fault.fault.round, fault.fault.chunk, fault.fault.op_class) \
        == (solo.fault.round, solo.fault.chunk, solo.fault.op_class) \
        == (1, 2, "FusedKernel")
    assert fault.last_committed_round == solo.last_committed_round == 0
    assert fault.fingerprint == solo.fingerprint
    assert stats.faults_injected == 1


# ------------------------------------------------- kernel-backed execution


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("name", ["box2d1r", "gradient2d"])
def test_pallas_backed_execution_matches_oracle(engine, name):
    """Every engine, fused step dispatched to the Pallas kernel
    (interpret mode): within fp tolerance of the oracle and of the
    reference-fused run, and bit-identical between the eager and the
    pipelined executor (pipelining is a pure reordering)."""
    st = get_stencil(name)
    x = _domain(st, rows=32, cols=32)
    plan = _plan(engine, st, x)
    n = plan.n
    ref = np.asarray(run_reference(jnp.asarray(x), st, n))
    scale = np.abs(ref).max() + 1e-6

    out_ref_step, _ = EagerExecutor().execute(plan, x)
    policy = DispatchPolicy(impl="pallas", tile=(8, 32))
    ex = EagerExecutor(policy=policy)
    out, _ = ex.execute(plan, x)
    out_db, _ = DoubleBufferedExecutor(policy=policy).execute(plan, x)
    np.testing.assert_array_equal(out, out_db)
    # vs the jnp-fused run only fp-tolerance holds: XLA may fuse the tap
    # arithmetic differently inside the Pallas interpreter (one-ulp skew)
    assert np.abs(out - out_ref_step).max() / scale < 1e-5
    assert np.abs(out - ref).max() / scale < 1e-5
    assert ex.exec_stats.kernel_impl == "pallas"
    assert ex.exec_stats.kernel_calls > 0


def test_explicit_fused_step_and_other_impls():
    """An explicit fused_step callable overrides dispatch; the DMA-overlap
    and MXU kernels plug in through the same policy."""
    from repro.kernels.ops import kernel_fused_step

    st = get_stencil("box2d2r")
    x = _domain(st, rows=32, cols=32)
    plan = _plan("so2dr", st, x)
    base, _ = EagerExecutor().execute(plan, x)

    ex = EagerExecutor(fused_step=kernel_fused_step)
    out, _ = ex.execute(plan, x)
    assert ex.exec_stats.kernel_impl == "explicit"
    np.testing.assert_allclose(out, base, rtol=1e-5, atol=1e-5)

    for impl in ("pallas_db", "mxu"):
        ex = EagerExecutor(policy=DispatchPolicy(impl=impl, tile=(8, 32)))
        out, _ = ex.execute(plan, x)
        assert ex.exec_stats.kernel_impl == impl
        np.testing.assert_allclose(out, base, rtol=1e-5, atol=1e-5)


# ------------------------------------------------- cache / bucket counters


def test_so2dr_compiles_at_most_one_kernel_per_shape_bucket():
    """The acceptance bar: a d=8, 4-round SO2DR plan presents at most
    one kernel signature per shape bucket — not chunks x rounds."""
    st = get_stencil("box2d1r")
    x = _domain(st, rows=96, cols=48)
    plan = _plan("so2dr", st, x, n=16, d=8, k_off=4, k_on=2)
    rounds = 4
    ex = EagerExecutor()
    _, _ = ex.execute(plan, x)
    es = ex.exec_stats
    assert es.stage_count == 8 * rounds
    assert es.kernel_calls == 8 * rounds * 2          # split_steps(4, 2)
    assert es.kernel_compiles <= es.shape_buckets
    assert es.shape_buckets < es.kernel_calls         # bucketing collapses
    assert es.kernel_compiles + es.kernel_cache_hits == es.kernel_calls

    # without bucketing every distinct band height is its own signature
    ex_nb = EagerExecutor(policy=DispatchPolicy(bucket=False))
    out_nb, _ = ex_nb.execute(plan, x)
    assert ex_nb.exec_stats.kernel_compiles >= es.kernel_compiles
    out_b, _ = EagerExecutor().execute(plan, x)
    np.testing.assert_array_equal(out_b, out_nb)      # padding is invisible


def test_kernel_cache_shared_across_runs():
    """Re-executing through the same executor is all cache hits."""
    st = get_stencil("box2d1r")
    x = _domain(st, rows=48)
    plan = _plan("so2dr", st, x, n=8, d=4, k_off=4)
    ex = EagerExecutor()
    ex.execute(plan, x)
    first = ex.exec_stats
    assert first.kernel_compiles > 0
    ex.execute(plan, x)
    second = ex.exec_stats
    assert second.kernel_compiles == 0
    assert second.kernel_cache_hits == second.kernel_calls


def test_swapping_fused_step_never_serves_stale_kernel():
    """Mutating a live executor's fused_step between runs must execute
    the *new* callable (and count its signatures as fresh compiles), not
    replay the cached one."""
    from repro.core.reference import multi_step_band

    st = get_stencil("box2d1r")
    x = _domain(st, rows=48)
    plan = _plan("so2dr", st, x, n=4, d=4)
    calls = {"a": 0, "b": 0}

    def step_a(band, name, steps, keep_top=False, keep_bottom=False):
        calls["a"] += 1
        return multi_step_band(band, name, steps, keep_top, keep_bottom)

    def step_b(band, name, steps, keep_top=False, keep_bottom=False):
        calls["b"] += 1
        return multi_step_band(band, name, steps, keep_top, keep_bottom)

    ex = EagerExecutor(fused_step=step_a)
    ex.execute(plan, x)
    assert calls["a"] == ex.exec_stats.kernel_calls and calls["b"] == 0
    ex.fused_step = step_b
    ex.execute(plan, x)
    assert calls["b"] == ex.exec_stats.kernel_calls
    # new callable = new signatures, honestly counted as compiles
    assert ex.exec_stats.kernel_compiles == ex.exec_stats.shape_buckets


def test_exec_stats_op_counts_match_plan():
    st = get_stencil("gradient2d")
    x = _domain(st, rows=48)
    plan = _plan("resreu", st, x, n=4, d=4, k_off=2, k_on=1, codec="zrle")
    ex = DoubleBufferedExecutor()
    _, _ = ex.execute(plan, x)
    es = ex.exec_stats
    counts = plan.op_counts()
    # op classes as the plan counts them; phases are the dotted names
    assert {k: v for k, v in es.op_counts.items() if "." not in k} == counts
    assert (es.op_counts["D2H.pull"] == es.op_counts["D2H.decode"]
            == es.op_counts["D2H.scatter"] == counts["D2H"])
    assert es.op_counts["HostCommit.drain"] == counts["HostCommit"]
    assert es.op_counts["Execute.validate"] == 1
    # a domain of one band or less is copied inline: no copy thread
    assert "HostCopy.band" not in es.op_counts
    assert set(es.op_wall_s) == set(es.op_counts)
    assert all(t >= 0.0 for t in es.op_wall_s.values())
    assert es.executor == "double_buffered"


def test_lower_describe_is_deterministic_and_execution_free():
    st = get_stencil("box2d1r")
    plan = compile_plan("so2dr", st, 98, 98, 16, 8, 4, 2)
    d1 = lower(plan).describe()
    d2 = lower(plan).describe()
    assert d1 == d2
    assert d1["stage_count"] == 32
    assert d1["shape_buckets"] >= 1
    # slots are reused (with the pipeline-safety delay), so the register
    # file stays far below one slot per (round, chunk) register name
    assert d1["reg_slots"] < 32


# ------------------------------------------------- identity fast path


def test_identity_codec_round_trip_is_skipped(monkeypatch):
    """The identity codec's encode/decode is a pure byte copy; executors
    must skip it entirely (the transfer op is already the copy) while
    keeping the plan's wire accounting."""
    st = get_stencil("box2d1r")
    x = _domain(st, rows=48)
    plan_id = _plan("so2dr", st, x, n=4, d=4, codec="identity")
    plan_raw = _plan("so2dr", st, x, n=4, d=4)
    base, _ = EagerExecutor().execute(plan_raw, x)

    def boom(*a, **k):
        raise AssertionError("identity codec round trip was not skipped")

    idc = CODECS["identity"]
    monkeypatch.setattr(idc, "encode", boom)
    monkeypatch.setattr(idc, "decode", boom)
    for cls in (EagerExecutor, DoubleBufferedExecutor):
        out, stats = cls().execute(plan_id, x)
        np.testing.assert_array_equal(out, base)
        assert stats.codec_ops == plan_id.op_counts()["Compress"] * 2


# ------------------------------------------------- dispatch registry


def test_dispatch_registry_selection():
    name, fn = select_kernel("box2d1r", 2)            # auto off-TPU
    assert name == "reference" and callable(fn)
    name, _ = select_kernel("box2d4r", 2, DispatchPolicy(backend="tpu"))
    assert name == "mxu"                              # mxu_wins at r=4
    name, _ = select_kernel("gradient2d", 2, DispatchPolicy(backend="tpu"))
    assert name == "pallas_db"                        # nonlinear: no mxu
    with pytest.raises(ValueError):
        select_kernel("gradient2d", 2, DispatchPolicy(impl="mxu"))
    with pytest.raises(KeyError):
        select_kernel("box2d1r", 2, DispatchPolicy(impl="warp_specialized"))
    assert set(KERNEL_IMPLS) >= {"reference", "pallas", "pallas_db", "mxu"}


def test_modeled_kernel_times_are_ordered():
    """reference streams HBM per step; the fused Pallas paths read the
    band once — and the overlapped variant can only be faster still."""
    from repro.core.analytic import TPU_V5E

    st = get_stencil("box2d1r")
    plan = compile_plan("so2dr", st, 404, 404, 40, 4, 10, 4)
    t_ref, _, _ = modeled_kernel_time(plan, TPU_V5E, "reference")
    t_p, _, _ = modeled_kernel_time(plan, TPU_V5E, "pallas")
    t_db, _, _ = modeled_kernel_time(plan, TPU_V5E, "pallas_db")
    assert t_db <= t_p
    assert t_db <= t_ref
    # nonlinear stencils cannot take the banded-MXU path
    plan_g = compile_plan("so2dr", get_stencil("gradient2d"),
                          404, 404, 40, 4, 10, 4)
    assert modeled_kernel_time(plan_g, TPU_V5E, "mxu") is None


def test_kernel_cache_counts_signatures():
    cache = KernelCache()
    fn = cache.lookup(("a", 1), lambda: "one")
    assert fn == "one" and cache.misses == 1 and cache.hits == 0
    assert cache.lookup(("a", 1), lambda: "two") == "one"
    assert cache.hits == 1 and len(cache) == 1


def test_autotune_sweeps_dispatch_policy():
    from repro.core.analytic import TPU_V5E
    from repro.core.autotune import autotune

    st = get_stencil("box2d1r")
    ranked = autotune(st, 256, 40, TPU_V5E, d_grid=(4,), s_tb_grid=(20, 40),
                      k_on_grid=(1, 2), kernel_impls=("reference", "pallas_db"),
                      codecs=("identity",))
    assert ranked
    impls = {c.kernel_impl for c in ranked}
    assert impls == {"reference", "pallas_db"}
    assert all(c.time_s > 0 for c in ranked)
    assert "kernel_impl" in ranked[0].config
