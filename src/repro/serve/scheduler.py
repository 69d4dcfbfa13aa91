"""Cross-job pipelined scheduling for :class:`~repro.serve.service.StencilService`.

The paper's SO2DR schedule hides transfer under compute *within* one
job; a warm service can do strictly better by interleaving the
per-(round, chunk) stage programs of M concurrent jobs, so one job's
H2D rides under another job's kernels — overlap a single job's barrier
structure can never express.

Soundness of the round-robin merge: each job's stages stay in its own
plan order, so every earlier stage of job *j* (including its HostCommit
barriers) has executed before any later stage of *j* is issued.  The
merged schedule runs through the same stage walker as a single plan
(:func:`repro.core.lower.walk_stages`), so the double-buffered prefetch
rule carries over unchanged — a stage's prefetchable prefix (H2D +
host-side Compress) is issued early only when the stage is a chunk
stage, never across its own job's barrier, and always against its own
job's runtime.

Admission ordering is deadline-aware shortest-predicted-first: the
dry-run cost model (:func:`repro.core.autotune.predicted_makespan`)
prices each job with zero device work, jobs with deadlines sort ahead
of best-effort jobs, and ties break on job id for determinism.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.analytic import Hardware
from repro.core.autotune import pipeline_makespan, stage_costs
from repro.core.lower import (
    CompiledPlan, ExecStats, PlanRun, SlotPool, SpanRecorder, walk_stages,
)
from repro.core.recovery import PlanExecutionError

__all__ = ["ScheduledJob", "admission_order", "interleave_stages",
           "modeled_makespan", "run_interleaved"]


@dataclasses.dataclass
class ScheduledJob:
    """One admitted job: its compiled plan, input domain, and the
    dry-run price admission sorted on."""

    job_id: int
    compiled: CompiledPlan
    x: np.ndarray
    predicted_s: float
    deadline: Optional[float] = None
    # fault-injection hooks (None in production): consulted before every
    # bound op of this job's stages, retried under ``retry``
    injector: Optional[object] = None
    retry: Optional[object] = None


def admission_order(jobs: Sequence[ScheduledJob]) -> List[ScheduledJob]:
    """Deadline-aware shortest-predicted-makespan-first admission.

    Jobs carrying a deadline run before best-effort jobs and among
    themselves by earliest deadline; within a deadline class the
    cheapest predicted job goes first (SJF minimizes mean latency);
    job id breaks the remaining ties deterministically."""
    return sorted(jobs, key=lambda j: (
        j.deadline if j.deadline is not None else float("inf"),
        j.predicted_s, j.job_id))


def interleave_stages(jobs: Sequence[ScheduledJob],
                      ) -> List[Tuple[ScheduledJob, int]]:
    """Round-robin merge of the jobs' stage programs.

    One stage per job per cycle, in admission order, preserving each
    job's internal stage order — the schedule both the makespan model
    and :func:`run_interleaved` walk."""
    merged: List[Tuple[ScheduledJob, int]] = []
    cursors = [0] * len(jobs)
    remaining = sum(len(j.compiled.stages) for j in jobs)
    while remaining:
        for i, job in enumerate(jobs):
            if cursors[i] < len(job.compiled.stages):
                merged.append((job, cursors[i]))
                cursors[i] += 1
                remaining -= 1
    return merged


def modeled_makespan(jobs: Sequence[ScheduledJob], hw: Hardware,
                     interleaved: bool = True, profile=None) -> float:
    """Dry-run makespan of the job set on the three-engine pipeline.

    ``interleaved=True`` prices the round-robin merge; ``False`` prices
    the same jobs back-to-back — the comparison the service's perf win
    is asserted against (no device work either way).  ``profile`` (a
    :class:`~repro.core.calibrate.DeviceProfile` or a path) substitutes
    calibrated constants for ``hw``."""
    if profile is not None:
        from repro.core.calibrate import resolve_hardware

        hw = resolve_hardware(profile)
    costed = {j.job_id: stage_costs(j.compiled.plan, hw) for j in jobs}
    if interleaved:
        schedule = [(job.job_id, costed[job.job_id][s])
                    for job, s in interleave_stages(jobs)]
        return pipeline_makespan(schedule)
    return sum(pipeline_makespan((j.job_id, sc) for sc in costed[j.job_id])
               for j in jobs)


def run_interleaved(jobs: Sequence[ScheduledJob],
                    slot_pool: Optional[SlotPool] = None,
                    ) -> List[Tuple[ScheduledJob, Optional[np.ndarray],
                                    ExecStats, float,
                                    Optional[PlanExecutionError]]]:
    """Execute the merged schedule; one result tuple per job, in the
    given (admission) order: ``(job, host_out, exec_stats, latency_s,
    fault)``.

    The merged schedule goes through the single-device stage walker
    (:func:`repro.core.lower.walk_stages`), pipelined: each job gets its
    own runtime (slot storage leased from ``slot_pool`` when given), and
    the prefetch rule applies across the *merged* sequence, so job B's
    H2D is issued while job A's kernels are still in flight — the
    cross-job analogue of the paper's N_strm = 3 overlap.  Latency is
    stamped when a job's last stage retires (its final barrier has
    drained its staged writes).  Each job's ops are timed by its own
    :class:`~repro.core.lower.SpanRecorder`, whose profiler spans carry
    ``job`` beside ``run``, ``round`` and ``chunk``.

    Graceful degradation: a job whose injector raises a terminal fault
    is *isolated* — its leased slots are released on the spot, its
    remaining merged entries are skipped, and it comes back with
    ``host_out=None`` and the typed ``fault`` attached — while every
    other job's stage walk continues untouched (property-tested: the
    survivors stay bit-identical to a fault-free run)."""
    runs = {j.job_id: PlanRun(j.compiled, j.x, j.injector, j.retry,
                              label=f"job {j.job_id}",
                              rec=SpanRecorder(job=j.job_id))
            for j in jobs}
    walk_stages(list(runs.values()),
                [(runs[j.job_id], s) for j, s in interleave_stages(jobs)],
                pipeline=True, slot_pool=slot_pool)
    out = []
    for job in jobs:
        run = runs[job.job_id]
        out.append((job, run.host, run.stats(executor="pipelined"),
                    run.wall_s, run.error))
    return out
