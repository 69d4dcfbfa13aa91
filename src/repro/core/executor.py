"""Pluggable executors over :class:`repro.core.plan.ExecutionPlan`.

Three interpreters of the same op schedule:

* :class:`EagerExecutor` — walks ops in plan order; reproduces the
  pre-refactor engine behavior bit-for-bit against the oracle.
* :class:`DoubleBufferedExecutor` — software-pipelined: chunk ``i+1``'s
  H2D is issued while chunk ``i``'s kernels/D2H are still in flight
  (JAX async dispatch carries the overlap; the issuing thread blocks
  only at a ``HostCommit`` barrier, and a streamed D2H box waits for
  the device on a write-back thread).  This is the paper's
  multi-stream overlap (Sec. II, N_strm = 3), previously impossible
  with inline engine loops.
* :class:`DryRunExecutor` — walks no device work at all and returns the
  plan-derived :class:`TransferStats`; the autotuner costs the whole
  configuration sweep with it.  It also costs multi-device
  :class:`~repro.core.plan.ShardedPlan` schedules with zero devices.

Sharded plans (:mod:`repro.core.shard`) add two more:

* :class:`ShardedSimExecutor` — lowers the per-rank op streams to
  lockstep stage programs (:func:`repro.core.lower.lower_sharded`) and
  runs them on a single device, halos moving through a mailbox; the
  differential counterpart of the shard_map oracle.
* :class:`ShardMapExecutor` — dispatches the plan to the real
  ``shard_map``/``ppermute`` backend in :mod:`repro.core.distributed`.

The device executors run plans through the lowering layer by default
(:func:`repro.core.lower.lower`): ops become per-(round, chunk) stage
programs of pre-bound closures (no per-op ``isinstance`` dispatch),
FusedKernel ops resolve through the kernel-dispatch registry
(:mod:`repro.kernels.dispatch`), band heights are padded to per-plan
shape buckets so chunks/rounds share one compiled kernel signature, and
an :class:`~repro.core.lower.ExecStats` with per-span wall clock (every
op class and the phases of the fused step and the barrier, each also a
profiler span) and compilation-cache counters lands on
``executor.exec_stats`` after every run.  ``lowered=False`` falls back to the original op-at-a-time
interpreter (:class:`_DeviceState`) — results are bitwise identical.

All executors return ``(host_array | None, TransferStats)`` where the
stats always come from :meth:`ExecutionPlan.stats` — accounting is a
property of the *plan*, not of how it was executed.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .compress import get_codec
from .lower import ExecStats, KernelCache, lower, lower_sharded, validate_domain
from .plan import (
    BufferRead, BufferWrite, Compress, D2H, Decompress, ExecutionPlan,
    FusedKernel, H2D, HostCommit, ShardedPlan, TransferStats,
)
from .reference import multi_step_band, multi_step_box

__all__ = [
    "EagerExecutor", "DoubleBufferedExecutor", "DryRunExecutor",
    "ShardedSimExecutor", "ShardMapExecutor",
    "get_executor", "EXECUTORS",
]

# fused-step implementation signature:
#   fn(band, stencil_name, steps, keep_top, keep_bottom) -> band
FusedStep = Callable[..., jnp.ndarray]


class _StagedWrite:
    """One staged D2H.

    ``rows`` stays an async device handle until the HostCommit barrier —
    also for compressed transfers: the codec's encode/decode round trip
    runs at commit time (the first point the bytes are forced anyway), so
    compression never adds a per-chunk sync and the double-buffered
    overlap is preserved.  ``pending`` is True only between a d2h-side
    Compress and its Decompress; committing a pending entry is a plan
    bug."""

    __slots__ = ("box", "rows", "codec", "pending")

    def __init__(self, box, rows, codec=None, pending=False):
        self.box = box            # destination host Box
        self.rows = rows          # async jnp handle (or np box payload)
        self.codec = codec        # codec name; round trip runs at commit
        self.pending = pending


class _DeviceState:
    """Register/buffer/staging state for the legacy op-at-a-time path.

    Codec ops run for real: the ``Compress``/``Decompress`` pairs the
    rewrite pass emits encode the transferred rows into an actual byte
    payload and decode them on the far side (this container is CPU, so
    the codec's device half runs in NumPy).  H2D encodes eagerly at the
    Compress op (a pure host-side read; the ``jnp.asarray`` hop carries
    the encoded bytes) and decodes at the Decompress op; the D2H round
    trip is recorded at the Decompress op but physically runs at the
    HostCommit barrier — the first point the device bytes are forced
    anyway — so compression never introduces a per-chunk sync.  Lossless
    codecs therefore round-trip bit-exactly through real encoded bytes;
    accounting still comes from the plan.

    The ``identity`` codec is fast-pathed: its encode/decode is a pure
    byte copy, so the round trip is skipped entirely — the H2D/D2H is
    already the copy — while wire-byte accounting (plan-derived) is
    untouched."""

    def __init__(self, host: np.ndarray, fused_step: Optional[FusedStep]):
        self.host = host
        self.fused_step = fused_step   # None = reference (banded path only)
        self.regs: Dict[str, jnp.ndarray] = {}
        self.bufs: Dict[str, jnp.ndarray] = {}
        self.staged: List[_StagedWrite] = []
        # reg -> (device payload, shape, dtype) between Compress(h2d) and
        # Decompress(h2d); reg -> codec name between Compress(d2h) and D2H
        self.h2d_wire: Dict[str, Tuple[jnp.ndarray, tuple, np.dtype]] = {}
        self.d2h_codec: Dict[str, str] = {}

    def issue_h2d(self, op: H2D) -> None:
        if op.reg in self.h2d_wire:
            return   # wire hop already happened at Compress time
        self.regs[op.reg] = jnp.asarray(self.host[op.box.slices()])

    def _compress(self, op: Compress) -> None:
        if op.codec == "identity":
            return   # fast path: the transfer op itself is the pure copy
        if op.direction == "h2d":
            rows = self.host[op.box.slices()]
            payload = get_codec(op.codec).encode(rows)
            # the wire hop: encoded bytes (not raw rows) go to the device
            self.h2d_wire[op.reg] = (jnp.asarray(payload), rows.shape, rows.dtype)
        else:
            self.d2h_codec[op.reg] = op.codec   # encode happens at the D2H

    def _decompress(self, op: Decompress) -> None:
        if op.codec == "identity":
            return
        if op.direction == "h2d":
            payload, shape, dtype = self.h2d_wire.pop(op.reg)
            decoded = get_codec(op.codec).decode(np.asarray(payload), shape, dtype)
            self.regs[op.reg] = jnp.asarray(decoded)
        else:
            entry = self.staged[-1]
            assert entry.pending and entry.box == op.box, \
                "Decompress does not match the staged D2H"
            entry.pending = False   # round trip scheduled; runs at commit

    def issue(self, op) -> None:
        if isinstance(op, H2D):
            self.issue_h2d(op)
        elif isinstance(op, Compress):
            self._compress(op)
        elif isinstance(op, Decompress):
            self._decompress(op)
        elif isinstance(op, BufferWrite):
            self.bufs[op.buf] = self.regs[op.reg][op.reg_box.slices()]
        elif isinstance(op, BufferRead):
            shared = self.bufs.pop(op.buf)
            self.regs[op.reg] = jnp.concatenate(
                [shared, self.regs.pop(op.src)], axis=op.axis)
        elif isinstance(op, FusedKernel):
            band = self.regs[op.reg]
            # banded = a classic 2-D row band (full width, frame columns
            # along): the registered fused-step kernels apply.  Anything
            # else (3-D tiles, column chunks) runs the N-D reference.
            if len(op.shape_in) == 2 and op.keep_lo[1] and op.keep_hi[1]:
                fn = self.fused_step or multi_step_band
                self.regs[op.reg] = fn(
                    band, op.stencil, op.steps,
                    keep_top=op.keep_lo[0], keep_bottom=op.keep_hi[0])
            else:
                self.regs[op.reg] = multi_step_box(
                    band, op.stencil, op.steps,
                    keep_lo=op.keep_lo, keep_hi=op.keep_hi)
        elif isinstance(op, D2H):
            band = self.regs.pop(op.reg)   # last use of the register
            codec = self.d2h_codec.pop(op.reg, None)
            self.staged.append(_StagedWrite(
                op.box, rows=band[op.reg_box.slices()],
                codec=codec, pending=codec is not None))
        elif isinstance(op, HostCommit):
            self.commit()
        else:  # pragma: no cover - planner/executor version skew
            raise TypeError(f"unknown op {op!r}")

    def commit(self) -> None:
        for entry in self.staged:
            assert not entry.pending, \
                "staged D2H committed before its Decompress"
            jax.block_until_ready(entry.rows)
        for entry in self.staged:
            rows = np.asarray(entry.rows)
            if entry.codec is not None:
                # the wire round trip: device-side encode, host-side decode
                codec = get_codec(entry.codec)
                rows = codec.decode(codec.encode(rows), rows.shape, rows.dtype)
            self.host[entry.box.slices()] = rows
        self.staged.clear()


class _LoweredExecutorBase:
    """Shared compile-then-run machinery for the device executors.

    Re-entrant: ``execute`` may be called from several threads at once
    (the serving layer compiles/admits jobs concurrently).  The lowering
    memo is a keyed, locked cache; ``exec_stats`` is thread-local on
    read (each thread sees its own last run) with a cross-thread
    fallback to the most recent run, which preserves the single-threaded
    ``executor.exec_stats`` idiom everywhere else."""

    name = "base"
    _pipeline = False
    _MEMO_CAP = 64   # FIFO bound on retained (plan -> CompiledPlan) entries

    def __init__(self, fused_step: Optional[FusedStep] = None,
                 policy=None, lowered: bool = True, slot_pool=None):
        self.fused_step = fused_step
        self.policy = policy
        self.lowered = lowered
        # kernel-signature cache shared across execute() calls: re-running
        # a plan (or one with the same shape buckets) is all hits
        self.kernel_cache = KernelCache()
        # optional shared SlotPool: device storage leased per run and
        # returned after commit instead of allocated per CompiledPlan
        self.slot_pool = slot_pool
        # keyed lowering memo: id(plan) -> (plan, fused_step, policy,
        # compiled).  Holding the plan keeps id()/`is` identity sound, and
        # comparing the fused_step/policy snapshot invalidates an entry if
        # either public attribute was swapped between runs.
        self._lowered_memo: Dict[int, tuple] = {}
        self._memo_lock = threading.Lock()
        self._tls = threading.local()
        self._last_stats: Optional[ExecStats] = None

    @property
    def exec_stats(self) -> Optional[ExecStats]:
        stats = getattr(self._tls, "stats", None)
        return stats if stats is not None else self._last_stats

    @exec_stats.setter
    def exec_stats(self, value: Optional[ExecStats]) -> None:
        self._tls.stats = value
        self._last_stats = value

    def _compiled(self, plan: ExecutionPlan):
        key = id(plan)
        fused_step, policy = self.fused_step, self.policy
        with self._memo_lock:
            memo = self._lowered_memo.get(key)
            if (memo is not None and memo[0] is plan
                    and memo[1] is fused_step and memo[2] == policy):
                return memo[3]
        # lower outside the lock: the KernelCache is itself thread-safe,
        # so a racing duplicate lower() costs hits, not recompiles
        compiled = lower(plan, policy=policy, fused_step=fused_step,
                         kernel_cache=self.kernel_cache)
        with self._memo_lock:
            if key not in self._lowered_memo and \
                    len(self._lowered_memo) >= self._MEMO_CAP:
                self._lowered_memo.pop(next(iter(self._lowered_memo)))
            self._lowered_memo[key] = (plan, fused_step, policy, compiled)
        return compiled

    supports_injection = True

    def execute(self, plan: ExecutionPlan, x: np.ndarray,
                injector=None, retry=None, on_commit=None,
                ) -> Tuple[np.ndarray, TransferStats]:
        """Run a plan.  ``injector``/``retry``/``on_commit`` thread the
        fault-injection and checkpoint hooks through to
        :meth:`repro.core.lower.CompiledPlan.execute`; they require the
        lowered path (the legacy op-at-a-time interpreter has no op
        sites to consult)."""
        if self.lowered:
            host, stats, exec_stats = self._compiled(plan).execute(
                x, pipeline=self._pipeline, slot_pool=self.slot_pool,
                injector=injector, retry=retry, on_commit=on_commit)
            exec_stats.executor = self.name
            self.exec_stats = exec_stats
            return host, stats
        if injector is not None or retry is not None or on_commit is not None:
            raise ValueError(
                "fault injection / commit hooks require the lowered "
                "executor path (lowered=True)")
        host, stats = self._execute_legacy(plan, x)
        self.exec_stats = None
        return host, stats

    def _execute_legacy(self, plan, x):
        raise NotImplementedError


class EagerExecutor(_LoweredExecutorBase):
    """In-order interpreter: one stage program at a time, plan order."""

    name = "eager"
    _pipeline = False

    def _execute_legacy(self, plan, x):
        state = _DeviceState(validate_domain(plan, x), self.fused_step)
        for op in plan.ops:
            state.issue(op)
        state.commit()   # no-op unless a planner forgot the final barrier
        return state.host, plan.stats()


class DoubleBufferedExecutor(_LoweredExecutorBase):
    """Software-pipelined interpreter (the paper's multi-stream overlap).

    Walks the plan stage-by-stage (one stage per ``(round, chunk)``).
    Before executing stage ``i``'s kernels it issues every H2D of stage
    ``i+1`` — legal because H2D only reads committed host rows and
    commits are stage-group barriers — so the next chunk's transfer rides
    under the current chunk's kernel work exactly like the paper's
    ``N_strm = 3`` double buffering.  Correctness is untouched: data
    dependencies flow through registers/buffers, which prefetching never
    reorders.
    """

    name = "double_buffered"
    _pipeline = True

    def _execute_legacy(self, plan, x):
        state = _DeviceState(validate_domain(plan, x), self.fused_step)
        stages = plan.stages()
        prefetched: set = set()
        for j, (key, ops) in enumerate(stages):
            if key is None:          # HostCommit barrier
                for op in ops:
                    state.issue(op)
                continue
            # prefetch the next chunk's H2D — and the host-side Compress
            # feeding it — before touching this chunk's kernels; stop at
            # barriers (host rows change there)
            if j + 1 < len(stages) and stages[j + 1][0] is not None:
                for nxt in stages[j + 1][1]:
                    if isinstance(nxt, H2D) or (
                            isinstance(nxt, Compress) and nxt.direction == "h2d"):
                        state.issue(nxt)
                        prefetched.add(id(nxt))
            for op in ops:
                if id(op) in prefetched:
                    continue
                state.issue(op)
        state.commit()
        return state.host, plan.stats()


class DryRunExecutor:
    """Zero-device-work interpreter: the plan *is* the result.

    Used by :mod:`repro.core.autotune` to cost the full configuration
    sweep and by ``benchmarks/run.py --dry-run`` to exercise plan
    construction for every engine without allocating a single device
    array.  Accepts both single-device :class:`ExecutionPlan` and
    multi-device :class:`~repro.core.plan.ShardedPlan` schedules — in
    both cases the accounting is a property of the plan, so a sharded
    plan's ICI/wedge costs are known with zero devices."""

    name = "dry_run"

    def execute(self, plan,
                x: Optional[np.ndarray] = None) -> Tuple[None, TransferStats]:
        return None, plan.stats()


class ShardedSimExecutor:
    """Single-device lockstep simulator for sharded plans.

    Lowers the per-rank op streams through
    :func:`repro.core.lower.lower_sharded` (slot-bound closures, shared
    halo mailbox, one cached kernel signature for every rank x round)
    and walks the global phases in barrier order.  Differentially tested
    against the ``shard_map`` oracle: results match
    :func:`repro.core.distributed.run_distributed` to float tolerance
    with zero real devices, which is what lets CI exercise multi-chip
    schedules on a CPU container.

    Hierarchical plans (:mod:`repro.core.hierarchy`) run through the
    same entry point: the lowering layer expands each ShardKernel into
    its rank's nested stage program, and ``slot_pool`` (optional, shared
    with the serving layer) supplies the chunk-slot storage those inner
    programs lease per round."""

    name = "sharded_sim"
    supports_injection = True

    def __init__(self, slot_pool=None, kernel_cache=None):
        self.kernel_cache = kernel_cache if kernel_cache is not None \
            else KernelCache()
        self.slot_pool = slot_pool
        self.exec_stats: Optional[ExecStats] = None
        self._lowered_memo = None

    def _compiled(self, plan):
        memo = self._lowered_memo
        if memo is not None and memo[0] is plan:
            return memo[1]
        compiled = lower_sharded(plan, kernel_cache=self.kernel_cache)
        self._lowered_memo = (plan, compiled)
        return compiled

    def execute(self, plan, x: np.ndarray,
                injector=None, retry=None, on_commit=None,
                ) -> Tuple[np.ndarray, TransferStats]:
        host, stats, exec_stats = self._compiled(plan).execute(
            x, injector=injector, retry=retry, slot_pool=self.slot_pool)
        exec_stats.executor = self.name
        self.exec_stats = exec_stats
        if on_commit is not None:
            # a sharded plan stores host state once, at the end: its
            # whole run is one commit of the final round
            on_commit(plan.rounds - 1, host)
        return host, stats


class ShardMapExecutor:
    """Multi-device backend: run a sharded plan through the
    ``shard_map``/``ppermute`` program in :mod:`repro.core.distributed`.

    The plan carries the whole geometry (mesh shape, k_ici, stencil, n),
    so ``execute(plan, x)`` needs no configuration beyond an optional
    explicit mesh — by default a ``plan.mesh_shape`` mesh is built from
    the visible devices.  Stats are the plan-derived accounting, same as
    every other executor.

    Hierarchical and halo-compressed plans dispatch on their *outer
    geometry*: the backend runs one fused shard_map program per round,
    so the nested chunking and the codec round trip are sim-only
    refinements — each device holds its full band (valid when the real
    device fits it) and halos cross ``ppermute`` raw.  Stats still
    report the plan's own two-level/wire accounting."""

    name = "shard_map"

    def __init__(self, mesh=None, row_axis: str = "data",
                 col_axis: str = "model"):
        self.mesh = mesh
        self.row_axis = row_axis
        self.col_axis = col_axis
        self.exec_stats: Optional[ExecStats] = None

    def execute(self, plan,
                x: np.ndarray) -> Tuple[np.ndarray, TransferStats]:
        import time

        from .distributed import execute_sharded_plan

        t0 = time.perf_counter()
        out = np.asarray(execute_sharded_plan(plan, x, mesh=self.mesh,
                                              row_axis=self.row_axis,
                                              col_axis=self.col_axis))
        # the backend runs one fused shard_map program, not per-op
        # closures: no per-op wall clock or cache counters to report
        self.exec_stats = ExecStats(
            executor=self.name, kernel_impl="shard_map",
            kernel_calls=plan.n_ranks * plan.rounds,
            stage_count=len(plan.barriers),
            wall_s=time.perf_counter() - t0)
        return out, plan.stats()


EXECUTORS = {e.name: e for e in
             (EagerExecutor, DoubleBufferedExecutor, DryRunExecutor,
              ShardedSimExecutor, ShardMapExecutor)}

# executors that interpret single-device ExecutionPlans (what
# benchmarks.run --exec sweeps); the sharded ones take a ShardedPlan
PLAN_EXECUTORS = ("eager", "double_buffered")


def get_executor(name: str, fused_step: Optional[FusedStep] = None,
                 policy=None):
    try:
        cls = EXECUTORS[name]
    except KeyError:
        raise KeyError(f"unknown executor {name!r}; known: {sorted(EXECUTORS)}")
    if cls in (DryRunExecutor, ShardedSimExecutor, ShardMapExecutor):
        if fused_step is not None or policy is not None:
            raise ValueError(
                f"executor {name!r} takes no fused_step/policy — it never "
                "dispatches single-device FusedKernel ops")
        return cls()
    return cls(fused_step, policy=policy)
