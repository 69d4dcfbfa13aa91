"""Plan lowering: stage programs, slot-bound closures, shape-bucketed cache.

This module compiles an :class:`~repro.core.plan.ExecutionPlan` once, so
that running it needs no per-op type dispatch and one kernel signature
serves every chunk and round of a shape bucket, and runs it:

* **stage programs** — :func:`lower` groups ops into per-``(round,
  chunk)`` stages of *pre-bound closures*: register/buffer names are
  resolved to integer slots, slice bounds and codec objects are baked
  into each closure, and per-op type dispatch disappears from the
  execution loop (:meth:`SpanRecorder.run` walks ``for tag, fn, rnd,
  chunk in stage``; the trailing site pair addresses fault injection and
  labels the op's profiler span).
* **one stage walker** — :func:`walk_stages` runs a schedule of
  ``(run, stage)`` entries, in order or with the next stage's transfers
  prefetched under this stage's kernels: one plan's stages for the
  executors (:meth:`CompiledPlan.execute`), several plans' stages merged
  for the service (:func:`repro.serve.scheduler.run_interleaved`).
* **kernel dispatch** — FusedKernel ops are resolved through the
  registry in :mod:`repro.kernels.dispatch` (reference jnp, Pallas,
  DMA-overlapped Pallas, banded-MXU) exactly once at lowering time.
* **shape bucketing** — band heights are padded up to per-plan buckets
  (one bucket per ``(stencil, steps, keep_top, keep_bottom)`` group, the
  group's max height) so all chunks and rounds share one compiled kernel
  signature.  Padding is on the frame-free side and the output is sliced
  back to the true height, so results are bit-identical: a valid output
  row never reads a pad row (output row ``i`` depends on input rows
  ``[i - m*r, i + m*r]`` intersected with the band).  Bands framed on
  both sides (``keep_top and keep_bottom``) are never padded.
* **compilation cache** — a :class:`KernelCache` keyed by
  ``(impl, stencil, steps, keeps, bucket_height, width, itemsize)``
  counts distinct signatures; hits/misses surface in :class:`ExecStats`
  alongside wall-clock per span name.  The d=8, 4-round SO2DR config
  compiles at most one kernel per shape bucket instead of one per
  chunk x round.
* **streamed write-back** — a large D2H box that no later host read of
  its round touches is pulled and scattered into the host array by a
  small write-back pool as soon as it is ready, under the next chunks'
  kernels; the round's barrier then waits only for what is still in
  flight and for the boxes held back (:func:`_streamed_d2h`).
* **background domain copy** — the run's host array is filled from the
  caller's domain in row bands on a thread of its own, under the
  device's work; reads before the first barrier go to the caller's
  array, and a write-back or a barrier waits for the rows it needs
  (:class:`_HostCopy`).
* **spans** — one :class:`SpanRecorder` per run times every bound op
  (and every phase of the fused step, the barrier and a write-back)
  into :class:`ExecStats` and writes each as a flat profiler host event
  carrying ``run``, ``round`` and ``chunk``, on the device trace's clock.

Accounting is untouched: :meth:`CompiledPlan.execute` still returns the
plan-derived :class:`~repro.core.plan.TransferStats`, so dry-run numbers,
autotune sweeps, and the CI bench-gate see identical bytes whether or
not a plan is lowered.
"""
from __future__ import annotations

import bisect
import dataclasses
import itertools
import threading
import time
from collections import defaultdict
from concurrent import futures
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .compress import get_codec
from .faults import InjectedFault, consult
from .plan import (
    Box, BufferRead, BufferWrite, Compress, D2H, Decompress, ExecutionPlan,
    FusedKernel, H2D, HaloCompress, HaloDecompress, HaloRecv, HaloSend,
    HostCommit, ShardKernel, ShardLoad, ShardStore, ShardedPlan,
    TransferStats,
)
from .recovery import PlanExecutionError, plan_fingerprint

__all__ = [
    "ExecStats", "KernelCache", "BucketRegistry", "SlotPool",
    "CompiledPlan", "LoweredStage", "PlanRun", "SpanRecorder", "lower",
    "walk_stages",
    "CompiledShardedPlan", "ShardStage", "lower_sharded",
    "check_domain",
]

# op-class tags: a bound op's first field; OP_TAGS[tag] names its span
OP_TAGS = ("H2D", "D2H", "BufferWrite", "BufferRead", "FusedKernel",
           "HostCommit", "Compress", "Decompress",
           "ShardLoad", "ShardStore", "HaloSend", "HaloRecv", "ShardKernel",
           "HaloCompress", "HaloDecompress")
_TAG = {name: i for i, name in enumerate(OP_TAGS)}

# (tag, closure over the runtime, round, chunk) — the trailing site pair
# is the fault-injection address: repro.core.faults consults it before
# the closure runs, so an injected fault never leaves a half-executed op
BoundOp = Tuple[int, Callable, int, int]

# op classes whose closures open their own phase spans (pad/call/crop,
# drain/pull/scatter): timed under the class name with no span of their own
_PHASED = frozenset((_TAG["FusedKernel"], _TAG["HostCommit"]))
# one per process, so no two runs in one trace share a ``run`` id
_RUN_IDS = itertools.count(1)
# write-back pool threads per run: one box of a 49152^2 box2d1r solve
# takes about 2.8 s to pull and scatter on a v5e host, a chunk's kernels
# about 1.6 s, so two keep up with the device
WRITEBACK_THREADS = 2
# smallest D2H box worth a write-back thread: below about a MiB, handing
# the box to the pool and waking the barrier cost more than the pull and
# scatter it would hide (a 2-round SO2DR solve on the CPU backend: 3 KB
# and 88 KB boxes slower streamed, 1 MiB even, 4 MiB faster)
STREAM_MIN_BYTES = 1 << 20
# the domain's copy into the run's host array goes in bands of about
# this many bytes, lowest rows first, on one thread under the device's
# work: the copy into fresh pages is bound by first-touch page faults
# (about 0.9 GB/s on a v5e host), so a 9.66 GB domain took 11 s before
# the first transfer; a domain of one band or less is copied inline
HOST_COPY_BAND_BYTES = 64 << 20


@dataclasses.dataclass
class ExecStats:
    """Execution-side counters (wall clock + compilation cache), the
    companion of the plan-side :class:`~repro.core.plan.TransferStats`.

    ``op_counts``/``op_wall_s`` are keyed by span name
    (:class:`SpanRecorder`): every op class, plus the phases
    ``FusedKernel.{pad,call,crop}``, ``HostCommit.drain``,
    ``D2H.{wait,pull,decode,scatter}`` (``D2H.wait`` on streamed boxes
    only; their spans are timed on the write-back threads),
    ``Execute.validate`` (the domain's check, before ``wall_s``),
    ``HostCopy.band`` (one band of the domain's copy, on its own thread)
    and ``HostCopy.wait`` (a write or a barrier waiting for the copy,
    inside ``wall_s``).  Wall-clock numbers are
    host-observed dispatch+compute time — meaningful for comparing
    executors/kernels on one machine, never for gating CI (the cache/op
    counters are the deterministic part)."""

    executor: str = ""
    kernel_impl: str = ""
    op_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    op_wall_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    kernel_calls: int = 0
    shape_buckets: int = 0         # distinct kernel signatures after bucketing
    kernel_compiles: int = 0       # cache misses this run (new signatures)
    kernel_cache_hits: int = 0
    stage_count: int = 0
    lower_s: float = 0.0
    wall_s: float = 0.0
    faults_injected: int = 0       # injected faults hit this run
    retries: int = 0               # transient faults absorbed by backoff
    resumes: int = 0               # checkpoint resumes (recovery loop)
    modeled_s: Optional[float] = None     # Sec. III prediction for this run
    model_error: Optional[float] = None   # (modeled_s - wall_s) / wall_s

    def __post_init__(self):
        # plain attribute, not a dataclass field: asdict/== never see it
        self._lock = threading.Lock()

    @property
    def kernel_cache_misses(self) -> int:
        return self.kernel_compiles

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["kernel_cache_misses"] = self.kernel_compiles
        return d

    def merge(self, other: "ExecStats") -> "ExecStats":
        """Accumulate another run's counters and stage timers into this
        one, thread-safely — the aggregation a long-lived service does as
        concurrent jobs complete.  Counters and wall clocks sum;
        ``shape_buckets``/``stage_count`` sum per-run values (a shared
        signature counts once per run that used it); identity fields keep
        the first non-empty value."""
        with self._lock:
            for k, v in other.op_counts.items():
                self.op_counts[k] = self.op_counts.get(k, 0) + v
            for k, v in other.op_wall_s.items():
                self.op_wall_s[k] = self.op_wall_s.get(k, 0.0) + v
            self.kernel_calls += other.kernel_calls
            self.shape_buckets += other.shape_buckets
            self.kernel_compiles += other.kernel_compiles
            self.kernel_cache_hits += other.kernel_cache_hits
            self.stage_count += other.stage_count
            self.lower_s += other.lower_s
            self.wall_s += other.wall_s
            self.faults_injected += other.faults_injected
            self.retries += other.retries
            self.resumes += other.resumes
            self.executor = self.executor or other.executor
            self.kernel_impl = self.kernel_impl or other.kernel_impl
            if other.modeled_s is not None:
                self.modeled_s = (self.modeled_s or 0.0) + other.modeled_s
            if self.modeled_s is not None and self.wall_s > 0:
                self.model_error = ((self.modeled_s - self.wall_s)
                                    / self.wall_s)
        return self


class SpanRecorder:
    """Host seconds and a count per span name for one run of a lowered
    plan, each span also a profiler host event
    (``jax.profiler.TraceAnnotation``: on the device trace's clock, and
    almost free while no profiler runs).

    Spans are leaves: each wraps one host action and none encloses
    another, so a trace reader that names an idle stretch of the device
    by the host event overlapping it most lands on the action, not on an
    enclosing phase.  An op class in ``phased`` is timed and counted
    under its class name with no event of its own; its closure opens
    phase spans through :meth:`span` instead.  Every event carries
    ``run`` (one id per recorder, i.e. per solve), ``round``, the op's
    site under ``site`` (``chunk``, or ``rank`` on sharded plans) and
    any fixed ``meta`` (``job`` in the interleaved scheduler)."""

    __slots__ = ("site", "phased", "meta", "wall", "count")

    def __init__(self, site: str = "chunk", phased: frozenset = _PHASED,
                 **meta):
        self.site = site
        self.phased = phased
        self.meta = {"run": next(_RUN_IDS), "round": -1, site: -1, **meta}
        self.wall: Dict[str, float] = defaultdict(float)
        self.count: Dict[str, int] = defaultdict(int)

    def at(self, rnd: int, site: int) -> None:
        self.meta["round"] = rnd
        self.meta[self.site] = site

    def span(self, name: str) -> "_Span":
        """A context manager: one span named ``name`` around its body."""
        return _Span(self, name)

    def run(self, ops: Tuple[BoundOp, ...], rt, injector=None,
            retry=None) -> None:
        """Run bound ops against runtime ``rt``, each timed under its op
        class, consulting ``injector`` first when one is given."""
        perf = time.perf_counter
        for tag, fn, rnd, site in ops:
            name = OP_TAGS[tag]
            if injector is not None:
                consult(injector, retry, rnd, site, name)
            self.at(rnd, site)
            if tag in self.phased:
                t0 = perf()
                fn(rt)
                self.wall[name] += perf() - t0
                self.count[name] += 1
            else:
                with _Span(self, name):
                    fn(rt)

    def fork(self, rnd: int, site: int) -> "SpanRecorder":
        """A recorder for work on another thread: the same ``run`` and
        fixed meta, pinned at ``(rnd, site)``, with tallies of its own
        (merged back by :meth:`absorb` on the issuing thread)."""
        child = SpanRecorder.__new__(SpanRecorder)
        child.site, child.phased = self.site, self.phased
        child.meta = {**self.meta, "round": rnd, self.site: site}
        child.wall = defaultdict(float)
        child.count = defaultdict(int)
        return child

    def absorb(self, other: "SpanRecorder") -> None:
        """Add a forked recorder's seconds and counts to this one's."""
        for k, v in other.wall.items():
            self.wall[k] += v
        for k, v in other.count.items():
            self.count[k] += v


class _Span:
    """One leaf span of a :class:`SpanRecorder` (a class, not a
    generator: about a microsecond cheaper per span)."""

    __slots__ = ("rec", "name", "ann", "t0")

    def __init__(self, rec: SpanRecorder, name: str):
        self.rec, self.name = rec, name

    def __enter__(self) -> None:
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.rec.meta)
        self.ann.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        self.rec.wall[self.name] += dt
        self.rec.count[self.name] += 1


class KernelCache:
    """Keyed compilation cache for fused-kernel callables.

    One entry per kernel *signature* ``(impl, stencil, steps, keep_top,
    keep_bottom, bucket_height, width, itemsize)`` — the same key set
    JAX's jit cache traces on, so ``misses`` counts actual retraces and
    ``hits`` counts dispatches that reuse a compiled kernel.  Executors
    hold one cache across ``execute()`` calls, so re-running a plan (or
    running another plan with the same buckets) is all hits.

    Thread-safe: a service shares one warm cache across concurrent jobs,
    and CI gates on the hit/miss counters, so lookups (including the
    ``make`` call on a miss) run under a lock — a signature is compiled
    and counted exactly once no matter how many jobs race to it."""

    def __init__(self):
        self._entries: Dict[tuple, Callable] = {}
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0

    def lookup(self, key: tuple, make: Callable[[], Callable]) -> Callable:
        with self._lock:
            fn = self._entries.get(key)
            if fn is None:
                self.misses += 1
                fn = self._entries[key] = make()
            else:
                self.hits += 1
            return fn

    def snapshot(self) -> Tuple[int, int]:
        """Atomic ``(hits, misses)`` read — per-job compile attribution
        in a shared-cache service needs both counters from one instant."""
        with self._lock:
            return self.hits, self.misses

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


class BucketRegistry:
    """Cross-plan shape buckets: the service-lifetime companion of the
    per-plan bucketing pass.

    Maps a kernel group ``(stencil, steps, keep_top, keep_bottom, width,
    itemsize)`` to the band heights already compiled for it.  When
    :func:`lower` routes a plan through a registry, each group's padded
    height becomes the smallest registered bucket that fits (registering
    a new one only when none does), so a job with an *unseen shape* whose
    bands fit existing buckets presents zero new kernel signatures to a
    warm :class:`KernelCache` — the shape-bucketing pass amortized across
    jobs instead of within one.  Padding stays on the frame-free side,
    so results remain bit-identical (both-sides-framed groups never
    reach the registry).  Thread-safe."""

    def __init__(self):
        self._heights: Dict[tuple, List[int]] = {}
        self._lock = threading.Lock()

    def resolve(self, group: tuple, height: int) -> int:
        """Smallest registered bucket >= ``height`` for ``group``; when
        none fits, ``height`` is registered as a new bucket."""
        with self._lock:
            heights = self._heights.setdefault(group, [])
            i = bisect.bisect_left(heights, height)
            if i < len(heights):
                return heights[i]
            heights.insert(i, height)
            return height

    def __len__(self) -> int:
        """Total registered buckets (over all groups)."""
        with self._lock:
            return sum(len(v) for v in self._heights.values())


class SlotPool:
    """Device buffer-slot storage shared and reused across compiled plans.

    A long-lived service owns one pool for its lifetime: every job leases
    register/buffer slot storage when its runtime is built and releases
    it when the job retires, so steady-state serving re-allocates no slot
    storage per job (``reuses``/``peak_in_use`` make that observable).
    Leases are exclusive — concurrent jobs each hold their own storage —
    and release clears every slot so no device buffer outlives its job.
    Thread-safe."""

    def __init__(self):
        self._free: List[Tuple[List, List]] = []
        self._lock = threading.Lock()
        self.leases = 0
        self.reuses = 0
        self.in_use = 0
        self.peak_in_use = 0

    def acquire(self, n_regs: int, n_bufs: int) -> Tuple[List, List]:
        with self._lock:
            self.leases += 1
            if self._free:
                self.reuses += 1
                regs, bufs = self._free.pop()
            else:
                regs, bufs = [], []
            self.in_use += 1
            self.peak_in_use = max(self.peak_in_use, self.in_use)
        if len(regs) < n_regs:
            regs.extend([None] * (n_regs - len(regs)))
        if len(bufs) < n_bufs:
            bufs.extend([None] * (n_bufs - len(bufs)))
        return regs, bufs

    def release(self, regs: List, bufs: List) -> None:
        for i in range(len(regs)):
            regs[i] = None
        for i in range(len(bufs)):
            bufs[i] = None
        with self._lock:
            self._free.append((regs, bufs))
            self.in_use -= 1

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"leases": self.leases, "reuses": self.reuses,
                    "in_use": self.in_use, "peak_in_use": self.peak_in_use}

    def assert_balanced(self) -> None:
        """Raise if any lease is still outstanding.

        The audit hook for quiescent points (end of a job, service
        drain): every ``acquire`` must have been paired with a
        ``release`` — including on exception paths, where the lowered
        executors release in ``finally`` — so a non-zero ``in_use`` here
        is a leaked lease, i.e. device slot storage pinned by a job that
        already retired."""
        with self._lock:
            if self.in_use != 0:
                raise AssertionError(
                    f"slot pool unbalanced: {self.in_use} lease(s) "
                    f"outstanding ({self.leases} acquired, "
                    f"{self.leases - self.in_use} released)")


class _HostCopy:
    """The copy of the caller's domain ``src`` into the run's host array
    ``dst``, in bands of ``band_rows`` rows along axis 0, lowest first,
    on one thread, each band a ``HostCopy.band`` span on ``rec`` (a
    forked recorder).  ``rows`` is the watermark: the rows copied so
    far.  :meth:`stop` drops the bands not yet started; the running one
    finishes."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, band_rows: int,
                 rec: SpanRecorder):
        self.src, self.dst, self.band_rows, self.rec = src, dst, band_rows, rec
        self.rows = 0
        self.error: Optional[BaseException] = None
        self.stopped = False
        self.cond = threading.Condition()
        self.thread = threading.Thread(target=self._run, name="hostcopy",
                                       daemon=True)
        self.thread.start()

    def _copy(self, lo: int, hi: int) -> None:
        self.dst[lo:hi] = self.src[lo:hi]

    def _run(self) -> None:
        n = self.src.shape[0]
        try:
            for lo in range(0, n, self.band_rows):
                if self.stopped:
                    return
                hi = min(lo + self.band_rows, n)
                with self.rec.span("HostCopy.band"):
                    self._copy(lo, hi)
                with self.cond:
                    self.rows = hi
                    self.cond.notify_all()
        except Exception as e:      # raised to the waiters
            with self.cond:
                self.error = e
                self.cond.notify_all()

    def wait(self, row: int, rec: SpanRecorder) -> None:
        """Return once rows ``[0, row)`` are copied, having waited (if at
        all) in a ``HostCopy.wait`` span on ``rec``; raise if the copy
        failed or was stopped short of ``row``."""
        if self.rows >= row:
            return
        with rec.span("HostCopy.wait"):
            with self.cond:
                while (self.rows < row and self.error is None
                       and not self.stopped):
                    self.cond.wait()
        if self.error is not None:
            raise RuntimeError("host domain copy failed") from self.error
        if self.rows < row:
            raise RuntimeError("host domain copy stopped")

    def stop(self) -> None:
        with self.cond:
            self.stopped = True
            self.cond.notify_all()
        self.thread.join()


def _start_copy(src: np.ndarray, rec: SpanRecorder,
                ) -> Tuple[np.ndarray, Optional[_HostCopy]]:
    """A fresh host array for a run on ``src`` and the copy that fills
    it: in bands on a thread of its own, or inline (no copy returned)
    where ``src`` holds one band or less."""
    if src.nbytes <= HOST_COPY_BAND_BYTES:
        return src.copy(), None
    host = np.empty_like(src, order="C")
    band_rows = max(1, HOST_COPY_BAND_BYTES * src.shape[0] // src.nbytes)
    return host, _HostCopy(src, host, band_rows, rec.fork(-1, -1))


def _pull_scatter(rt: "_Runtime", sl, rows, codec_name: Optional[str],
                  rec: SpanRecorder) -> None:
    """Pull one staged box over the link, decode it if a codec carried
    it, and scatter it into ``rt``'s host array once the domain's copy
    has passed its rows, each phase a span on ``rec``."""
    with rec.span("D2H.pull"):
        rows = np.asarray(rows)
    if codec_name is not None:
        # the wire round trip: device-side encode, host-side decode
        with rec.span("D2H.decode"):
            codec = get_codec(codec_name)
            rows = codec.decode(codec.encode(rows), rows.shape, rows.dtype)
    copy = rt.copy
    if copy is not None:
        # the copy must not land on the box after it
        copy.wait(sl[0].stop, rec)
    with rec.span("D2H.scatter"):
        rt.host[sl] = rows


def _write_back(rt: "_Runtime", sl, rows, codec_name: Optional[str],
                rec: SpanRecorder) -> None:
    """A streamed box's write-back, on a pool thread: wait for the box on
    the device (``D2H.wait``), then pull, decode and scatter it.  The
    device array and its pulled copy die with this call."""
    with rec.span("D2H.wait"):
        jax.block_until_ready(rows)
    _pull_scatter(rt, sl, rows, codec_name, rec)


class _Runtime:
    """Slot-indexed register/buffer/staging state the bound closures run
    against.

    Write-back: a D2H box that the plan lets stream (:func:`_streamed_d2h`)
    is committed as soon as it is staged, to a pool of
    ``WRITEBACK_THREADS`` threads that write it into the host array
    under the next chunks' kernels; a held box waits for the round's
    barrier, as does the barrier for every write-back of the round.

    Domain copy: while ``copy`` (a :class:`_HostCopy`) fills ``host``,
    host reads go to ``src``, the caller's domain, and a scatter waits
    for the copy to pass its rows.  Before the first barrier no read
    sees a row that a write-back has written (:func:`_streamed_d2h`), so
    the two arrays agree wherever a read looks; the first barrier waits
    for the whole copy and points ``src`` at ``host``."""

    __slots__ = ("host", "src", "copy", "regs", "bufs", "staged",
                 "staged_sites", "held", "pool", "writebacks", "wire",
                 "on_commit", "committed_round", "spans")

    def __init__(self, host: np.ndarray, n_regs: int, n_bufs: int,
                 regs: Optional[List] = None, bufs: Optional[List] = None,
                 spans: Optional[SpanRecorder] = None,
                 src: Optional[np.ndarray] = None,
                 copy: Optional[_HostCopy] = None):
        self.host = host
        self.src = src if copy is not None else host
        self.copy = copy
        self.spans = spans if spans is not None else SpanRecorder()
        # recovery hooks: the newest round whose barrier fully drained
        # (-1 = none), and an optional per-round checkpoint callback
        self.on_commit: Optional[Callable[[int, np.ndarray], None]] = None
        self.committed_round = -1
        # storage may be leased from a SlotPool (possibly longer than
        # needed — closures only ever index their bound slots)
        self.regs: List = regs if regs is not None else [None] * n_regs
        self.bufs: List = bufs if bufs is not None else [None] * n_bufs
        # D2H boxes being committed: (host slice tuple, device payload,
        # codec|None), and the (round, chunk, streams) of each; boxes held
        # for the barrier as (box, site) pairs
        self.staged: List[tuple] = []
        self.staged_sites: List[tuple] = []
        self.held: List[tuple] = []
        # the write-back pool (made on the first streamed box) and its
        # write-backs in flight: (future, forked SpanRecorder)
        self.pool: Optional[ThreadPoolExecutor] = None
        self.writebacks: List[tuple] = []
        # reg slot -> (payload, shape, dtype) between a non-identity
        # Compress(h2d) and its Decompress
        self.wire: Dict[int, tuple] = {}

    def stage(self, box: tuple, site: tuple) -> None:
        """A D2H box leaves its register: committed at once if it streams
        (``site[2]``), else held for the barrier."""
        if site[2]:
            self.staged.append(box)
            self.staged_sites.append(site)
            self.commit()
        else:
            self.held.append((box, site))

    def commit(self) -> None:
        """Commit the staged boxes: a streamed box goes to the write-back
        pool, which waits for it on the device; a held box (staged by
        :meth:`barrier` once the device is drained) is pulled and
        scattered here, each phase its own span."""
        sp = self.spans
        for (sl, rows, codec_name), (rnd, chunk, streams) in zip(
                self.staged, self.staged_sites):
            if streams:
                if self.pool is None:
                    self.pool = ThreadPoolExecutor(
                        WRITEBACK_THREADS, thread_name_prefix="writeback")
                rec = sp.fork(rnd, chunk)
                self.writebacks.append((self.pool.submit(
                    _write_back, self, sl, rows, codec_name, rec), rec))
            else:
                sp.at(rnd, chunk)
                _pull_scatter(self, sl, rows, codec_name, sp)
        self.staged.clear()
        self.staged_sites.clear()

    def barrier(self) -> None:
        """Write back every box not yet in the host array, and complete
        the domain's copy.  ``HostCommit.drain`` waits, on this thread,
        for the held boxes on the device and for the write-backs in
        flight (raising the first one's exception); ``HostCopy.wait``
        then waits for the rest of the copy, and the held boxes are
        committed here."""
        if self.held or self.writebacks:
            for box, site in self.held:
                self.staged.append(box)
                self.staged_sites.append(site)
            self.held.clear()
            with self.spans.span("HostCommit.drain"):
                for _, rows, _ in self.staged:
                    jax.block_until_ready(rows)
                done, self.writebacks = self.writebacks, []
                futures.wait([f for f, _ in done])
                for _, rec in done:
                    self.spans.absorb(rec)
                for f, _ in done:
                    f.result()
        copy = self.copy
        if copy is not None:
            copy.wait(self.host.shape[0], self.spans)
            copy.thread.join()
            self.spans.absorb(copy.rec)
            self.copy = None
            self.src = self.host
        self.commit()

    def commit_round(self, rnd: int) -> None:
        """A round's HostCommit barrier: write back the round's boxes,
        record the round as the recovery point, fire the checkpoint hook
        (the host array is the complete machine state here — nothing
        else survives a barrier)."""
        self.barrier()
        self.committed_round = rnd
        if self.on_commit is not None:
            self.on_commit(rnd, self.host)

    def close(self) -> None:
        """Stop the domain's copy (bands not yet started are dropped; a
        write-back waiting for one raises) and the write-back pool
        (write-backs not yet started are cancelled, running ones finish)
        and drop every staged box, so no thread and no device buffer
        outlives the run."""
        if self.copy is not None:
            self.copy.stop()
            self.copy = None
        if self.pool is not None:
            self.pool.shutdown(wait=True, cancel_futures=True)
            self.pool = None
        self.writebacks.clear()
        self.staged.clear()
        self.staged_sites.clear()
        self.held.clear()


@dataclasses.dataclass(frozen=True)
class LoweredStage:
    """One pipeline stage: all bound ops in plan order, pre-split into
    the prefetchable prefix (H2D + host-side Compress — ops that only
    read committed host rows and write fresh slots) and the rest."""

    key: Optional[Tuple[int, int]]      # (round, chunk); None = barrier
    ops: Tuple[BoundOp, ...]
    prefetch: Tuple[BoundOp, ...]
    rest: Tuple[BoundOp, ...]


def check_domain(plan, x: np.ndarray) -> None:
    """Raise if a host domain does not match the plan geometry.

    Shared by every executor entry point (including the shard_map
    backend, which needs no mutable copy), so all backends reject
    identically by construction."""
    if tuple(x.shape) != tuple(plan.shape):
        raise ValueError(f"domain {x.shape} does not match plan "
                         f"{tuple(plan.shape)}")
    if x.dtype.itemsize != plan.itemsize:
        raise ValueError(f"dtype itemsize {x.dtype.itemsize} does not match "
                         f"plan itemsize {plan.itemsize}")


def _noop(rt) -> None:
    return None


@dataclasses.dataclass
class CompiledPlan:
    """A lowered :class:`ExecutionPlan`: stage programs of slot-bound
    closures plus the kernel-signature cache they dispatch through."""

    plan: ExecutionPlan
    stages: Tuple[LoweredStage, ...]
    n_reg_slots: int
    n_buf_slots: int
    kernel_impl: str
    shape_buckets: int
    cache: KernelCache
    lower_s: float

    kernel_op = "FusedKernel"     # the op class ExecStats.kernel_calls counts

    @property
    def stage_count(self) -> int:
        """Chunk stages (barriers not counted)."""
        return sum(1 for s in self.stages if s.key is not None)

    def describe(self) -> dict:
        """Deterministic lowering metrics (no execution): what the CI
        bench-gate records next to the plan's byte accounting."""
        return {
            "stage_count": self.stage_count,
            "shape_buckets": self.shape_buckets,
            "kernel_impl": self.kernel_impl,
            "reg_slots": self.n_reg_slots,
            "buf_slots": self.n_buf_slots,
        }

    def runtime(self, x: np.ndarray,
                slot_pool: Optional[SlotPool] = None,
                spans: Optional[SpanRecorder] = None) -> _Runtime:
        """Build the slot-indexed runtime for one run, leasing slot
        storage from ``slot_pool`` when given (:meth:`PlanRun.release`
        gives it back).  The run's spans go to ``spans`` (a fresh
        :class:`SpanRecorder` by default), starting with
        ``Execute.validate``: the domain's check, and the start of its
        copy into the run's host array (:func:`_start_copy`), which the
        run's first writes and first barrier wait for."""
        spans = spans if spans is not None else SpanRecorder()
        with spans.span("Execute.validate"):
            check_domain(self.plan, x)
            src = np.asarray(x)
            host, copy = _start_copy(src, spans)
        regs = bufs = None
        if slot_pool is not None:
            regs, bufs = slot_pool.acquire(self.n_reg_slots, self.n_buf_slots)
        return _Runtime(host, self.n_reg_slots, self.n_buf_slots, regs, bufs,
                        spans, src, copy)

    def execute(self, x: np.ndarray, pipeline: bool = False,
                slot_pool: Optional[SlotPool] = None,
                injector=None, retry=None, on_commit=None,
                ) -> Tuple[np.ndarray, TransferStats, ExecStats]:
        """Run the stage programs through :func:`walk_stages`.

        ``pipeline=True`` issues the next stage's prefetchable ops (H2D
        and host-side Compress) before the current stage's kernels — the
        double-buffered schedule; results are bitwise identical either
        way because prefetched ops only read committed host rows.
        ``slot_pool`` leases the runtime's slot storage from a shared
        pool instead of allocating fresh lists.

        ``injector`` (a :class:`repro.core.faults.FaultInjector`) is
        consulted before every bound op; transient faults are retried in
        place under ``retry`` (a :class:`repro.core.faults.RetryPolicy`),
        terminal faults surface as a typed
        :class:`repro.core.recovery.PlanExecutionError` carrying the
        last committed round.  ``on_commit(round, host)`` fires after
        every round's barrier drains — the checkpoint hook.  A streamed
        D2H box is written back on a pool thread under the next chunks'
        kernels; a write-back's exception is raised at the next barrier.
        The domain's copy into the returned array runs on a thread of
        its own under the device's work; the first barrier (the final
        one, where the plan has no other) waits for it."""
        run = PlanRun(self, x, injector, retry, on_commit)
        walk_stages([run], [(run, s) for s in range(len(self.stages))],
                    pipeline, slot_pool)
        if run.error is not None:
            raise run.error from run.error.fault
        return run.host, self.plan.stats(), run.stats()


class PlanRun:
    """One plan's run in :func:`walk_stages`: its compiled plan, domain,
    spans and fault hooks, its runtime while it runs, and what its
    :class:`ExecStats` is built from (:meth:`stats`).  ``host`` is the
    result once the run has retired; ``error`` the typed
    :class:`~repro.core.recovery.PlanExecutionError` of a run a terminal
    fault ended, named by ``label`` in its message."""

    __slots__ = ("compiled", "x", "injector", "retry", "on_commit", "label",
                 "rec", "rt", "host", "error", "hits", "misses", "inj0",
                 "wall_s")

    def __init__(self, compiled, x, injector=None, retry=None,
                 on_commit=None, label: str = "plan execution",
                 rec: Optional[SpanRecorder] = None):
        self.compiled, self.x = compiled, x
        self.injector, self.retry, self.on_commit = injector, retry, on_commit
        self.label = label
        self.rec = rec if rec is not None else SpanRecorder()
        self.rt = self.host = self.error = None
        self.hits = self.misses = 0
        self.inj0 = self._injected()
        self.wall_s = 0.0

    def _injected(self) -> Tuple[int, int]:
        inj = self.injector
        return (0, 0) if inj is None else (inj.faults_injected, inj.retries)

    def run(self, ops: Tuple[BoundOp, ...]) -> None:
        """Run one stage call's bound ops on the runtime, counting the
        kernel cache's hits and misses meanwhile to this run."""
        cache = self.compiled.cache
        h0, m0 = cache.snapshot()
        try:
            self.rec.run(ops, self.rt, self.injector, self.retry)
        finally:
            h1, m1 = cache.snapshot()
            self.hits += h1 - h0
            self.misses += m1 - m0

    def release(self, slot_pool: Optional[SlotPool]) -> None:
        """Stop the runtime's domain copy and write-back pool, and give
        its slot storage back to ``slot_pool``."""
        rt, self.rt = self.rt, None
        if rt is not None:
            rt.close()
            if slot_pool is not None:
                slot_pool.release(rt.regs, rt.bufs)

    def stats(self, **fields) -> ExecStats:
        """The run's :class:`ExecStats`: its spans, cache counts and
        seconds, and the injector's faults and retries since the run was
        made."""
        c, rec = self.compiled, self.rec
        f1, r1 = self._injected()
        return ExecStats(
            op_counts=dict(rec.count), op_wall_s=dict(rec.wall),
            kernel_calls=rec.count.get(c.kernel_op, 0),
            kernel_impl=c.kernel_impl,
            shape_buckets=c.shape_buckets, kernel_compiles=self.misses,
            kernel_cache_hits=self.hits, stage_count=c.stage_count,
            lower_s=c.lower_s, wall_s=self.wall_s,
            faults_injected=f1 - self.inj0[0], retries=r1 - self.inj0[1],
            **fields)


def walk_stages(runs: Sequence[PlanRun],
                schedule: Sequence[Tuple[PlanRun, int]], pipeline: bool,
                slot_pool: Optional[SlotPool] = None) -> None:
    """Run ``schedule``, entries ``(run, stage index)`` that keep each
    run's stages in plan order, each against its own run's runtime.

    With ``pipeline``, a chunk stage first issues the next entry's
    prefetchable ops (H2D and host-side Compress) on *that* entry's run,
    so they ride under this stage's kernels; a barrier neither
    prefetches nor is prefetched (host rows change there).  A run's last
    stage is followed by its final barrier (a no-op unless a planner
    forgot it); the run then retires: its runtime is released and its
    ``wall_s`` stamped from the walk's start.

    A run whose op raises :class:`~repro.core.faults.InjectedFault` is
    isolated: its runtime is released at once, its remaining entries are
    skipped and ``error`` holds the typed error; the other runs go on.
    Any other exception propagates.  Every runtime is released on every
    exit path, so no copy thread, write-back pool or slot lease outlives
    the walk."""
    perf = time.perf_counter

    def attempt(run: PlanRun, ops: Tuple[BoundOp, ...]) -> bool:
        try:
            run.run(ops)
            return True
        except InjectedFault as f:
            done = run.rt.committed_round
            run.error = PlanExecutionError(
                f"{run.label} failed at round={f.round} chunk={f.chunk} "
                f"op={f.op_class}: {f.kind} (last committed round {done})",
                fault=f, last_committed_round=done,
                fingerprint=plan_fingerprint(run.compiled.plan))
            run.release(slot_pool)
            run.wall_s = perf() - t0
            return False

    def retire(run: PlanRun) -> None:
        run.rt.barrier()
        run.host = run.rt.host
        run.release(slot_pool)
        run.wall_s = perf() - t0

    try:
        for run in runs:
            run.rt = run.compiled.runtime(run.x, slot_pool, run.rec)
            run.rt.on_commit = run.on_commit
        t0 = perf()
        n = len(schedule)
        prefetched = [False] * n
        for m, (run, s) in enumerate(schedule):
            if run.rt is None:                  # isolated by a fault
                continue
            stages = run.compiled.stages
            if pipeline and stages[s].key is not None and m + 1 < n:
                nrun, ns = schedule[m + 1]
                nxt = nrun.compiled.stages[ns]
                if nxt.key is not None and nrun.rt is not None:
                    prefetched[m + 1] = attempt(nrun, nxt.prefetch)
            ops = stages[s].rest if prefetched[m] else stages[s].ops
            if run.rt is not None and attempt(run, ops) \
                    and s == len(stages) - 1:
                retire(run)
        for run in runs:                        # runs with no stages
            if run.rt is not None:
                retire(run)
    finally:
        for run in runs:
            run.release(slot_pool)


class _SlotAllocator:
    """Linear-scan name->slot assignment with *delayed* slot reuse.

    Registers and buffers die at statically known ops, so slots can be
    recycled — but not immediately: the pipelined executor issues stage
    ``k``'s prefetchable ops (H2D / host-side Compress) before stage
    ``k-1``'s ops run, so a slot freed in stage ``k-1`` is still being
    read when stage ``k``'s prefetch would write it.  Holding every freed
    slot out of the pool for two chunk stages guarantees a reused slot's
    last touch strictly precedes the earliest point the pipeline can
    write it again (the prefetch of the stage after next)."""

    REUSE_DELAY = 2

    def __init__(self):
        self._live: Dict[str, int] = {}
        self._free: List[int] = []
        self._pending: List[Tuple[int, int]] = []   # (freed_at_stage, slot)
        self.n_slots = 0

    def new_stage(self, ordinal: int) -> None:
        """Called when lowering enters chunk stage ``ordinal``: slots
        freed at least ``REUSE_DELAY`` stages ago become reusable."""
        keep = []
        for freed_at, slot in self._pending:
            if freed_at <= ordinal - self.REUSE_DELAY:
                self._free.append(slot)
            else:
                keep.append((freed_at, slot))
        self._pending = keep

    def alloc(self, name: str) -> int:
        assert name not in self._live, f"slot name {name!r} already live"
        if self._free:
            slot = self._free.pop()
        else:
            slot = self.n_slots
            self.n_slots += 1
        self._live[name] = slot
        return slot

    def get(self, name: str) -> int:
        return self._live[name]

    def free(self, name: str, stage_ordinal: int) -> int:
        slot = self._live.pop(name)
        self._pending.append((stage_ordinal, slot))
        return slot


def _is_banded(op: FusedKernel) -> bool:
    """True for a classic 2-D row band (full width, frame columns along)
    — the shape the registered fused-step kernels and the bucketing pass
    understand.  Anything else (3-D tiles, column chunks) lowers through
    the N-D reference binder."""
    return len(op.shape_in) == 2 and op.keep_lo[1] and op.keep_hi[1]


def _bucket_heights(plan: ExecutionPlan, bucket: bool,
                    registry: Optional[BucketRegistry] = None,
                    ) -> Dict[tuple, int]:
    """Per-group padded band heights: one bucket per ``(stencil, steps,
    keep_top, keep_bottom)`` group (its max h_in).  Both-sides-framed
    bands are excluded — there is no frame-free side to pad — and so are
    non-banded (N-D box) kernels, which have no single pad axis.  A
    :class:`BucketRegistry` lifts each group's height to the smallest
    already-compiled cross-plan bucket that fits, so warm-service jobs
    with unseen shapes reuse existing kernel signatures."""
    buckets: Dict[tuple, int] = {}
    if not bucket:
        return buckets
    for op in plan.ops:
        if isinstance(op, FusedKernel) and _is_banded(op) \
                and not (op.keep_lo[0] and op.keep_hi[0]):
            key = (op.stencil, op.steps, op.keep_lo[0], op.keep_hi[0])
            buckets[key] = max(buckets.get(key, 0), op.shape_in[0])
    if registry is not None:
        for key, h in buckets.items():
            buckets[key] = registry.resolve(
                key + (plan.X, plan.itemsize), h)
    return buckets


def _bind_kernel(slot: int, op: FusedKernel, bucket_h: int, impl_name: str,
                 fn: Callable, cache: KernelCache, itemsize: int) -> Callable:
    h_in, width = op.shape_in
    pad = bucket_h - h_in
    kt, kb = op.keep_lo[0], op.keep_hi[0]
    # pad on the frame-free side; slice the true output back out
    pad_top = kb and not kt
    # id(fn) keeps the signature count honest when the same impl name
    # resolves to a different callable (swapped fused_step, new tile):
    # the cache entry holds fn alive, so its id cannot be reused while
    # the key is live.  The callable itself is always the freshly
    # resolved fn — the cache only counts, it never serves stale code.
    key = (impl_name, id(fn), op.stencil, op.steps, kt, kb,
           bucket_h, width, itemsize)
    name, steps = op.stencil, op.steps
    h_out = op.shape_out[0]

    def run(rt):
        cache.lookup(key, lambda: fn)
        band = rt.regs[slot]
        sp = rt.spans
        if pad:
            with sp.span("FusedKernel.pad"):
                z = jnp.zeros((pad, band.shape[1]), band.dtype)
                band = jnp.concatenate([z, band] if pad_top else [band, z],
                                       axis=0)
        with sp.span("FusedKernel.call"):
            out = fn(band, name, steps, keep_top=kt, keep_bottom=kb)
        if pad:
            with sp.span("FusedKernel.crop"):
                out = out[out.shape[0] - h_out:] if pad_top else out[:h_out]
        rt.regs[slot] = out

    return run


def _bind_kernel_nd(slot: int, op: FusedKernel, cache: KernelCache,
                    itemsize: int) -> Callable:
    """Bind a non-banded (N-D box) FusedKernel to the reference kernel.

    No padding/bucketing: each distinct ``(shape_in, keeps)`` is its own
    jit signature, and the cache key mirrors that so ``shape_buckets``
    keeps counting the true compile ceiling."""
    from .reference import multi_step_box

    key = ("reference_nd", op.stencil, op.steps, op.keep_lo, op.keep_hi,
           op.shape_in, itemsize)
    name, steps, kl, kh = op.stencil, op.steps, op.keep_lo, op.keep_hi

    def run(rt):
        cache.lookup(key, lambda: multi_step_box)
        with rt.spans.span("FusedKernel.call"):
            rt.regs[slot] = multi_step_box(rt.regs[slot], name, steps,
                                           keep_lo=kl, keep_hi=kh)

    return run


def _bind_kernel_masked(slot: int, op: FusedKernel, box: Box,
                        origin: Tuple[int, int, int, int],
                        cache: KernelCache, itemsize: int) -> Callable:
    """Bind a hierarchical inner FusedKernel to the globally-masked
    update (:func:`repro.core.distributed.masked_local_steps`).

    ``box`` is the register's ext in band coordinates; ``origin`` maps
    the band into the global framed domain ``(gy0, gx0, Yg, Xg)``.  The
    per-chunk global offsets are *traced* arguments, so every chunk of
    every rank with the same ext shape shares one compiled signature —
    the same trick :func:`_bind_shard_kernel` plays one level up.  No
    crop here: the masked step preserves the ext's frame, and the D2H
    that follows selects only the rows/cols at halo depth."""
    from .distributed import masked_local_steps
    from .stencil import get_stencil

    st = get_stencil(op.stencil)
    gy0, gx0, Yg, Xg = origin
    key = ("hier", op.stencil, op.steps, op.shape_in, Yg, Xg, itemsize)
    oy, ox = gy0 + box.lo[0], gx0 + box.lo[1]
    steps = op.steps

    def make() -> Callable:
        def f(ext, y0, x0):
            return masked_local_steps(ext, st, steps, y0, x0, Yg, Xg)
        return jax.jit(f)

    def run(rt):
        fn = cache.lookup(key, make)
        with rt.spans.span("FusedKernel.call"):
            rt.regs[slot] = fn(rt.regs[slot], oy, ox)

    return run


def _overlaps(a: Box, b: Box) -> bool:
    return all(alo < bhi and blo < ahi
               for alo, ahi, blo, bhi in zip(a.lo, a.hi, b.lo, b.hi))


def _streamed_d2h(ops) -> set:
    """The ids of the D2H ops whose boxes stream: written back as soon as
    they are ready instead of at their round's barrier.

    A box streams when it holds at least ``STREAM_MIN_BYTES``, a
    FusedKernel of its round comes after it in plan order (there is
    device work to hide the write-back under) and no host read of its
    round that comes after it (an H2D, or a host-side Compress) reads
    rows that intersect it.  A later read therefore
    always sees the rows as they were before the round; prefetching only
    moves reads earlier, so the rule holds under ``pipeline=True``.
    Host reads that come before the box belong to chunks whose input the
    in-order device has consumed by the time the box is ready."""
    streamed = set()
    reads: List[Box] = []           # host boxes read later in the round
    kernel_after = False
    for op in reversed(ops):
        if isinstance(op, HostCommit):
            reads, kernel_after = [], False
        elif isinstance(op, H2D) or (isinstance(op, Compress)
                                     and op.direction == "h2d"):
            reads.append(op.box)
        elif isinstance(op, FusedKernel):
            kernel_after = True
        elif (isinstance(op, D2H) and op.nbytes >= STREAM_MIN_BYTES
              and kernel_after
              and not any(_overlaps(op.box, b) for b in reads)):
            streamed.add(id(op))
    return streamed


def lower(plan: ExecutionPlan, policy=None, fused_step=None,
          kernel_cache: Optional[KernelCache] = None,
          bucket_registry: Optional[BucketRegistry] = None,
          shard_origin: Optional[Tuple[int, int, int, int]] = None,
          ) -> CompiledPlan:
    """Compile a plan into stage programs of slot-bound closures.

    ``fused_step`` (an explicit ``fn(band, name, steps, keep_top=...,
    keep_bottom=...)`` callable) overrides the dispatch registry;
    otherwise ``policy`` (a :class:`repro.kernels.dispatch.DispatchPolicy`,
    default ``auto``) picks the implementation per stencil/steps/backend.
    ``kernel_cache`` lets an executor share one signature cache across
    plans and runs; ``bucket_registry`` additionally routes this plan's
    band heights to already-registered cross-plan buckets so a warm
    service compiles zero new kernels for shapes that fit an existing
    bucket.

    ``shard_origin`` switches the kernel binding to hierarchical inner
    semantics: the plan's domain is one shard's halo-extended band at
    global origin ``(gy0, gx0)`` inside a ``(Yg, Xg)`` framed domain,
    and every FusedKernel runs the globally-masked update instead of
    the frame-shrinking fused step (:func:`_bind_kernel_masked`)."""
    from repro.kernels.dispatch import DispatchPolicy, select_kernel

    t0 = time.perf_counter()
    policy = policy or DispatchPolicy()
    cache = kernel_cache if kernel_cache is not None else KernelCache()
    buckets = _bucket_heights(plan, policy.bucket, bucket_registry)
    # band-coordinate ext of each live register, tracked only for the
    # masked (shard_origin) binding, which needs the global offset
    reg_boxes: Dict[str, Box] = {}

    regs = _SlotAllocator()
    bufs = _SlotAllocator()
    # (stencil, steps) -> (impl_name, callable); resolved once at lower time
    kernels: Dict[tuple, Tuple[str, Callable]] = {}
    nd_impls: set = set()               # "reference_nd" when box kernels bind
    # statically tracked codec context between a Compress and its transfer
    pending_h2d: Dict[str, str] = {}    # reg -> codec (non-identity, h2d)
    pending_d2h: Dict[str, str] = {}    # reg -> codec (non-identity, d2h)

    streamed = _streamed_d2h(plan.ops)
    signatures = set()
    stages: List[List] = []             # [key, [BoundOp...]]
    chunk_ordinal = -1                  # index of the current chunk stage

    def emit(key, tag: str, fn: Callable, site=None) -> None:
        s = site if site is not None else key
        bound = (_TAG[tag], fn, s[0], s[1])
        if stages and stages[-1][0] == key and key is not None:
            stages[-1][1].append(bound)
        else:
            stages.append([key, [bound]])

    for op in plan.ops:
        if isinstance(op, HostCommit):
            def run_commit(rt, _r=op.round):
                rt.commit_round(_r)

            emit(None, "HostCommit", run_commit, site=(op.round, -1))
            continue
        key = (op.round, op.chunk)
        if not stages or stages[-1][0] != key:
            chunk_ordinal += 1
            regs.new_stage(chunk_ordinal)
            bufs.new_stage(chunk_ordinal)
        if isinstance(op, Compress):
            if op.direction == "h2d":
                codec = get_codec(op.codec)
                if codec.name == "identity":
                    # identity fast path: skip the encode/decode byte
                    # round trip — the H2D itself is the (pure) copy;
                    # wire-byte accounting stays plan-derived
                    emit(key, "Compress", _noop)
                else:
                    slot = regs.alloc(op.reg)   # H2D binds as the wire hop
                    pending_h2d[op.reg] = op.codec
                    sl = op.box.slices()

                    def run(rt, _s=slot, _sl=sl, _c=codec):
                        rows = rt.src[_sl]
                        rt.wire[_s] = (jnp.asarray(_c.encode(rows)),
                                       rows.shape, rows.dtype)

                    emit(key, "Compress", run)
            else:
                if op.codec != "identity":
                    pending_d2h[op.reg] = op.codec
                emit(key, "Compress", _noop)
        elif isinstance(op, Decompress):
            if op.direction == "h2d" and op.codec != "identity":
                slot = regs.get(op.reg)
                codec = get_codec(op.codec)

                def run(rt, _s=slot, _c=codec):
                    payload, shape, dtype = rt.wire.pop(_s)
                    rt.regs[_s] = jnp.asarray(
                        _c.decode(np.asarray(payload), shape, dtype))

                emit(key, "Decompress", run)
            else:
                # d2h decode runs at the box's write-back (the first
                # point the device bytes are forced anyway)
                emit(key, "Decompress", _noop)
        elif isinstance(op, H2D):
            if shard_origin is not None:
                reg_boxes[op.reg] = op.box
            if op.reg in pending_h2d:
                # the wire hop already carried the encoded payload
                del pending_h2d[op.reg]
                emit(key, "H2D", _noop)
            else:
                slot = regs.alloc(op.reg)
                sl = op.box.slices()

                def run(rt, _s=slot, _sl=sl):
                    rt.regs[_s] = jnp.asarray(rt.src[_sl])

                emit(key, "H2D", run)
        elif isinstance(op, BufferWrite):
            rslot = regs.get(op.reg)
            bslot = bufs.alloc(op.buf)
            sl = op.reg_box.slices()

            def run(rt, _b=bslot, _r=rslot, _sl=sl):
                rt.bufs[_b] = rt.regs[_r][_sl]

            emit(key, "BufferWrite", run)
        elif isinstance(op, BufferRead):
            bslot = bufs.free(op.buf, chunk_ordinal)    # consumed exactly once
            src_slot = regs.free(op.src, chunk_ordinal)  # src dies here
            dst_slot = regs.alloc(op.reg)
            if shard_origin is not None:
                # the buffer's extent slices prepend at the low side
                sbox = reg_boxes.pop(op.src)
                reg_boxes[op.reg] = sbox.with_axis(
                    op.axis, sbox.lo[op.axis] - op.extent, sbox.hi[op.axis])

            def run(rt, _b=bslot, _src=src_slot, _dst=dst_slot, _ax=op.axis):
                shared = rt.bufs[_b]
                rt.bufs[_b] = None
                src = rt.regs[_src]
                if _src != _dst:
                    rt.regs[_src] = None
                rt.regs[_dst] = jnp.concatenate([shared, src], axis=_ax)

            emit(key, "BufferRead", run)
        elif isinstance(op, FusedKernel):
            slot = regs.get(op.reg)
            if shard_origin is not None:
                # hierarchical inner kernel: globally-masked update, one
                # signature per ext shape (origins are traced)
                signatures.add(("hier", op.stencil, op.steps, op.shape_in))
                nd_impls.add("masked_hier")
                emit(key, "FusedKernel",
                     _bind_kernel_masked(slot, op, reg_boxes[op.reg],
                                         shard_origin, cache, plan.itemsize))
                continue
            if not _is_banded(op):
                # N-D box band: reference kernel, one signature per
                # distinct (shape, keeps)
                signatures.add((op.stencil, op.steps, op.keep_lo,
                                op.keep_hi, op.shape_in))
                nd_impls.add("reference_nd")
                emit(key, "FusedKernel",
                     _bind_kernel_nd(slot, op, cache, plan.itemsize))
                continue
            kkey = (op.stencil, op.steps)
            if kkey not in kernels:
                if fused_step is not None:
                    kernels[kkey] = ("explicit", fused_step)
                else:
                    kernels[kkey] = select_kernel(op.stencil, op.steps, policy)
            impl_name, fn = kernels[kkey]
            gkey = (op.stencil, op.steps, op.keep_lo[0], op.keep_hi[0])
            bucket_h = buckets.get(gkey, op.shape_in[0])
            signatures.add(gkey + (bucket_h,))
            emit(key, "FusedKernel",
                 _bind_kernel(slot, op, bucket_h, impl_name, fn, cache,
                              plan.itemsize))
        elif isinstance(op, D2H):
            slot = regs.free(op.reg, chunk_ordinal)   # last use of the register
            if shard_origin is not None:
                reg_boxes.pop(op.reg, None)
            codec_name = pending_d2h.pop(op.reg, None)
            rsl, hsl = op.reg_box.slices(), op.box.slices()
            site = (op.round, op.chunk, id(op) in streamed)

            def run(rt, _s=slot, _rsl=rsl, _hsl=hsl, _codec=codec_name,
                    _site=site):
                band = rt.regs[_s]
                rt.regs[_s] = None
                rt.stage((_hsl, band[_rsl], _codec), _site)

            emit(key, "D2H", run)
        else:  # pragma: no cover - planner/lowering version skew
            raise TypeError(f"unknown op {op!r}")

    impl_names = sorted({name for name, _ in kernels.values()} | nd_impls)
    lowered_stages = []
    for key, ops in stages:
        ops = tuple(ops)
        prefetch = tuple(
            b for b in ops
            if b[0] == _TAG["H2D"] or b[0] == _TAG["Compress"])
        rest = tuple(b for b in ops if b not in prefetch)
        lowered_stages.append(LoweredStage(key=key, ops=ops,
                                           prefetch=prefetch, rest=rest))
    return CompiledPlan(
        plan=plan,
        stages=tuple(lowered_stages),
        n_reg_slots=regs.n_slots,
        n_buf_slots=bufs.n_slots,
        kernel_impl="+".join(impl_names) if impl_names else "none",
        shape_buckets=len(signatures),
        cache=cache,
        lower_s=time.perf_counter() - t0,
    )


# --------------------------------------------------------------------------
# Sharded-plan lowering: per-rank streams -> global phase-ordered stage
# programs, executed in lockstep on a single device (the simulator behind
# repro.core.executor.ShardedSimExecutor).  Reuses the slot binder for
# rank bands and the KernelCache for the masked shard kernel — shards are
# uniform, so every rank and round shares ONE compiled signature (the
# per-rank global origin is a traced argument, not a static one).
# --------------------------------------------------------------------------


class _ShardRuntime:
    """Slot-indexed per-rank band state + the halo mailbox the bound
    closures run against.  ``mail`` is keyed ``(src, dst, axis, round)``
    — unique per exchange because each ordered rank pair swaps at most
    one payload per axis per round; with a halo codec the value is the
    encoded ``(payload, shape, dtype)`` wire triple instead of the raw
    slice.  ``slot_pool`` (optional) is the shared pool hierarchical
    inner plans lease their chunk-slot storage from."""

    __slots__ = ("host", "bands", "mail", "staged", "slot_pool", "spans")

    def __init__(self, host: np.ndarray, n_slots: int, slot_pool=None,
                 spans: Optional[SpanRecorder] = None):
        self.host = host
        self.bands: List = [None] * n_slots
        self.mail: Dict[tuple, jnp.ndarray] = {}
        # (host slice tuple, device band, (round, rank) of the store)
        self.staged: List[tuple] = []
        self.slot_pool = slot_pool
        self.spans = spans if spans is not None else SpanRecorder("rank")

    def commit(self) -> None:
        """The end-of-plan barrier, in the phases of
        :meth:`_Runtime.commit`."""
        if not self.staged:
            return
        sp = self.spans
        with sp.span("HostCommit.drain"):
            for _, rows, _ in self.staged:
                jax.block_until_ready(rows)
        for sl, rows, site in self.staged:
            sp.at(*site)
            with sp.span("D2H.pull"):
                rows = np.asarray(rows)
            with sp.span("D2H.scatter"):
                self.host[sl] = rows
        self.staged.clear()


@dataclasses.dataclass(frozen=True)
class ShardStage:
    """One global phase: every rank's bound ops, rank order.  Phase
    boundaries are the plan's barrier structure — an executor must drain
    a stage before starting the next (sends and recvs never share one)."""

    label: str
    ops: Tuple[BoundOp, ...]


def _bind_hier_kernel(slot: int, hk: int, inner) -> Callable:
    """Bind a ShardKernel to its expanded inner plan (hierarchical
    execution): the rank's halo-extended band becomes the inner plan's
    host domain, the nested stage programs stream it chunk-wise through
    the ordinary H2D/kernel/D2H path (leasing slot storage from the
    shared pool when one rides on the runtime), and the updated owned
    region is cropped back — exactly what the flat masked kernel's crop
    produces, because the inner kernels run the same globally-masked
    update on ext regions whose write-back depth equals the halo."""

    def run(rt):
        band = np.asarray(rt.bands[slot])
        host, _, _ = inner.execute(band, slot_pool=rt.slot_pool)
        rt.bands[slot] = jnp.asarray(
            host[hk:-hk, hk:-hk] if hk else host)

    return run


def _bind_shard_kernel(slot: int, op: ShardKernel, plan: ShardedPlan,
                       cache: KernelCache) -> Callable:
    from .distributed import masked_local_steps
    from .stencil import get_stencil

    st = get_stencil(op.stencil)
    hk = op.steps * st.radius
    # one signature per (stencil, steps, band shape, domain): gy0/gx0 are
    # traced, so all ranks and rounds hit the same compiled kernel
    key = ("shard", op.stencil, op.steps, op.h, op.w, plan.Y, plan.X,
           plan.itemsize)
    gy0, gx0 = op.gy0, op.gx0

    def make() -> Callable:
        def f(ext, y0, x0):
            out = masked_local_steps(ext, st, op.steps, y0, x0,
                                     plan.Y, plan.X)
            return out[hk:-hk, hk:-hk] if hk else out
        return jax.jit(f)

    def run(rt):
        fn = cache.lookup(key, make)
        rt.bands[slot] = fn(rt.bands[slot], gy0, gx0)

    return run


@dataclasses.dataclass
class CompiledShardedPlan:
    """A lowered :class:`~repro.core.plan.ShardedPlan`: phase-ordered
    stage programs of slot-bound closures over a shared halo mailbox."""

    plan: ShardedPlan
    stages: Tuple[ShardStage, ...]
    n_slots: int
    shape_buckets: int
    cache: KernelCache
    lower_s: float
    kernel_impl: str = "shard_sim"
    # op classes timed without a span of their own: a hierarchical
    # ShardKernel runs a nested plan, whose own spans are the leaves
    phased: frozenset = _PHASED

    kernel_op = "ShardKernel"

    @property
    def stage_count(self) -> int:
        return len(self.stages)

    def describe(self) -> dict:
        return {
            "stage_count": self.stage_count,
            "shape_buckets": self.shape_buckets,
            "kernel_impl": self.kernel_impl,
            "reg_slots": self.n_slots,
            "buf_slots": 0,
        }

    def execute(self, x: np.ndarray, injector=None, retry=None,
                slot_pool: Optional[SlotPool] = None,
                ) -> Tuple[np.ndarray, TransferStats, ExecStats]:
        """Run every phase in barrier order (all ranks lockstep).  The
        result matches the shard_map backend to float tolerance — same
        masked-update math via :func:`repro.core.distributed
        .masked_local_steps` — and the returned stats are the
        plan-derived accounting, untouched by execution.

        ``injector``/``retry`` mirror :meth:`CompiledPlan.execute`, with
        the op site's chunk field addressing the *rank* — a
        ``rank_loss`` trigger at ``(round, rank)`` fires mid-round, after
        that round's loads/halos already moved (what a real preemption
        costs).  Sharded plans commit host state once at the end, so a
        terminal fault surfaces with ``last_committed_round = -1``; the
        elastic harness (:mod:`repro.launch.elastic`) recovers round
        granularity by executing one-round continuation plans.

        ``slot_pool`` is only consulted by hierarchical plans: each
        expanded ShardKernel leases its inner chunk-slot storage from
        the pool and releases it when the nested run retires (also on
        fault paths — the inner executor releases in ``finally``), so
        :meth:`SlotPool.assert_balanced` holds after any exit."""
        run = PlanRun(self, x, injector, retry,
                      rec=SpanRecorder("rank", self.phased))
        with run.rec.span("Execute.validate"):
            check_domain(self.plan, x)
            host = np.asarray(x).copy()
        run.rt = _ShardRuntime(host, self.n_slots, slot_pool=slot_pool,
                               spans=run.rec)
        t_run = time.perf_counter()
        try:
            for stage in self.stages:
                run.run(stage.ops)
            run.rt.commit()
        except InjectedFault as f:
            raise PlanExecutionError(
                f"sharded plan failed at round={f.round} rank={f.chunk} "
                f"op={f.op_class}: {f.kind}",
                fault=f, last_committed_round=-1,
                fingerprint=plan_fingerprint(self.plan)) from f
        run.wall_s = time.perf_counter() - t_run
        return host, self.plan.stats(), run.stats()


def lower_sharded(plan,
                  kernel_cache: Optional[KernelCache] = None,
                  ) -> CompiledShardedPlan:
    """Compile a sharded plan's per-rank streams into global stage
    programs.

    Each rank's evolving band (own -> row-extended -> fully-extended ->
    cropped own) binds to one slot via the same :class:`_SlotAllocator`
    the single-device lowering uses; halo ops become mailbox closures;
    :class:`~repro.core.plan.ShardKernel` ops dispatch through the keyed
    :class:`KernelCache` — uniform shards mean exactly one kernel
    signature for the whole plan (``shape_buckets == 1``).

    Accepts a :class:`~repro.core.hierarchy.HierarchicalPlan` too: the
    outer streams lower exactly as above, except each ShardKernel binds
    to its rank's nested inner plan — itself lowered through
    :func:`lower` in masked ``shard_origin`` mode, sharing this plan's
    :class:`KernelCache` so inner compiles surface in the same counters.

    A non-identity halo codec (``plan.codec``) runs for real: the
    ``HaloCompress`` closure slices the edge payload and encodes it —
    the mailbox then carries the encoded wire triple — and the paired
    ``HaloRecv`` decodes before attaching, so lossless codecs round-trip
    bit-exactly through actual encoded bytes while the accounting stays
    plan-derived.  The ``identity`` codec is fast-pathed (the raw slice
    is already the copy)."""
    t0 = time.perf_counter()
    hplan = None
    if not isinstance(plan, ShardedPlan) and hasattr(plan, "outer"):
        # HierarchicalPlan (duck-typed: hierarchy.py must stay importable
        # without this module)
        hplan = plan
        outer = plan.outer
    else:
        outer = plan
    if outer.trailing:
        raise ValueError(
            f"plan models trailing axes {outer.trailing}; trailing plans "
            "are dry-run-only (byte/flop accounting) and cannot execute")
    cache = kernel_cache if kernel_cache is not None else KernelCache()
    regs = _SlotAllocator()
    signatures = set()
    stages: List[ShardStage] = []
    hk = outer.k_ici * outer.radius

    halo_codec = None
    if outer.codec and outer.codec != "identity":
        halo_codec = get_codec(outer.codec)

    inner_compiled = {}
    if hplan is not None:
        for rank, sh in enumerate(outer.shards):
            origin = (sh.y0 - hk, sh.x0 - hk, outer.Y, outer.X)
            inner_compiled[rank] = lower(
                hplan.inner[rank], shard_origin=origin, kernel_cache=cache)
            # uniform shards -> every rank's inner plan presents the same
            # ext shapes, so the signature census dedupes across ranks
            for iop in hplan.inner[rank].ops:
                if isinstance(iop, FusedKernel):
                    signatures.add(("hier", iop.stencil, iop.steps,
                                    iop.shape_in))

    for ordinal, (label, ops) in enumerate(outer.phases()):
        regs.new_stage(ordinal)
        bound: List[BoundOp] = []
        for op in ops:
            if isinstance(op, ShardLoad):
                slot = regs.alloc(f"band:{op.rank}")
                sl = op.box.slices()

                def run(rt, _s=slot, _sl=sl):
                    rt.bands[_s] = jnp.asarray(rt.host[_sl])

                bound.append((_TAG["ShardLoad"], run, op.round, op.rank))
            elif isinstance(op, HaloCompress):
                if halo_codec is None:
                    bound.append((_TAG["HaloCompress"], _noop,
                                  op.round, op.rank))
                else:
                    # the encode IS the send: the mailbox carries the
                    # encoded wire triple instead of the raw edge slice
                    slot = regs.get(f"band:{op.rank}")
                    mkey = (op.rank, op.peer, op.axis, op.round)
                    axis, side = op.axis, op.side

                    def run(rt, _s=slot, _k=mkey, _a=axis, _e=side, _d=hk,
                            _c=halo_codec):
                        band = rt.bands[_s]
                        if _a == 0:
                            payload = band[-_d:] if _e == "hi" else band[:_d]
                        else:
                            payload = (band[:, -_d:] if _e == "hi"
                                       else band[:, :_d])
                        rows = np.asarray(payload)
                        rt.mail[_k] = (_c.encode(rows), rows.shape,
                                       rows.dtype)

                    bound.append((_TAG["HaloCompress"], run,
                                  op.round, op.rank))
            elif isinstance(op, HaloSend):
                if halo_codec is not None:
                    # wire hop already happened at the HaloCompress
                    bound.append((_TAG["HaloSend"], _noop,
                                  op.round, op.rank))
                    continue
                slot = regs.get(f"band:{op.rank}")
                mkey = (op.rank, op.dst, op.axis, op.round)
                axis, side, depth = op.axis, op.side, op.depth

                def run(rt, _s=slot, _k=mkey, _a=axis, _e=side, _d=depth):
                    band = rt.bands[_s]
                    if _a == 0:
                        payload = band[-_d:] if _e == "hi" else band[:_d]
                    else:
                        payload = band[:, -_d:] if _e == "hi" else band[:, :_d]
                    rt.mail[_k] = payload

                bound.append((_TAG["HaloSend"], run, op.round, op.rank))
            elif isinstance(op, HaloRecv):
                slot = regs.get(f"band:{op.rank}")
                mkey = (op.src, op.rank, op.axis, op.round)
                axis, side, depth, src = op.axis, op.side, op.depth, op.src

                def run(rt, _s=slot, _k=mkey, _a=axis, _e=side, _d=depth,
                        _src=src, _c=halo_codec):
                    band = rt.bands[_s]
                    if _src < 0:
                        # mesh edge: zero fill, exactly what ppermute
                        # leaves for non-receivers (masked, never read
                        # by valid cells)
                        shape = ((_d, band.shape[1]) if _a == 0
                                 else (band.shape[0], _d))
                        payload = jnp.zeros(shape, band.dtype)
                    elif _c is not None:
                        wire, shape, dtype = rt.mail.pop(_k)
                        payload = jnp.asarray(
                            _c.decode(np.asarray(wire), shape, dtype))
                    else:
                        payload = rt.mail.pop(_k)
                    pair = [payload, band] if _e == "lo" else [band, payload]
                    rt.bands[_s] = jnp.concatenate(pair, axis=_a)

                bound.append((_TAG["HaloRecv"], run, op.round, op.rank))
            elif isinstance(op, HaloDecompress):
                # decode runs at the paired HaloRecv (the payload must
                # materialize before it is concatenated anyway)
                bound.append((_TAG["HaloDecompress"], _noop,
                              op.round, op.rank))
            elif isinstance(op, ShardKernel):
                slot = regs.get(f"band:{op.rank}")
                if hplan is not None:
                    bound.append((_TAG["ShardKernel"],
                                  _bind_hier_kernel(
                                      slot, hk, inner_compiled[op.rank]),
                                  op.round, op.rank))
                    continue
                signatures.add((op.stencil, op.steps, op.h, op.w))
                bound.append((_TAG["ShardKernel"],
                              _bind_shard_kernel(slot, op, outer, cache),
                              op.round, op.rank))
            elif isinstance(op, ShardStore):
                slot = regs.free(f"band:{op.rank}", ordinal)
                sl = op.box.slices()

                def run(rt, _s=slot, _sl=sl, _site=(op.round, op.rank)):
                    band = rt.bands[_s]
                    rt.bands[_s] = None
                    rt.staged.append((_sl, band, _site))

                bound.append((_TAG["ShardStore"], run, op.round, op.rank))
            else:  # pragma: no cover - planner/lowering version skew
                raise TypeError(f"unknown sharded op {op!r}")
        stages.append(ShardStage(label=label, ops=tuple(bound)))

    return CompiledShardedPlan(
        plan=plan,   # the hierarchical wrapper when given one: stats()
        stages=tuple(stages),     # must report both levels
        n_slots=regs.n_slots,
        shape_buckets=len(signatures),
        cache=cache,
        lower_s=time.perf_counter() - t0,
        kernel_impl="shard_sim+hier" if hplan is not None else "shard_sim",
        phased=(_PHASED | {_TAG["ShardKernel"]}) if hplan is not None
        else _PHASED,
    )
