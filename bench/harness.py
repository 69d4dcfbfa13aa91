"""One run of one cell: set-up, the measured window, and the check.

The window drives the system's own path, whole solves back to back in a
closed loop, one at a time, each from the host array in to the host
array out.  It stops at the first solve that ends after ``seconds``,
and holds at least the configuration's ``min_window_solves`` (1 where
it gives none).  The configuration's engine picks the path from
:data:`PATHS`:

* an engine of ``compile_plan`` (one chip, a 2-D domain):
  ``compile_plan(engine, ...)`` into
  ``DoubleBufferedExecutor(policy=DispatchPolicy())``; set-up warms
  every shape up by running one round on the fewest leading rows of the
  domain whose plan has every op shape of the solve.
* ``box_tb`` (one chip, a domain of any rank): ``compile_box_plan`` of
  the schedule's ``tiles``, ``time_depth`` and ``k_on`` into the same
  executor; set-up runs one round on the domain cut, along each axis of
  more than three equal tiles, to its first three, whose plan has every
  op shape of the solve.
* ``sharded`` (a mesh of chips): ``compile_sharded(...)`` into
  ``ShardMapExecutor`` on a mesh of the cell's devices.  The solve is
  one program, which the executor traces anew at every solve and loads
  from the persistent compilation cache; set-up compiles that program
  once, for the sharded domain, so that it is in the cache.

Set-up, which :func:`run_cell` times from the process start its caller
gives, makes the domain from the seed and runs that warm-up.
"""
from __future__ import annotations

import dataclasses
import statistics
import sys
import tempfile
import time
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

import jax
from repro.core.oocore import ENGINES

from bench import peaks as peaks_mod
from bench import reference, work
from bench.spec import Cell, read_metrics
from bench.trace import TraceSummary, reduce_trace
from bench.workload import SolveParams, make_domain

# compile events are JAX's own monitoring events under this prefix
COMPILE_EVENTS = "/jax/core/compile/"
# the persistent compilation cache's hits and misses
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}
WINDOW_SPAN = "bench_window"


def log_err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileLog:
    """JAX compile events by ``(event, function)``: count and seconds,
    for set-up and for the window apart.  A context manager: the
    listener is removed on exit."""

    def __init__(self):
        self.phase = "setup"
        self.events: Dict[str, Dict[tuple, List[float]]] = defaultdict(
            lambda: defaultdict(lambda: [0, 0.0]))
        self.cache: Dict[str, Dict[str, int]] = defaultdict(
            lambda: defaultdict(int))

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.startswith(COMPILE_EVENTS):
            slot = self.events[self.phase][(event[len(COMPILE_EVENTS):],
                                            kw.get("fun_name", ""))]
            slot[0] += 1
            slot[1] += duration

    def _on_cache(self, event: str, **kw) -> None:
        if event in CACHE_EVENTS:
            self.cache[self.phase][CACHE_EVENTS[event]] += 1

    def __enter__(self) -> "CompileLog":
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_cache)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._on_cache)

    def count(self, phase: str) -> int:
        return sum(c for c, _ in self.events[phase].values())

    def report(self, phase: str, log: Callable[[str], None]) -> None:
        rows = sorted(self.events[phase].items(), key=lambda kv: -kv[1][1])
        total = sum(s for _, s in self.events[phase].values())
        log(f"compile events in {phase}: {self.count(phase)} events, "
            f"{total!r} s")
        for (event, fun), (n, s) in rows:
            log(f"  compile {phase} {event} {fun} n={n} s={s!r}")
        cache = self.cache[phase]
        log(f"persistent compilation cache in {phase}: "
            f"hits={cache['hits']} misses={cache['misses']}")


@dataclasses.dataclass
class Context:
    """What a per-layer reader (``bench/metrics/<name>.py``) reads."""

    cell: Cell
    params: SolveParams
    plan: object                 # the solve's ExecutionPlan or ShardedPlan
    solves: int                  # whole solves in the window
    exec_wall_s: float           # sum of ExecStats.wall_s over them
    op_wall_s: Dict[str, float]  # sum of ExecStats.op_wall_s over them
    trace: Optional[TraceSummary]
    peaks: Optional[dict]        # the device's row of bench/peaks.json
    f32_flops_per_s: Optional[float]
    log: Callable[[str], None]

    @property
    def stats(self):
        return self.plan.stats()

    @property
    def kernel_ops(self) -> list:
        from repro.core.plan import FusedKernel

        return [op for op in self.plan.ops if isinstance(op, FusedKernel)]

    @property
    def kernel_work(self) -> List[work.Work]:
        """Useful work of each kernel call of one solve, on every chip
        (:mod:`bench.work`)."""
        return solve_path(self.params.engine).kernel_work(
            self.plan, self.params, int(self.cell.config["flops_per_cell"]))


def solve_params(config: dict) -> SolveParams:
    """One solve of the configuration, its schedule read by its
    engine's path.  ``interior`` is a side (a square) or one extent per
    axis.  Raises ``KeyError`` for an unknown engine or a
    schedule that lacks a key the engine needs, and ``ValueError`` for
    steps that are not whole rounds."""
    engine = config["engine"]
    path = solve_path(engine)
    sched = config["schedule"]
    missing = [k for k in path.schedule if k not in sched]
    if missing:
        raise KeyError(f"the schedule lacks {missing} for engine {engine!r}")
    r = int(config["radius"])
    interior = config["interior"]
    inner = ([int(interior)] * 2 if isinstance(interior, int)
             else [int(n) for n in interior])
    common = dict(engine=engine, stencil=config["stencil"], radius=r,
                  shape=tuple(n + 2 * r for n in inner),
                  steps=int(config["steps_per_solve"]))
    p = path.params(common, sched)
    rounds_of = getattr(p, path.rounds_of)
    if p.steps % rounds_of:
        raise ValueError(f"{p.steps} steps are not whole rounds of "
                         f"{path.rounds_of}={rounds_of}")
    return p


def check_cell(cell: Cell) -> SolveParams:
    """Exit non-zero, before any set-up, where the cell cannot run as
    its configuration says: an unknown engine, a schedule its engine
    cannot take, or a mesh of another size than the cell's chips."""
    try:
        p = solve_params(cell.config)
    except (KeyError, ValueError) as e:
        sys.exit(f"bench: {cell.name}: configuration {cell.config_name}: "
                 f"{e.args[0]}")
    if p.chips != cell.chips:
        sys.exit(f"bench: {cell.name} asks for {cell.chips} chip(s); its "
                 f"configuration's solve runs on {p.chips}")
    return p


def check_device(chips: int) -> None:
    """Exit non-zero unless JAX sees ``chips`` chips of a kind in the
    peaks table: no result off the chip, and no fall-back to the CPU."""
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found {devs[0].platform!r}")
    try:
        peaks_mod.lookup(kind)
    except KeyError as e:
        sys.exit(f"bench: {e.args[0]}")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")


def _device_info(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def _memory_peaks(chips: int) -> List[Optional[int]]:
    """``peak_bytes_in_use`` of each of the cell's devices."""
    out = []
    for d in jax.devices()[:chips]:
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        out.append(int(peak) if peak is not None else None)
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log: Callable[[str], None] = log_err,
             control: bool = False) -> dict:
    """Run ``cell`` once; return the result line's object.

    With ``control``, the configuration's control (``control`` in its
    file: the reference in the precision below the configuration's)
    takes the program's place in the check, and no window runs: its
    ``correct`` has to come out false."""
    from repro import get_stencil

    p = check_cell(cell)
    path = solve_path(p.engine)
    st = get_stencil(p.stencil)
    if st.radius != p.radius:
        raise ValueError(f"config radius {p.radius} is not the program's "
                         f"{st.radius} for {p.stencil}")
    if st.ndim != len(p.shape):
        raise ValueError(f"config domain {p.shape} is not of the program's "
                         f"rank {st.ndim} for {p.stencil}")
    with CompileLog() as clog:
        t0 = time.perf_counter()
        x = make_domain(p.shape, seed, float(cell.traffic["low"]),
                        float(cell.traffic["high"]))
        log(f"setup: domain {_dims(p.shape)} f32 made in "
            f"{time.perf_counter() - t0!r} s")
        plan = path.compile(p, st)
        if control:
            result = {"correct": None, "attempted": 1, "failed": 0,
                      "metrics": {}, "device": _device_info(cell.chips)}
            return _check(result, cell, p, plan, x, None, seed, log,
                          cell.config["control"])
        ex = path.executor(p)
        t0 = time.perf_counter()
        what = path.warm_up(p, st, plan, ex, x)
        log(f"setup: warm-up of {what} {time.perf_counter() - t0!r} s"
            + _exec_note(ex.exec_stats))
        setup_s = time.perf_counter() - t_start
        clog.report("setup", log)

        clog.phase = "window"
        min_solves = int(cell.config.get("min_window_solves", 1))
        tracer = _Tracer() if trace else None
        solve_s: List[float] = []
        op_wall: Dict[str, float] = defaultdict(float)
        exec_wall = 0.0
        out = None
        if tracer:
            tracer.start()
        t_w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            while True:
                out = None      # at most one output held besides the input
                t0 = time.perf_counter()
                out, _ = ex.execute(plan, x)
                solve_s.append(time.perf_counter() - t0)
                es = ex.exec_stats
                exec_wall += es.wall_s
                for k, v in es.op_wall_s.items():
                    op_wall[k] += v
                if (time.perf_counter() - t_w0 >= seconds
                        and len(solve_s) >= min_solves):
                    break
        window_s = time.perf_counter() - t_w0
        if tracer:
            tracer.stop()
        clog.report("window", log)
        window_compiles = clog.count("window")
    peaks = _memory_peaks(cell.chips)
    known = [v for v in peaks if v is not None]
    memory_peak = max(known) if known else None
    log(f"memory: peak_bytes_in_use of each device {peaks!r}")
    del ex
    shown = solve_s if len(solve_s) <= 20 else (
        [min(solve_s), statistics.median(solve_s), max(solve_s)])
    log(f"window: {len(solve_s)} solves in {window_s!r} s; solve seconds "
        f"{'' if len(solve_s) <= 20 else '(min, median, max) '}{shown!r}; "
        f"kernel_impl={es.kernel_impl}; "
        f"compile events in window {window_compiles}")
    log(f"window: op_wall_s {dict(op_wall)!r} exec_wall_s {exec_wall!r}")

    device = _device_info(cell.chips)
    device["memory_peak_bytes"] = memory_peak
    result = {"correct": None, "attempted": len(solve_s), "failed": 0}
    if not trace:
        updates = len(solve_s) * p.interior_updates
        metrics = {"cell_updates_per_s": updates / window_s / 1e9,
                   "setup_s": setup_s}
        units = {m.name: m.unit for m in cell.end_to_end}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in metrics.items() if k in units}
    else:
        row = peaks_mod.lookup(device["kind"])
        f32 = peaks_mod.measure_f32_flops(log)
        summary = tracer.reduce(cell.config["kernel_pattern"], log,
                                cell.config.get("collective_pattern"))
        tracer.close()
        ctx = Context(cell=cell, params=p, plan=plan, solves=len(solve_s),
                      exec_wall_s=exec_wall, op_wall_s=dict(op_wall),
                      trace=summary, peaks=row, f32_flops_per_s=f32, log=log)
        result["metrics"] = read_metrics(cell, ctx)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
    result["device"] = device
    return _check(result, cell, p, plan, x, out, seed, log)


def _check(result: dict, cell: Cell, p: SolveParams, plan, x, out, seed: int,
           log: Callable[[str], None], control: Optional[str] = None) -> dict:
    """Compare ``out``, the solve of ``x`` (or the ``control`` in its
    place), with the reference; set ``correct``, ``failed`` and, last,
    ``checks`` in ``result``."""
    t0 = time.perf_counter()
    wins = reference.windows(p.shape, p.radius, seed=seed,
                             size=reference.window_size(p.shape),
                             **solve_path(p.engine).seams(plan))
    numbers = reference.compare(out, x, cell.config, p.steps, wins, control,
                                cell.update())
    limits = cell.config["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    log(f"check{f' of the control ({control})' if control else ''}: "
        f"{len(wins)} windows against the "
        f"reference in {time.perf_counter() - t0!r} s: "
        + ", ".join(w[0] for w in wins))
    result["correct"] = correct
    result["failed"] = 0 if correct else 1
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} value={v['value']!r} limit={v['limit']!r}")
    return result


def _dims(shape) -> str:
    return "x".join(str(n) for n in shape)


def _exec_note(es) -> str:
    """The executor's kernel notes after a warm-up that executed."""
    if es is None:
        return ""
    return (f", kernel_impl={es.kernel_impl} kernel_compiles="
            f"{es.kernel_compiles} shape_buckets={es.shape_buckets}")


def _shape_keys(plan) -> set:
    return {_shape_key(op) for op in plan.ops}


def _shape_key(op) -> tuple:
    """What of a plan op decides the programs it runs: its type and
    fields, with the host box by its extent, and no round, chunk or
    register name."""
    if type(op).__name__ == "HostCommit":
        return ("HostCommit",)
    fields = []
    for f in dataclasses.fields(op):
        v = getattr(op, f.name)
        if f.name in ("round", "chunk", "reg", "buf", "src"):
            continue
        if f.name == "box":
            v = tuple(h - lo for lo, h in zip(v.lo, v.hi))
        fields.append((f.name, v))
    return (type(op).__name__, tuple(fields))


def warm_up_plan(p: SolveParams, st, plan):
    """One round of the cell's schedule on the fewest leading rows of the
    domain whose chunks give every op shape of ``plan``: the first, a
    middle and the last chunk hold all of them where the chunks are
    equal.  Returns the plan and its row count (frame included)."""
    from repro import compile_plan

    steps = min(p.steps, p.s_tb)
    need = _shape_keys(plan)
    inner = p.Y - 2 * p.radius
    for d in range(min(3, p.d), p.d):
        if inner % p.d:
            break
        rows = inner // p.d * d + 2 * p.radius
        warm = compile_plan(p.engine, st, rows, p.X, steps, d, p.s_tb, p.k_on)
        if need <= _shape_keys(warm):
            return warm, rows
    if steps == p.steps:
        return plan, p.Y
    return compile_plan(p.engine, st, p.Y, p.X, steps, p.d, p.s_tb,
                        p.k_on), p.Y


def _chunk_starts(plan) -> List[int]:
    """First row each chunk after the first writes back: the places
    where region sharing hands rows between chunks."""
    from repro.core.plan import D2H

    starts = {op.box.lo[0] for op in plan.ops
              if isinstance(op, D2H) and op.chunk > 0}
    return sorted(starts)


def _plan_params(common: dict, sched: dict) -> SolveParams:
    return SolveParams(**common, d=int(sched["d"]), s_tb=int(sched["s_tb"]),
                       k_on=int(sched["k_on"]))


def _compile_plan(p: SolveParams, st):
    from repro import compile_plan

    return compile_plan(p.engine, st, p.Y, p.X, p.steps, p.d, p.s_tb,
                        p.k_on)


def _plan_executor(_: SolveParams):
    from repro.core.executor import DoubleBufferedExecutor
    from repro.kernels.dispatch import DispatchPolicy

    return DoubleBufferedExecutor(policy=DispatchPolicy())


def _warm_up_plan(p: SolveParams, st, plan, ex, x) -> str:
    warm, rows = warm_up_plan(p, st, plan)
    ex.execute(warm, x[:rows])
    return (f"{warm.op_counts().get('HostCommit', 0)} round(s) on "
            f"{rows}x{p.X} in {warm.d} chunk(s)")


def _plan_work(plan, p: SolveParams, flops_per_cell: int) -> List[work.Work]:
    from repro.core.plan import FusedKernel

    return work.fused_work([op for op in plan.ops
                            if isinstance(op, FusedKernel)],
                           p.radius, flops_per_cell, plan.itemsize)


def _mesh_params(common: dict, sched: dict) -> SolveParams:
    mesh = tuple(int(n) for n in sched["mesh"])
    if len(mesh) != 2 or min(mesh) < 1:
        raise ValueError(f"mesh {list(mesh)} is not two positive sizes")
    return SolveParams(**common, mesh=mesh, k_ici=int(sched["k_ici"]))


def _compile_sharded(p: SolveParams, st):
    from repro.core.shard import compile_sharded

    return compile_sharded(st, p.Y, p.X, p.steps, p.k_ici, p.mesh)


def _mesh_executor(p: SolveParams):
    """``ShardMapExecutor`` on a mesh of the first ``p.chips`` devices,
    rows over ``data`` and columns over ``model``."""
    from jax.sharding import AxisType
    from repro.core.executor import ShardMapExecutor

    mesh = jax.make_mesh(p.mesh, ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:p.chips])
    return ShardMapExecutor(mesh=mesh)


def _warm_up_mesh(p: SolveParams, st, plan, ex, x) -> str:
    """Compile the shard program the executor builds for ``plan``, for
    ``x`` sharded as the executor puts it, into the persistent cache;
    the window's solves trace it anew and load it from there."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core.distributed import distributed_stencil_step_fn

    fn = distributed_stencil_step_fn(plan.stencil, plan.k_ici, plan.n,
                                     ex.mesh, ex.row_axis, ex.col_axis)
    sharding = NamedSharding(ex.mesh, PartitionSpec(ex.row_axis,
                                                    ex.col_axis))
    fn.lower(jax.ShapeDtypeStruct(x.shape, x.dtype,
                                  sharding=sharding)).compile()
    return (f"the shard program (compiled, not run), {plan.rounds} round(s) "
            f"of k_ici={plan.k_ici} on {p.Y}x{p.X} over a "
            f"{p.mesh[0]}x{p.mesh[1]} mesh")


def _mesh_seams(plan) -> dict:
    """First row and first column of each shard after the first in its
    mesh axis: the places where halos cross between chips."""
    return {"chunk_starts": (),
            "seams": (sorted({sh.y0 for sh in plan.shards if sh.y0}),
                      sorted({sh.x0 for sh in plan.shards if sh.x0}))}


def _mesh_work(plan, p: SolveParams, flops_per_cell: int) -> List[work.Work]:
    from repro.core.plan import ShardKernel

    return [work.shard_work(op.gy0, op.gx0, op.h, op.w, p.Y, p.X, p.radius,
                            op.steps, flops_per_cell, plan.itemsize)
            for stream in plan.streams for op in stream
            if isinstance(op, ShardKernel)]


def _box_params(common: dict, sched: dict) -> SolveParams:
    tiles = tuple(int(t) for t in sched["tiles"])
    if len(tiles) != len(common["shape"]) or min(tiles) < 1:
        raise ValueError(f"tiles {list(tiles)} are not one positive count "
                         f"per axis of the domain {common['shape']}")
    return SolveParams(**common, tiles=tiles,
                       time_depth=int(sched["time_depth"]),
                       k_on=int(sched["k_on"]))


def _compile_box(p: SolveParams, st, shape=None, steps=None, tiles=None):
    from repro import compile_box_plan

    return compile_box_plan(st, shape or p.shape, steps or p.steps,
                            tiles or p.tiles, p.time_depth, p.k_on)


def warm_up_box_plan(p: SolveParams, st, plan):
    """One round of the cell's box schedule on the domain cut, along each
    axis of more than three equal tiles, to its first three: the first,
    a middle and the last tile of every axis give every op shape of
    ``plan``.  Where they do not, one round on the whole domain."""
    steps = min(p.steps, p.time_depth)
    r = p.radius
    shape, tiles = list(p.shape), list(p.tiles)
    for a, (n, t) in enumerate(zip(p.shape, p.tiles)):
        if t > 3 and (n - 2 * r) % t == 0:
            shape[a], tiles[a] = (n - 2 * r) // t * 3 + 2 * r, 3
    warm = _compile_box(p, st, tuple(shape), steps, tuple(tiles))
    if _shape_keys(plan) <= _shape_keys(warm):
        return warm
    if steps == p.steps:
        return plan
    return _compile_box(p, st, steps=steps)


def _warm_up_box(p: SolveParams, st, plan, ex, x) -> str:
    warm = warm_up_box_plan(p, st, plan)
    ex.execute(warm, x[tuple(slice(0, n) for n in warm.shape)])
    return (f"{warm.op_counts().get('HostCommit', 0)} round(s) on "
            f"{_dims(warm.shape)} in {warm.d} tile(s)")


def _tile_starts(plan) -> dict:
    """First index of each tile after the first along each axis: the
    places where one tile's apron reads another's cells."""
    from repro.core.plan import D2H

    boxes = [op.box for op in plan.ops if isinstance(op, D2H)]
    return {"chunk_starts": (),
            "seams": [sorted({b.lo[a] for b in boxes})[1:]
                      for a in range(len(plan.shape))]}


@dataclasses.dataclass(frozen=True)
class SolvePath:
    """What one kind of plan needs of a run."""

    schedule: Tuple[str, ...]  # the schedule keys the engine needs
    rounds_of: str             # the one whose whole multiples steps are
    params: Callable           # (common params, schedule) -> SolveParams
    compile: Callable          # (params, stencil) -> plan
    executor: Callable         # (params) -> executor
    # (params, stencil, plan, executor, domain) -> what it did: has every
    # program of the solve compiled and loaded before the window
    warm_up: Callable
    seams: Callable            # (plan) -> reference.windows' fixed places
    kernel_work: Callable      # (plan, params, flops/cell) -> [Work]


PLAN_PATH = SolvePath(
    schedule=("d", "s_tb", "k_on"), rounds_of="s_tb", params=_plan_params,
    compile=_compile_plan, executor=_plan_executor, warm_up=_warm_up_plan,
    seams=lambda plan: {"chunk_starts": _chunk_starts(plan)},
    kernel_work=_plan_work)

BOX_PATH = SolvePath(
    schedule=("tiles", "time_depth", "k_on"), rounds_of="time_depth",
    params=_box_params, compile=_compile_box, executor=_plan_executor,
    warm_up=_warm_up_box, seams=_tile_starts, kernel_work=_plan_work)

SHARDED_PATH = SolvePath(
    schedule=("mesh", "k_ici"), rounds_of="k_ici", params=_mesh_params,
    compile=_compile_sharded, executor=_mesh_executor, warm_up=_warm_up_mesh,
    seams=_mesh_seams, kernel_work=_mesh_work)

# engine name -> path: every single-device engine of compile_plan, but
# box_tb, which runs its box plan of any rank, and the plan sharded over
# a chip mesh
PATHS = {**{name: PLAN_PATH for name in ENGINES}, "box_tb": BOX_PATH,
         "sharded": SHARDED_PATH}


def solve_path(engine: str) -> SolvePath:
    """The path of a configuration's ``engine``; ``KeyError`` when no
    path runs it."""
    if engine not in PATHS:
        raise KeyError(f"unknown engine {engine!r}; known: {sorted(PATHS)}")
    return PATHS[engine]


class _Tracer:
    """The profiler around the window, writing under ``TMPDIR``."""

    def __init__(self):
        self.dir = tempfile.TemporaryDirectory(prefix="bench-trace-")

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir.name, profiler_options=opts)

    def stop(self) -> None:
        jax.profiler.stop_trace()

    def reduce(self, kernel_pattern: str, log,
               collective_pattern: Optional[str] = None) -> TraceSummary:
        import glob

        paths = glob.glob(f"{self.dir.name}/**/*.xplane.pb", recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        t0 = time.perf_counter()
        summary = reduce_trace(paths[0], WINDOW_SPAN, kernel_pattern,
                               collective_pattern)
        log(f"trace: read in {time.perf_counter() - t0!r} s; "
            f"window_s={summary.window_s!r} busy_s={summary.busy_s!r} "
            f"kernel_s={summary.kernel_s!r} kernel_calls="
            f"{summary.kernel_calls} device_lines={summary.lines}")
        if collective_pattern:
            log(f"trace: collective_s={summary.collective_s!r} "
                f"collective_calls={summary.collective_calls} "
                f"({collective_pattern!r})")
        return summary

    def close(self) -> None:
        self.dir.cleanup()
