"""One run of one cell: set-up, the measured window, and the check.

The window drives the system's own path, ``compile_plan(engine, ...)``
into ``DoubleBufferedExecutor(policy=DispatchPolicy()).execute(plan,
x)``, whole solves back to back in a closed loop, one at a time, each
from the host array in to the host array out.  It stops at the first
solve that ends after ``seconds``.  Set-up, which :func:`run_cell`
times from the process start its caller gives, makes the domain from
the seed and warms every shape up by running one round on the fewest
leading rows of it whose plan has every op shape of the solve.
"""
from __future__ import annotations

import dataclasses
import statistics
import sys
import tempfile
import time
from collections import defaultdict
from typing import Callable, Dict, List, Optional

import jax

from bench import peaks as peaks_mod
from bench import reference
from bench.spec import Cell, read_metrics
from bench.trace import TraceSummary, reduce_trace
from bench.workload import SolveParams, make_domain, solve_params

# compile events are JAX's own monitoring events under this prefix
COMPILE_EVENTS = "/jax/core/compile/"
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

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.startswith(COMPILE_EVENTS):
            slot = self.events[self.phase][(event[len(COMPILE_EVENTS):],
                                            kw.get("fun_name", ""))]
            slot[0] += 1
            slot[1] += duration

    def __enter__(self) -> "CompileLog":
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)

    def count(self, phase: str) -> int:
        return sum(c for c, _ in self.events[phase].values())

    def report(self, phase: str, log: Callable[[str], None]) -> None:
        rows = sorted(self.events[phase].items(), key=lambda kv: -kv[1][1])
        total = sum(s for _, s in self.events[phase].values())
        log(f"compile events in {phase}: {self.count(phase)} events, "
            f"{total!r} s")
        for (event, fun), (n, s) in rows:
            log(f"  compile {phase} {event} {fun} n={n} s={s!r}")


@dataclasses.dataclass
class Context:
    """What a per-layer reader (``bench/metrics/<name>.py``) reads."""

    cell: Cell
    params: SolveParams
    plan: object                 # the solve's ExecutionPlan
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


def _memory_peak() -> Optional[int]:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log: Callable[[str], None] = log_err,
             control: bool = False) -> dict:
    """Run ``cell`` once; return the result line's object.

    With ``control``, the configuration's control (``control`` in its
    file: the reference in the precision below the configuration's)
    takes the program's place in the check, and no window runs: its
    ``correct`` has to come out false."""
    from repro import compile_plan, get_stencil
    from repro.core.executor import DoubleBufferedExecutor
    from repro.kernels.dispatch import DispatchPolicy

    p = solve_params(cell.config)
    st = get_stencil(p.stencil)
    if st.radius != p.radius:
        raise ValueError(f"config radius {p.radius} is not the program's "
                         f"{st.radius} for {p.stencil}")
    with CompileLog() as clog:
        t0 = time.perf_counter()
        x = make_domain((p.Y, p.X), seed, float(cell.traffic["low"]),
                        float(cell.traffic["high"]))
        log(f"setup: domain {p.Y}x{p.X} f32 made in "
            f"{time.perf_counter() - t0!r} s")
        plan = compile_plan(p.engine, st, p.Y, p.X, p.steps, p.d, p.s_tb,
                            p.k_on)
        if control:
            result = {"correct": None, "attempted": 1, "failed": 0,
                      "metrics": {}, "device": _device_info(cell.chips)}
            return _check(result, cell, p, plan, x, None, seed, log,
                          cell.config["control"])
        warm, warm_rows = warm_up_plan(p, st, plan)
        ex = DoubleBufferedExecutor(policy=DispatchPolicy())
        t0 = time.perf_counter()
        ex.execute(warm, x[:warm_rows])
        es = ex.exec_stats
        log(f"setup: warm-up of {warm.op_counts().get('HostCommit', 0)} "
            f"round(s) on {warm_rows}x{p.X} in {warm.d} chunk(s) "
            f"{time.perf_counter() - t0!r} s, kernel_impl="
            f"{es.kernel_impl} kernel_compiles={es.kernel_compiles} "
            f"shape_buckets={es.shape_buckets}")
        del warm
        setup_s = time.perf_counter() - t_start
        clog.report("setup", log)

        clog.phase = "window"
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
                if time.perf_counter() - t_w0 >= seconds:
                    break
        window_s = time.perf_counter() - t_w0
        if tracer:
            tracer.stop()
        clog.report("window", log)
        window_compiles = clog.count("window")
    memory_peak = _memory_peak()
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
        summary = tracer.reduce(cell.config["kernel_pattern"], log)
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
    chunk_starts = _chunk_starts(plan)
    wins = reference.windows((p.Y, p.X), p.radius, chunk_starts, seed,
                             size=reference.window_size((p.Y, p.X)))
    numbers = reference.compare(out, x, cell.config, p.steps, wins, control)
    limits = cell.config["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    log(f"check{f' of the control ({control})' if control else ''}: "
        f"{len(wins)} windows against the "
        f"reference in {time.perf_counter() - t0!r} s: "
        + ", ".join(label for label, _, _ in wins))
    result["correct"] = correct
    result["failed"] = 0 if correct else 1
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} value={v['value']!r} limit={v['limit']!r}")
    return result


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
    need = {_shape_key(op) for op in plan.ops}
    inner = p.Y - 2 * p.radius
    for d in range(min(3, p.d), p.d):
        if inner % p.d:
            break
        rows = inner // p.d * d + 2 * p.radius
        warm = compile_plan(p.engine, st, rows, p.X, steps, d, p.s_tb, p.k_on)
        if need <= {_shape_key(op) for op in warm.ops}:
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

    def reduce(self, kernel_pattern: str, log) -> TraceSummary:
        import glob

        paths = glob.glob(f"{self.dir.name}/**/*.xplane.pb", recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        t0 = time.perf_counter()
        summary = reduce_trace(paths[0], WINDOW_SPAN, kernel_pattern)
        log(f"trace: read in {time.perf_counter() - t0!r} s; "
            f"window_s={summary.window_s!r} busy_s={summary.busy_s!r} "
            f"kernel_s={summary.kernel_s!r} kernel_calls="
            f"{summary.kernel_calls} device_lines={summary.lines}")
        return summary

    def close(self) -> None:
        self.dir.cleanup()
