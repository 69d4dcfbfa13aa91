"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run
    PYTHONPATH=src python -m benchmarks.run --dry-run
    PYTHONPATH=src python -m benchmarks.run --dry-run --codec all --json BENCH_plan.json
    PYTHONPATH=src python -m benchmarks.run --exec --executor double_buffered \
        --fused-step reference --json BENCH_exec.json

Prints ``name,us_per_call,derived`` CSV.  Rows labeled ``measured_cpu``
are wall-clock on this container; ``modeled`` rows evaluate the paper's
Sec. III analytic model over exact TransferStats geometry with RTX-3080
(paper-validation) or TPU-v5e (deployment-target) constants.  The
roofline rows read the multi-pod dry-run artifacts if present.

``--dry-run`` compiles the transfer/kernel op schedule for every engine x
paper stencil at the full out-of-core size and walks it with the dry-run
executor — plan construction and plan-derived accounting are exercised
end-to-end with zero device work (the CI smoke job).  Each record also
carries the deterministic lowering metrics (stage count, shape buckets =
max kernel compiles) from :func:`repro.core.lower.lower`.  ``--codec``
sweeps transfer codecs (``all`` = every registered codec) and reports raw
vs wire bytes; ``--json`` writes the records as machine-readable JSON for
the CI bench-gate (``benchmarks/check_regression.py`` diffs byte and
op-count/cache metrics against the committed ``benchmarks/baselines.json``).

``--exec`` *executes* every engine x paper stencil at a small real size
through the lowered executors (``--executor``, ``--fused-step`` pick the
interpreter and the kernel-dispatch implementation) and reports the
:class:`~repro.core.lower.ExecStats` wall-clock-per-op-class and
compilation-cache counters.  Timings are machine-dependent and never
gate CI; the JSON is uploaded as a non-gating artifact.

``--dry-run`` also sweeps plan *geometry*: ``--chunk-axis 1`` reorients
the 2-D engine sweep to column chunking (keys gain an ``/axisA``
suffix), and ``--tile T0,T1[,T2]`` / ``--time-depth T[,T...]`` override
the committed box_tb tile-grid x time-depth sweep on the 3-D
``heat3d1r`` workload.  Every dry-run record carries its box geometry
(``shape``, ``chunk_axis``, ``tiles``, ``time_depth``).

``--inject-fault`` is the chaos smoke (the CI ``chaos`` job): a small
SO2DR run with a seeded transient-fault schedule absorbed by the retry
loop, then a terminal kernel fault at every round recovered through the
HostCommit checkpoint/resume path — each variant must be bit-identical
to the uninterrupted run (exit code 1 on any mismatch).

Unknown ``--engine``/``--codec``/``--executor``/``--fused-step`` names,
geometry flags outside ``--dry-run``, and infeasible ``--tile`` x
``--time-depth`` combinations (apron deeper than a tile) are a hard
error (exit code 2), not a silent skip.
"""
import argparse
import json
import sys

# --exec workload: small enough to run on a CPU container in seconds,
# big enough that every engine produces multi-chunk, multi-round plans
EXEC_SZ = 192
EXEC_STEPS = 8
EXEC_D = 4
EXEC_S_TB = 4
EXEC_K_ON = 2


def _resolve_names(requested, known, kind, parser):
    """Expand 'all' and validate names against a registry; exit 2 on
    unknown names instead of silently skipping them."""
    if requested in (None, "all"):
        return sorted(known)
    names = [s for s in requested.split(",") if s]
    for name in names:
        if name not in known:
            parser.error(
                f"unknown {kind} {name!r}; known: {sorted(known)} (or 'all')")
    return names


def _write_json(records, json_path) -> None:
    with open(json_path, "w") as f:
        json.dump(records, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {len(records)} records to {json_path}", file=sys.stderr)


# sharded (L2) dry-run workload: the full 38400^2 framed domain over a
# 4x2 chip mesh, k_ici sweeping the per-step-exchange baseline (k=1)
# against communication-avoiding depths
SHARD_MESH = (4, 2)
SHARD_K_ICI = (1, 4, 8)

# hierarchical dry-run workload: 1024^3 heat3d1r (trailing third axis)
# over a 2x2 mesh whose shard working sets exceed a 1 GiB device budget,
# so each ShardKernel expands into a nested box_tb streaming program;
# the halo codec sweep shows ici_wire_bytes trading against raw payload
HIER_STENCIL = "heat3d1r"
HIER_SIDE = 1026                  # framed Y = X (interior 1024)
HIER_TRAILING = (1026,)
HIER_MESH = (2, 2)
HIER_K_ICI = 4
HIER_STEPS = 16
HIER_C_DEV = 1 << 30
HIER_CODECS = ("identity", "zrle", "bf16")

# 3-D box temporal-blocking dry-run workload: a 1024^3 interior (4.3 GB
# per array — out-of-core on the paper's 10 GB GPU), tile grids on the
# leading two axes x time depths.  Geometry only: the dry-run executor
# never allocates the domain.
BOX_STENCIL = "heat3d1r"
BOX_SHAPE = (1026, 1026, 1026)
BOX_STEPS = 16
BOX_TILES = ((2, 2), (4, 4))
BOX_DEPTHS = (2, 4)


def _plan_geometry(plan) -> dict:
    """Box geometry of a compiled plan, recorded with every dry-run row."""
    return {
        "shape": list(plan.shape),
        "chunk_axis": plan.chunk_axis,
        "tiles": list(plan.tiles) if plan.tiles else [plan.d],
        "time_depth": plan.k_off,
    }


def _box_records(ex, records, codecs, tile_grid=BOX_TILES,
                 depths=BOX_DEPTHS) -> None:
    from repro.core.compress import compress_plan
    from repro.core.lower import lower
    from repro.core.oocore import compile_box_plan
    from repro.core.stencil import get_stencil

    st = get_stencil(BOX_STENCIL)
    for tiles in tile_grid:
        for t in depths:
            base = compile_box_plan(st, BOX_SHAPE, BOX_STEPS, tiles, t)
            for codec in codecs:
                plan = compress_plan(base, codec)
                _, s = ex.execute(plan)
                lowering = lower(plan).describe()
                tag = "x".join(str(x) for x in tiles)
                key = f"{BOX_STENCIL}/box_tb/tiles{tag}/t{t}/{codec}"
                print(f"dryrun/{key},{len(plan)},"
                      f"wire_gb={s.wire_bytes / 1e9:.2f} "
                      f"odc_gb={s.buffer_bytes / 1e9:.2f} "
                      f"kernels={s.kernel_calls} "
                      f"redundancy={s.redundancy:.4f}")
                records[key] = {
                    "plan_ops": len(plan),
                    "raw_bytes": s.transfer_bytes,
                    "wire_bytes": s.wire_bytes,
                    "h2d_wire_bytes": s.h2d_wire_bytes,
                    "d2h_wire_bytes": s.d2h_wire_bytes,
                    "buffer_bytes": s.buffer_bytes,
                    "kernel_calls": s.kernel_calls,
                    "redundant_elements": s.redundant_elements,
                    "stage_count": lowering["stage_count"],
                    "shape_buckets": lowering["shape_buckets"],
                    "box": _plan_geometry(plan),
                }


def _sharded_records(ex, records) -> None:
    from repro.core.shard import compile_sharded
    from repro.core.stencil import PAPER_BENCHMARKS

    from .common import N_STEPS, OOC_SZ

    for name in PAPER_BENCHMARKS:
        for k_ici in SHARD_K_ICI:
            plan = compile_sharded(name, OOC_SZ, OOC_SZ, N_STEPS, k_ici,
                                   SHARD_MESH)
            _, s = ex.execute(plan)
            key = (f"sharded/{name}/mesh{SHARD_MESH[0]}x{SHARD_MESH[1]}"
                   f"/k{k_ici}")
            print(f"dryrun/{key},{len(plan)},"
                  f"ici_gb={s.ici_bytes / 1e9:.2f} "
                  f"per_round_mb={plan.collective_bytes_per_round / 1e6:.2f} "
                  f"halo_ops={s.halo_ops} "
                  f"kernels={s.kernel_calls} "
                  f"redundancy={s.redundancy:.6f}")
            records[key] = {
                "plan_ops": len(plan),
                "raw_bytes": s.transfer_bytes,
                "ici_bytes": s.ici_bytes,
                "collective_bytes_per_round": plan.collective_bytes_per_round,
                "halo_ops": s.halo_ops,
                "kernel_calls": s.kernel_calls,
                "redundant_elements": s.redundant_elements,
                "stage_count": len(plan.barriers),
            }


def _hierarchy_records(ex, records) -> None:
    from repro.core.hierarchy import compile_hierarchical

    for codec in HIER_CODECS:
        plan = compile_hierarchical(
            HIER_STENCIL, HIER_SIDE, HIER_SIDE, HIER_STEPS, HIER_K_ICI,
            HIER_MESH, c_dev=HIER_C_DEV, inner_engine="box_tb",
            codec=None if codec == "identity" else codec,
            trailing=HIER_TRAILING)
        _, s = ex.execute(plan)
        key = (f"hier/{HIER_STENCIL}/mesh{HIER_MESH[0]}x{HIER_MESH[1]}"
               f"/k{HIER_K_ICI}/{codec}")
        print(f"dryrun/{key},{len(plan)},"
              f"ici_gb={s.ici_bytes / 1e9:.2f} "
              f"ici_wire_gb={s.ici_wire_bytes / 1e9:.2f} "
              f"h2d_gb={s.h2d_bytes / 1e9:.2f} "
              f"inner_chunks={plan.inner_chunks} "
              f"kernels={s.kernel_calls} "
              f"redundancy={s.redundancy:.4f}")
        records[key] = {
            "plan_ops": len(plan),
            "raw_bytes": s.transfer_bytes,
            "wire_bytes": s.wire_bytes,
            "buffer_bytes": s.buffer_bytes,
            "ici_bytes": s.ici_bytes,
            "ici_wire_bytes": s.ici_wire_bytes,
            "collective_bytes_per_round": plan.collective_bytes_per_round,
            "collective_wire_bytes_per_round":
                plan.collective_wire_bytes_per_round,
            "halo_ops": s.halo_ops,
            "codec_ops": s.codec_ops,
            "kernel_calls": s.kernel_calls,
            "inner_chunks": plan.inner_chunks,
            "redundant_elements": s.redundant_elements,
            "stage_count": len(plan.barriers),
        }


def dry_run(engines, codecs, json_path=None, chunk_axis=0,
            tile_grid=BOX_TILES, depths=BOX_DEPTHS) -> None:
    from repro.core.compress import compress_plan
    from repro.core.executor import DryRunExecutor
    from repro.core.lower import lower
    from repro.core.stencil import PAPER_BENCHMARKS

    from .common import OOC_SZ, PAPER_CONFIG, paper_plan

    print("name,plan_ops,derived")
    ex = DryRunExecutor()
    records = {}
    for name in PAPER_BENCHMARKS:
        d, s_tb = PAPER_CONFIG[name]
        for engine in engines:
            base = paper_plan(engine, name, OOC_SZ, d, s_tb,
                              chunk_axis=chunk_axis)
            for codec in codecs:
                plan = compress_plan(base, codec)
                _, s = ex.execute(plan)
                # deterministic lowering metrics: stage programs + shape
                # buckets (= the kernel-compile ceiling), no execution
                lowering = lower(plan).describe()
                key = f"{name}/{engine}/{codec}"
                if chunk_axis:
                    key += f"/axis{chunk_axis}"
                print(f"dryrun/{key},{len(plan)},"
                      f"h2d_gb={s.h2d_bytes / 1e9:.2f} "
                      f"d2h_gb={s.d2h_bytes / 1e9:.2f} "
                      f"wire_gb={s.wire_bytes / 1e9:.2f} "
                      f"ratio={s.compression_ratio:.3f} "
                      f"odc_gb={s.buffer_bytes / 1e9:.2f} "
                      f"kernels={s.kernel_calls} "
                      f"buckets={lowering['shape_buckets']} "
                      f"redundancy={s.redundancy:.4f}")
                records[key] = {
                    "plan_ops": len(plan),
                    "raw_bytes": s.transfer_bytes,
                    "wire_bytes": s.wire_bytes,
                    "h2d_wire_bytes": s.h2d_wire_bytes,
                    "d2h_wire_bytes": s.d2h_wire_bytes,
                    "buffer_bytes": s.buffer_bytes,
                    "kernel_calls": s.kernel_calls,
                    "stage_count": lowering["stage_count"],
                    "shape_buckets": lowering["shape_buckets"],
                    "box": _plan_geometry(plan),
                }
    # 3-D box temporal-blocking plans (trapezoid aprons), the multi-chip
    # (L2) sharded plans (ICI + ghost-wedge accounting), then the
    # hierarchical plans (nested L1 streaming inside shards, halo-codec
    # wire bytes) — all gated by check_regression.py next to the row
    # byte records
    if chunk_axis == 0:
        _box_records(ex, records, codecs, tile_grid, depths)
        _sharded_records(ex, records)
        _hierarchy_records(ex, records)
    if json_path:
        _write_json(records, json_path)


def exec_bench(engines, codecs, executor_name, fused_impl,
               json_path=None, profile=None) -> None:
    import numpy as np

    from repro.core.autotune import predicted_makespan
    from repro.core.executor import get_executor
    from repro.core.oocore import compile_plan
    from repro.core.stencil import PAPER_BENCHMARKS, get_stencil
    from repro.kernels.dispatch import DispatchPolicy

    hw_prof = profile.as_hardware() if profile is not None else None
    print("name,wall_ms,derived")
    records = {}
    policy = DispatchPolicy(impl=fused_impl)
    for name in PAPER_BENCHMARKS:
        st = get_stencil(name)
        Y = X = EXEC_SZ + 2 * st.radius
        x = np.random.default_rng(42).standard_normal((Y, X)).astype(np.float32)
        for engine in engines:
            d_eff = 1 if engine == "incore" else EXEC_D
            k_on = 1 if engine == "resreu" else EXEC_K_ON
            for codec in codecs:
                plan = compile_plan(engine, st, Y, X, EXEC_STEPS, d_eff,
                                    EXEC_S_TB, k_on, codec=codec)
                ex = get_executor(executor_name, policy=policy)
                _, _ = ex.execute(plan, x)
                es = ex.exec_stats
                derived = ""
                if hw_prof is not None:
                    # calibrated prediction vs this run's wall clock —
                    # the per-record model-vs-measured attribution
                    es.modeled_s = predicted_makespan(plan, hw_prof)
                    es.model_error = ((es.modeled_s - es.wall_s)
                                      / max(es.wall_s, 1e-12))
                    derived = (f" modeled_ms={es.modeled_s * 1e3:.1f} "
                               f"model_err={es.model_error:+.2f}")
                key = f"{name}/{engine}/{codec}"
                print(f"exec/{key},{es.wall_s * 1e3:.1f},"
                      f"impl={es.kernel_impl} "
                      f"kernels={es.kernel_calls} "
                      f"compiles={es.kernel_compiles} "
                      f"hits={es.kernel_cache_hits} "
                      f"buckets={es.shape_buckets} "
                      f"stages={es.stage_count}" + derived)
                rec = es.as_dict()
                rec["executor"] = executor_name
                if profile is not None:
                    rec["profile_id"] = profile.profile_id
                records[key] = rec
    if json_path:
        _write_json(records, json_path)


def inject_fault_smoke(seed: int) -> int:
    """Chaos smoke: faulted runs must stay bit-identical to clean runs.

    Two drills on a small SO2DR workload (zero devices beyond the CPU
    backend): a seeded transient-transfer schedule fully absorbed by the
    bounded-backoff retry loop, and a terminal kernel fault at every
    round recovered through ``run_with_recovery`` + the HostCommit
    checkpointer.  Returns a process exit code (1 = a recovered run
    diverged from the uninterrupted one)."""
    import tempfile

    import numpy as np

    from repro.checkpoint import CheckpointManager
    from repro.core.executor import EagerExecutor
    from repro.core.faults import (
        KERNEL_FAULT, FaultPlan, FaultTrigger, RetryPolicy,
    )
    from repro.core.oocore import compile_plan
    from repro.core.recovery import PlanCheckpointer, run_with_recovery
    from repro.core.stencil import get_stencil

    st = get_stencil("star2d1r")
    plan = compile_plan("so2dr", st, 64, 32, 8, 2, 4, 2)
    x = np.random.default_rng(seed).standard_normal((64, 32)) \
        .astype(np.float32)
    ref, _ = EagerExecutor().execute(plan, x)
    retry = RetryPolicy(sleep=lambda s: None)
    failures = 0

    print("name,ok,derived")
    faults = FaultPlan.seeded(seed, plan, n_faults=3)
    ex = EagerExecutor()
    host, _ = run_with_recovery(plan, x, executor=ex, faults=faults,
                                retry=retry)
    ok = np.array_equal(host, ref)
    failures += not ok
    print(f"chaos/transient_seeded,{int(ok)},"
          f"faults={ex.exec_stats.faults_injected} "
          f"retries={ex.exec_stats.retries}")

    for rnd in sorted({op.round for op in plan.ops}):
        faults = FaultPlan([FaultTrigger(round=rnd, chunk=None,
                                         op_class="*", kind=KERNEL_FAULT)])
        ex = EagerExecutor()
        with tempfile.TemporaryDirectory() as d:
            host, _ = run_with_recovery(
                plan, x, executor=ex, faults=faults,
                checkpoint=PlanCheckpointer(CheckpointManager(d), plan))
        ok = np.array_equal(host, ref)
        failures += not ok
        print(f"chaos/kernel_fault_round{rnd},{int(ok)},"
              f"resumes={ex.exec_stats.resumes} "
              f"faults={ex.exec_stats.faults_injected}")

    if failures:
        print(f"chaos: {failures} recovered run(s) diverged from the "
              f"uninterrupted reference", file=sys.stderr)
        return 1
    print("chaos: every faulted run bit-identical to the clean run",
          file=sys.stderr)
    return 0


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true",
                    help="compile + cost every engine's plan, no device work")
    ap.add_argument("--exec", dest="exec_bench", action="store_true",
                    help="execute every engine at a small size; report "
                         "ExecStats wall clock + cache counters (non-gating)")
    ap.add_argument("--engine", default="all",
                    help="comma-separated engine names, or 'all' (default)")
    ap.add_argument("--codec", default="identity",
                    help="comma-separated transfer codecs, or 'all' "
                         "(default: identity — uncompressed wire bytes)")
    ap.add_argument("--executor", default="eager",
                    help="executor for --exec (eager | double_buffered)")
    ap.add_argument("--fused-step", default="auto",
                    help="kernel-dispatch impl for --exec "
                         "(auto | reference | pallas | pallas_db | mxu)")
    ap.add_argument("--inject-fault", action="store_true",
                    help="chaos smoke: seeded fault injection + "
                         "checkpoint/resume must stay bit-identical to "
                         "the clean run (exit 1 on divergence)")
    ap.add_argument("--fault-seed", type=int, default=0, metavar="S",
                    help="seed for the --inject-fault schedule (default 0)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write dry-run/exec records as JSON")
    ap.add_argument("--profile", metavar="PATH", default=None,
                    help="DeviceProfile JSON (benchmarks/calibrate.py): "
                         "price modeled rows with the calibrated constants "
                         "— --exec records gain modeled_s/model_error, the "
                         "measured suite adds a profile-priced autotune row")
    ap.add_argument("--chunk-axis", type=int, default=0, metavar="A",
                    help="streaming axis for the --dry-run engine sweep "
                         "(0 = the paper's row chunking; 1 = column "
                         "chunking of the same 2-D domains)")
    ap.add_argument("--tile", default=None, metavar="T0,T1[,T2]",
                    help="tile grid for the --dry-run box_tb sweep, e.g. "
                         "'2,2' (default: the committed "
                         f"{'/'.join('x'.join(map(str, t)) for t in BOX_TILES)} grids)")
    ap.add_argument("--time-depth", default=None, metavar="T[,T...]",
                    help="time depth(s) per H2D round trip for the "
                         "--dry-run box_tb sweep (default: "
                         f"{','.join(map(str, BOX_DEPTHS))})")
    args = ap.parse_args(argv)

    from repro.core.compress import CODECS
    from repro.core.executor import PLAN_EXECUTORS
    from repro.core.oocore import ENGINES, compile_box_plan
    from repro.core.stencil import get_stencil
    from repro.kernels.dispatch import KERNEL_IMPLS

    engines = _resolve_names(args.engine, ENGINES, "engine", ap)
    codecs = _resolve_names(args.codec, CODECS, "codec", ap)

    if sum((args.dry_run, args.exec_bench, args.inject_fault)) > 1:
        ap.error("--dry-run, --exec, and --inject-fault are mutually "
                 "exclusive")
    if args.fault_seed != 0 and not args.inject_fault:
        ap.error("--fault-seed only applies to --inject-fault")
    profile = None
    if args.profile is not None:
        if args.dry_run or args.inject_fault:
            ap.error("--profile applies where a Hardware is implied "
                     "(--exec and the measured suite); dry-run records "
                     "are plan geometry and the chaos smoke prices "
                     "nothing")
        from repro.core.calibrate import DeviceProfile, ProfileError
        try:
            profile = DeviceProfile.load(args.profile)
        except (OSError, ProfileError, ValueError) as e:
            ap.error(f"--profile {args.profile!r}: {e}")
    if args.inject_fault:
        if args.json or args.engine != "all" or args.codec != "identity":
            ap.error("--inject-fault takes only --fault-seed (the chaos "
                     "smoke runs one committed workload)")
        sys.exit(inject_fault_smoke(args.fault_seed))
    box_flags = args.tile is not None or args.time_depth is not None
    if (args.chunk_axis != 0 or box_flags) and not args.dry_run:
        ap.error("--chunk-axis/--tile/--time-depth only apply to --dry-run "
                 "(plan geometry knobs; the measured/exec paths run the "
                 "committed configurations)")
    if args.chunk_axis not in (0, 1):
        ap.error(f"--chunk-axis must be 0 or 1 for the 2-D paper domains, "
                 f"got {args.chunk_axis}")
    if args.chunk_axis != 0 and box_flags:
        ap.error("--tile/--time-depth sweep the box_tb engine on the 3-D "
                 "workload; --chunk-axis reorients the 2-D row sweep — "
                 "pick one")
    tile_grid, depths = BOX_TILES, BOX_DEPTHS
    if args.tile is not None:
        try:
            tiles = tuple(int(s) for s in args.tile.split(","))
        except ValueError:
            ap.error(f"--tile expects comma-separated integers, "
                     f"got {args.tile!r}")
        if not tiles or any(t < 1 for t in tiles) or len(tiles) > len(BOX_SHAPE):
            ap.error(f"--tile needs 1..{len(BOX_SHAPE)} counts >= 1, "
                     f"got {args.tile!r}")
        tile_grid = (tiles,)
    if args.time_depth is not None:
        try:
            depths = tuple(int(s) for s in args.time_depth.split(","))
        except ValueError:
            ap.error(f"--time-depth expects comma-separated integers, "
                     f"got {args.time_depth!r}")
        if not depths or any(t < 1 for t in depths):
            ap.error(f"--time-depth needs positive integers, "
                     f"got {args.time_depth!r}")
    if box_flags:
        # fail fast on infeasible geometry (apron deeper than a tile)
        # instead of half-writing a record set
        st = get_stencil(BOX_STENCIL)
        for tiles in tile_grid:
            for t in depths:
                try:
                    compile_box_plan(st, BOX_SHAPE, 1, tiles, t)
                except ValueError as e:
                    ap.error(f"--tile {','.join(map(str, tiles))} "
                             f"--time-depth {t}: {e}")
    if args.dry_run:
        dry_run(engines, codecs, json_path=args.json,
                chunk_axis=args.chunk_axis, tile_grid=tile_grid,
                depths=depths)
        return
    if args.exec_bench:
        # the sharded executors interpret ShardedPlans, not the
        # single-device engine schedules --exec sweeps
        if args.executor not in PLAN_EXECUTORS:
            ap.error(f"unknown --executor {args.executor!r}; known: "
                     f"{sorted(PLAN_EXECUTORS)}")
        if args.fused_step != "auto" and args.fused_step not in KERNEL_IMPLS:
            ap.error(f"unknown --fused-step {args.fused_step!r}; known: "
                     f"{sorted(KERNEL_IMPLS)} (or 'auto')")
        exec_bench(engines, codecs, args.executor, args.fused_step,
                   json_path=args.json, profile=profile)
        return
    if args.json or args.engine != "all" or args.codec != "identity":
        ap.error("--engine/--codec/--json only apply to --dry-run/--exec; "
                 "the measured path always runs the full figure suite")
    if profile is not None:
        # autotune_bench reads TUNE_PROFILE: the measured suite gains a
        # row priced with this machine's calibrated constants
        import os
        os.environ["TUNE_PROFILE"] = args.profile

    from . import (
        autotune_bench, fig5_config_sweep, fig6_so2dr_vs_resreu,
        fig7_breakdown, fig7_codec_breakdown, fig8_single_step,
        fig9_incore_vs_oocore, kernel_micro, roofline,
    )
    from .common import emit

    print("name,us_per_call,derived")
    failed = []
    for mod in (fig6_so2dr_vs_resreu, fig7_breakdown, fig7_codec_breakdown,
                fig5_config_sweep, fig8_single_step, fig9_incore_vs_oocore,
                autotune_bench, kernel_micro):
        try:
            emit(mod.run())
        except Exception as e:  # report every module, then fail the run
            print(f"{mod.__name__},0,ERROR {e}", file=sys.stdout)
            failed.append(mod.__name__)
    try:
        rows = roofline.run()
        if rows:
            emit(rows)
        else:
            print("roofline,0,no dry-run artifacts "
                  "(run: PYTHONPATH=src python -m repro.launch.dryrun --all)")
    except Exception as e:
        print(f"roofline,0,ERROR {e}")
        failed.append(roofline.__name__)
    if failed:
        sys.exit(f"benchmark modules raised: {', '.join(failed)}")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
