#!/usr/bin/env python3
"""Smoke run of the SO2DR out-of-core path on a TPU v5e.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # 2x2 mesh: the sharded plan only

Phase A drives the main path, ``compile_plan("so2dr", ...)`` into
``DoubleBufferedExecutor()`` with the default dispatch policy, at the
paper's out-of-core domain (38400^2 interior plus frame, f32; Table III):
box2d1r on the DMA-overlapped Pallas kernel and box2d4r on the banded
MXU kernel.  Phase B runs a ``StencilService`` batch twice; the second
flush of the same shapes must compile no kernel.  ``--four-chips``
runs a sharded box2d1r plan through ``ShardMapExecutor`` on a 2x2 mesh.

Results are checked against ``run_reference``: on the full domain for
the service jobs, and on windows cropped with an ``n*r`` margin (the
dependency cone of ``n`` steps) for the 38400^2 domains.

Every phase runs in this one process.  Without a TPU v5e the script
exits non-zero before any phase and prints no result line.  The seconds
it prints are timings of one cold smoke run, not benchmark numbers.  On
success the last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro import compile_cache  # noqa: E402  (before any JAX work)

CACHE = compile_cache.enable()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import (  # noqa: E402
    TPU_V5E, StencilJob, StencilService, compile_plan, compile_sharded,
    get_stencil, run_reference,
)
from repro.core.executor import (  # noqa: E402
    DoubleBufferedExecutor, ShardMapExecutor,
)
from repro.core.plan import D2H  # noqa: E402
from repro.kernels.dispatch import DispatchPolicy, interpret_mode  # noqa: E402

INTERIOR = 38400          # paper's out-of-core domain (Table III)
WINDOW = 256              # side of each checked output window
MAX_REL_ERR = 1e-5
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its own
    monitoring events), so a phase's compile and run times separate."""

    def __init__(self):
        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.s += duration


def check_device(count: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {d.platform!r}")
    if d.device_kind != TPU_V5E.device_kind:
        sys.exit(f"chip_smoke: device kind {d.device_kind!r} is not the "
                 f"{TPU_V5E.device_kind!r} that TPU_V5E describes")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} chips, found {len(devs)}")
    hbm = (d.memory_stats() or {}).get("bytes_limit")
    ram = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    log(f"device: platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devs)} hbm_bytes={hbm} host_ram_bytes={ram}")
    log(f"compile cache: {CACHE}")
    return devs


def domain(Y: int, X: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).random((Y, X), dtype=np.float32)


def window_errors(out, x, st, n, corners):
    """Max relative error of ``out`` against ``run_reference`` on each
    ``WINDOW``-sided window, the reference run on the window plus an
    ``n*r`` margin (clipped at the domain, whose frame is exact)."""
    m = n * st.radius
    Y, X = x.shape
    errs = {}
    for label, (y0, x0) in corners.items():
        ys, xs = max(y0 - m, 0), max(x0 - m, 0)
        ye, xe = min(y0 + WINDOW + m, Y), min(x0 + WINDOW + m, X)
        ref = np.asarray(run_reference(jnp.asarray(x[ys:ye, xs:xe]), st, n))
        ref = ref[y0 - ys:y0 - ys + WINDOW, x0 - xs:x0 - xs + WINDOW]
        got = out[y0:y0 + WINDOW, x0:x0 + WINDOW]
        errs[label] = float(np.abs(got - ref).max() / np.abs(ref).max())
    return errs


def check_errors(tag, errs):
    for label, e in errs.items():
        log(f"{tag}: window {label} max_rel_err={e!r} (bound {MAX_REL_ERR})")
    bad = {k: e for k, e in errs.items() if not e <= MAX_REL_ERR}
    if bad:
        raise AssertionError(f"{tag}: windows over {MAX_REL_ERR}: {bad}")


def phase_main_path(clock, name, n, d, s_tb, k_on, want_impl):
    """Phase A: one SO2DR plan at the paper's domain, default dispatch."""
    st = get_stencil(name)
    Y = X = INTERIOR + 2 * st.radius
    tag = f"phase A {name}"
    t0 = time.perf_counter()
    x = domain(Y, X, SEED)
    t_data = time.perf_counter() - t0
    plan = compile_plan("so2dr", st, Y, X, n, d, s_tb, k_on)
    policy = DispatchPolicy()
    ex = DoubleBufferedExecutor(policy=policy)
    c0, t0 = clock.s, time.perf_counter()
    out, _ = ex.execute(plan, x)
    wall, compile_s = time.perf_counter() - t0, clock.s - c0
    es = ex.exec_stats
    log(f"{tag}: domain {Y}x{X} f32 n={n} d={d} s_tb={s_tb} k_on={k_on} "
        f"rounds={plan.op_counts()['HostCommit']} "
        f"kernel_impl={es.kernel_impl} interpret={interpret_mode(policy)} "
        f"kernel_calls={es.kernel_calls} kernel_compiles={es.kernel_compiles} "
        f"shape_buckets={es.shape_buckets}")
    log(f"{tag}: smoke timings (one cold run, not a benchmark): "
        f"data_s={t_data!r} compile_s={compile_s!r} "
        f"run_s={wall - compile_s!r} wall_s={wall!r}")
    assert es.kernel_impl == want_impl, (es.kernel_impl, want_impl)
    assert not interpret_mode(policy)
    assert es.kernel_compiles <= es.shape_buckets, es
    # first row of chunk 1: a window straddles the chunk boundary
    b = min(op.box.lo[0] for op in plan.ops
            if isinstance(op, D2H) and op.chunk == 1)
    mid = Y // 2 - WINDOW // 2
    t0 = time.perf_counter()
    errs = window_errors(out, x, st, n, {
        "corner(0,0)": (0, 0),
        f"chunk-boundary({b - WINDOW // 2},{mid})": (b - WINDOW // 2, mid),
        f"centre({mid},{mid})": (mid, mid)})
    log(f"{tag}: reference check smoke_s={time.perf_counter() - t0!r}")
    check_errors(tag, errs)


def phase_service(clock):
    """Phase B: a service batch, flushed twice over the same shapes."""
    svc = StencilService()
    specs = [StencilJob((s, s), name, steps=16, d=4, s_tb=8, k_on=4)
             for name in ("box2d1r", "gradient2d") for s in (4096, 8192)]
    for flush in (1, 2):
        inputs = {}
        c0, t0 = clock.s, time.perf_counter()
        for i, job in enumerate(specs):
            x = domain(*job.shape, SEED + 10 * flush + i)
            inputs[svc.submit(job, x)] = (job, x)
        results = svc.flush()
        wall, compile_s = time.perf_counter() - t0, clock.s - c0
        compiles = 0
        for res in results:
            job, x = inputs[res.job_id]
            if res.status != "ok":
                raise AssertionError(f"service job {res.job_id}: {res.fault}")
            st = get_stencil(job.stencil)
            ref = np.asarray(run_reference(jnp.asarray(x), st, job.steps))
            err = float(np.abs(res.out - ref).max() / np.abs(ref).max())
            es = res.exec_stats
            compiles += es.kernel_compiles
            log(f"phase B flush {flush}: job {res.job_id} {job.stencil} "
                f"{job.shape[0]}x{job.shape[1]} kernel_impl={es.kernel_impl} "
                f"kernel_compiles={es.kernel_compiles} max_rel_err={err!r} "
                f"latency_s={res.latency_s!r} (smoke timing)")
            if not err <= MAX_REL_ERR:
                raise AssertionError(f"job {res.job_id}: error {err}")
        log(f"phase B flush {flush}: smoke timings (not a benchmark): "
            f"compile_s={compile_s!r} wall_s={wall!r} "
            f"kernel_compiles={compiles}")
        if flush == 2 and compiles:
            raise AssertionError(f"second flush compiled {compiles} kernels")


def phase_mesh(clock):
    """--four-chips: a sharded box2d1r plan on a 2x2 mesh."""
    from jax.sharding import AxisType

    st = get_stencil("box2d1r")
    Y = X = INTERIOR + 2 * st.radius
    n, k_ici = 320, 4
    tag = "four-chips box2d1r"
    x = domain(Y, X, SEED)
    plan = compile_sharded(st, Y, X, n, k_ici, (2, 2))
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    ex = ShardMapExecutor(mesh=mesh)
    c0, t0 = clock.s, time.perf_counter()
    out, _ = ex.execute(plan, x)
    wall, compile_s = time.perf_counter() - t0, clock.s - c0
    log(f"{tag}: domain {Y}x{X} f32 n={n} k_ici={k_ici} mesh=2x2 "
        f"executor={ex.exec_stats.executor}")
    log(f"{tag}: smoke timings (one cold run, not a benchmark): "
        f"compile_s={compile_s!r} run_s={wall - compile_s!r}")
    for dev in mesh.devices.flat:
        log(f"{tag}: device {dev.id} peak_bytes_in_use="
            f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
    mid = Y // 2 - WINDOW // 2
    check_errors(tag, window_errors(out, x, st, n, {
        "corner(0,0)": (0, 0),
        f"mesh-row-boundary-at-frame({mid},0)": (mid, 0),
        f"mesh-centre({mid},{mid})": (mid, mid)}))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded plan on a 2x2 mesh")
    args = ap.parse_args(argv)
    devs = check_device(4 if args.four_chips else 1)
    clock = CompileClock()
    if args.four_chips:
        phase_mesh(clock)
    else:
        phase_main_path(clock, "box2d1r", n=320, d=4, s_tb=160, k_on=4,
                        want_impl="pallas_db")
        phase_main_path(clock, "box2d4r", n=80, d=4, s_tb=40, k_on=4,
                        want_impl="mxu")
        phase_service(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
