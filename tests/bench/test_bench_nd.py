"""Domains of any rank, the box plan and a named update.

* A 3-D ``heat3d1r`` configuration on the ``box_tb`` path, added by its
  file and its ``BENCHMARK.json`` entries alone and cut to a tiny size by
  the same code as every other: sound, it is ``correct`` with nothing
  compiled in the window, and its readers read the box plan's work; its
  control fails the limit; its windows lie across every tile seam and
  every crossing of seams.
* :func:`bench.work.box_work` against a hand count and against the
  program's closed form for a box schedule's redundant updates.
* A 2-D ``gradient2d`` configuration whose reference is the update of
  ``bench/updates/gradient.py``: ``correct``, and its bf16 control fails.
"""
import dataclasses
import hashlib
import itertools
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, spec, work, workload
from conftest import ROOT, cut_to_tiny, make_tiny_root

SEED = 2**33 + 29
HEAT = "heat3d1r.ooc-1344"
GRADIENT = "gradient2d.ooc-49152"


def heat3d_config(tiles=(2, 2, 2), time_depth=16, k_on=4) -> dict:
    """A 3-D heat configuration at the size the next 3-D cell is meant
    to run: 1344^3 f32, 128 steps a solve; the system's own ``heat3d1r``
    update ``c + 0.1 * (faces - 6c)`` as a 3x3x3 tap table.  It has the
    shape of PolyBench/C's heat-3d kernel, not its coefficients (0.125
    on each axis's second difference) or sizes."""
    c = np.zeros((3, 3, 3))
    c[1, 1, 1] = 0.4
    for a in range(3):
        for side in (0, 2):
            idx = [1, 1, 1]
            idx[a] = side
            c[tuple(idx)] = 0.1
    return {
        "name": "heat3d1r-f32",
        "source": "the system's heat3d1r, shaped as PolyBench/C heat-3d",
        "stencil": "heat3d1r", "radius": 1, "points": 7,
        "flops_per_cell": 13, "dtype": "float32",
        "matmul_precision": None, "engine": "box_tb",
        "interior": [1344, 1344, 1344], "steps_per_solve": 128,
        "schedule": {"tiles": list(tiles), "time_depth": time_depth,
                     "k_on": k_on},
        "control": "bf16", "coefficients": c.tolist(),
        "kernel_pattern": "^jit_multi_step_box\\(",
        "limits": {"max_rel_err": 1e-5, "frame_cells_changed": 0}}


def gradient_config() -> dict:
    """The system's nonlinear ``gradient2d`` out of core, its reference
    the update ``bench/updates/gradient.py`` names."""
    cfg = json.loads((ROOT / "bench/configs/box2d1r-f32.json").read_text())
    for key in ("coefficients", "assumed", "reduced", "published"):
        del cfg[key]
    cfg.update(name="gradient2d-f32", stencil="gradient2d", points=5,
               flops_per_cell=19, update="gradient")
    return cfg


def _add_cell(root, cfg, cell, traffic="closed-uniform"):
    """Add ``cfg`` and a one-chip cell of it to the benchmark at
    ``root``: a new file and new entries, nothing else."""
    path = f"bench/configs/{cfg['name']}.json"
    (root / path).write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": cfg["name"], "source": cfg["source"],
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": cfg["name"],
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in ("kernel_roofline_pct", "redundant_update_pct"):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.fixture
def nd_root(tmp_path):
    """The tiny root with the 3-D heat and the gradient cells added."""
    root = make_tiny_root(tmp_path / "root")
    _add_cell(root, cut_to_tiny(heat3d_config()), HEAT)
    _add_cell(root, cut_to_tiny(gradient_config()), GRADIENT)
    return root


def _run(root, cell, control=False, seed=SEED, lines=None):
    log = lines.append if lines is not None else (lambda _: None)
    return harness.run_cell(spec.load_cell(cell, root), seed, 0.2, False,
                            time.perf_counter(), log=log, control=control)


def test_tiny_cut_keeps_the_schedule_feasible():
    cfg = cut_to_tiny(heat3d_config(tiles=(4, 4, 1), time_depth=32,
                                    k_on=32))
    assert cfg["interior"] == [32, 32, 32]
    assert cfg["steps_per_solve"] == 8
    # rounds of 4 steps, a tile of 32 // 4 = 8 cells holds a 4-cell apron
    assert cfg["schedule"] == {"tiles": [4, 4, 1], "time_depth": 4,
                               "k_on": 32}
    cfg = cut_to_tiny(heat3d_config(tiles=(16, 2, 1), time_depth=3))
    assert cfg["schedule"]["time_depth"] == 2
    assert cfg["schedule"]["tiles"] == [16, 2, 1]
    cfg = cut_to_tiny(heat3d_config(tiles=(32, 1, 1), time_depth=16))
    assert cfg["schedule"]["tiles"] == [8, 1, 1]


@pytest.mark.parametrize("cell", [HEAT, GRADIENT])
@pytest.mark.parametrize("control", [False, True])
def test_nd_and_named_update_cells_run(nd_root, cell, control):
    """Sound, the tiny cell is ``correct`` and nothing compiles in its
    window; its control (bf16, in the program's place) is not."""
    lines = []
    res = _run(nd_root, cell, control, lines=lines)
    assert list(res)[-1] == "checks" and res["attempted"] >= 1
    if control:
        assert res["correct"] is False, res["checks"]
        assert res["checks"]["max_rel_err"]["value"] > 1e-3
        return
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["frame_cells_changed"]["value"] == 0
    assert "compile events in window: 0 events, 0 s" in lines
    assert set(res["metrics"]) == {"cell_updates_per_s", "setup_s"}
    if cell == HEAT:
        assert any(l.startswith("setup: warm-up of 1 round(s) on 34x34x34 "
                                "in 8 tile(s)") for l in lines), lines
        assert any(l.startswith("setup: domain 34x34x34 ") for l in lines)


# bench/run.py in a child with its look for a chip skipped: the CPU has
# none, and the rest of the run is the chip's
RUN_PY = r"""
import runpy, sys
sys.path[:0] = ["src", "."]
from bench import harness
harness.check_device = lambda chips: None
sys.argv = ["bench/run.py"] + sys.argv[1:]
runpy.run_path("bench/run.py", run_name="__main__")
"""


@pytest.mark.parametrize("control", [0, 1])
def test_tiny_3d_cell_runs_through_run_py(tmp_path, control):
    """The tiny 3-D cell, tiles (2, 2, 2), through ``bench/run.py``:
    its last line is the result, ``checks`` last, ``correct`` sound and
    not ``correct`` with ``--control 1``."""
    root = make_tiny_root(tmp_path / "root")
    _add_cell(root, cut_to_tiny(heat3d_config()), HEAT)
    for f in (ROOT / "bench").glob("*.*"):
        shutil.copy(f, root / "bench")
    (root / "src").symlink_to(ROOT / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    r = subprocess.run(
        [sys.executable, "-c", RUN_PY, "--workload", HEAT, "--seed",
         str(SEED), "--seconds", "0.2", "--trace", "0", "--control",
         str(control)],
        cwd=root, capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.splitlines()[-1])
    assert list(res)[-1] == "checks"
    assert res["correct"] is (not control), res["checks"]
    err = r.stderr.splitlines()
    assert err[-1].startswith("check frame_cells_changed" if not control
                              else "check max_rel_err")
    if not control:
        assert "setup: warm-up of 1 round(s) on 34x34x34 in 8 tile(s)" in \
            r.stderr
        assert "compile events in window: 0 events, 0 s" in err


def test_traced_3d_run_reads_the_box_plans_work(nd_root, monkeypatch):
    """With a stand-in trace of 1 s of kernel time, the kernel roofline
    is the least time of the box calls' useful work (``box_work``) over
    it, and the plan's counts read from the box plan."""
    from bench import peaks, trace
    from repro import compile_box_plan, get_stencil

    class Summary:
        def start(self):
            pass

        def stop(self):
            pass

        def close(self):
            pass

        def reduce(self, kernel_pattern, log, collective_pattern=None):
            assert kernel_pattern == "^jit_multi_step_box\\("
            return trace.TraceSummary(
                window_s=2.0, busy_s=1.5, kernel_s=1.0, kernel_calls=8,
                chips=1, top_ops=[], idle_gaps=[], lines=[])

    row = peaks.lookup("TPU v5 lite")
    monkeypatch.setattr(harness, "_Tracer", Summary)
    monkeypatch.setattr(harness.peaks_mod, "lookup", lambda kind: row)
    monkeypatch.setattr(harness.peaks_mod, "measure_f32_flops",
                        lambda log: 4e12)
    res = harness.run_cell(spec.load_cell(HEAT, nd_root), SEED, 0.2, True,
                           time.perf_counter(), log=lambda _: None)
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    n = res["attempted"]
    plan = compile_box_plan(get_stencil("heat3d1r"), (34,) * 3, 8,
                            (2, 2, 2), 4, 4)
    p = harness.check_cell(spec.load_cell(HEAT, nd_root))
    least = sum(w.least_seconds(819e9, 4e12)
                for w in harness.BOX_PATH.kernel_work(plan, p, 13))
    assert m["kernel_roofline_pct"]["value"] == pytest.approx(
        100 * n * least, rel=1e-12)
    assert m["device_idle_pct"]["value"] == 25.0
    st = plan.stats()
    assert st.redundant_elements > 0
    assert m["redundant_update_pct"]["value"] == pytest.approx(
        100 * st.redundant_elements / (8 * 32 ** 3), rel=1e-12)
    assert m["link_bytes_per_update"]["value"] == pytest.approx(
        (st.h2d_bytes + st.d2h_bytes) / (8 * 32 ** 3), rel=1e-12)


def test_a_3d_cell_is_added_by_files_alone(tmp_path):
    """A copy of the benchmark as it stands, plus a 3-D configuration at
    its deployment size and its entries, cut to a tiny size by the
    tests' one cut: it runs and is ``correct``, and no file that was
    there is changed."""
    full = tmp_path / "full"
    (full / "bench").mkdir(parents=True)
    shutil.copy(ROOT / "BENCHMARK.json", full)
    for part in ("configs", "metrics", "traffic", "updates"):
        shutil.copytree(ROOT / "bench" / part, full / "bench" / part)

    def digests():
        return {p.relative_to(full): hashlib.sha256(p.read_bytes()).digest()
                for p in (full / "bench").rglob("*") if p.is_file()}

    before = digests()
    _add_cell(full, heat3d_config(), HEAT)
    after = digests()
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        (full / "bench/configs/heat3d1r-f32.json").relative_to(full)}

    root = make_tiny_root(tmp_path / "tiny", src=full)
    cell = spec.load_cell(HEAT, root)
    p = harness.check_cell(cell)
    assert p.shape == (34, 34, 34) and p.tiles == (2, 2, 2)
    res = _run(root, HEAT)
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("tiles", [(2, 2, 2), (3, 2, 1), (1, 1, 2)])
def test_3d_windows_cross_every_tile_seam(tiles):
    """Plans are geometry only: the windows of a box plan, cubes of
    their side, lie across each tile seam of each axis and each point
    where seams of two or three axes cross."""
    from repro import compile_box_plan, get_stencil

    shape, size = (130, 130, 130), 16
    plan = compile_box_plan(get_stencil("heat3d1r"), shape, 8, tiles, 4, 4)
    seams = harness.BOX_PATH.seams(plan)
    # near-even tiles of the 128 interior cells, the frame's 1 first
    sizes = [[128 // t + (i < 128 % t) for i in range(t)] for t in tiles]
    assert seams["seams"] == [[1 + sum(z[:k]) for k in range(1, len(z))]
                              for z in sizes]
    wins = reference.windows(shape, 1, seed=SEED, size=size, **seams)
    assert [w[0] for w in wins[:2]] == ["corner", "far-corner"]
    assert [w[0] for w in wins if w[0].startswith("random")] == [
        f"random{i}" for i in range(4)]
    for _, *origin in wins:
        assert len(origin) == 3
        assert all(0 <= o <= n - size for o, n in zip(origin, shape))

    def covered(fixed):
        return any(all(w[1 + a] < b < w[1 + a] + size
                       for a, b in fixed.items()) for w in wins)

    places = []
    for k in (1, 2, 3):
        for axes in itertools.combinations(range(3), k):
            for bs in itertools.product(*(seams["seams"][a] for a in axes)):
                places.append(dict(zip(axes, bs)))
    assert places and all(covered(f) for f in places)
    # one window a seam, one a crossing, no more
    assert len(wins) == 6 + len(places)
    assert wins != reference.windows(shape, 1, (), SEED + 1, size=size,
                                     seams=seams["seams"])


def test_window_side_by_rank():
    assert reference.window_size((49154, 49154)) == reference.WINDOW == 256
    assert reference.window_size((1346,) * 3) == reference.WINDOW_3D == 64
    assert reference.window_size((34,) * 3) == 34
    with pytest.raises(ValueError, match="rank 4"):
        reference.window_size((10,) * 4)


def test_box_work_matches_a_hand_count():
    """A 2x2x2 grid on a 10^3 framed domain, r = 1, rounds of 2 steps:
    each tile owns 4^3 cells, its box holds the frame on one side of each
    axis and a 2-cell apron on the other, 7^3 in all.  Step 1 updates
    5^3 cells, step 2 4^3; 5^3 (the owned cells and the frame) are
    written back."""
    from repro import compile_box_plan, get_stencil
    from repro.core.plan import FusedKernel

    w = work.box_work((7, 7, 7), 1, 2, (True,) * 3, (False,) * 3, 13, 4)
    assert (w.cells, w.flops, w.bytes) == (125 + 64, 189 * 13,
                                           (343 + 125) * 4)
    plan = compile_box_plan(get_stencil("heat3d1r"), (10,) * 3, 2, (2, 2, 2),
                            2, 2)
    assert [op.shape_in for op in plan.ops
            if isinstance(op, FusedKernel)] == [(7, 7, 7)] * 8
    p = workload.SolveParams(engine="box_tb", stencil="heat3d1r", radius=1,
                             shape=(10,) * 3, steps=2, tiles=(2, 2, 2),
                             time_depth=2, k_on=2)
    calls = harness.BOX_PATH.kernel_work(plan, p, 13)
    assert [(c.cells, c.bytes) for c in calls] == [(189, 468 * 4)] * 8


@pytest.mark.parametrize("shape,steps,tiles,depth,k_on", [
    ((10, 10, 10), 4, (2, 2, 2), 2, 1),
    ((34, 34, 34), 8, (2, 2, 2), 4, 4),
    ((30, 26, 22), 6, (3, 2, 1), 3, 2),
    ((66, 66), 8, (4, 2), 4, 2),
])
def test_box_work_matches_the_programs_closed_form(shape, steps, tiles,
                                                   depth, k_on):
    """Summed over a solve's calls, the useful work is the exact updates
    plus the trapezoids' redundant ones, as the program's closed form
    (``autotune.trapezoid_redundant_elements``) counts them; call by
    call it is what the planner counts for the op."""
    from repro import compile_box_plan, get_stencil
    from repro.core.autotune import trapezoid_redundant_elements

    name = "heat3d1r" if len(shape) == 3 else "box2d1r"
    st = get_stencil(name)
    plan = compile_box_plan(st, shape, steps, tiles, depth, k_on)
    p = workload.SolveParams(engine="box_tb", stencil=name, radius=1,
                             shape=shape, steps=steps, tiles=tiles,
                             time_depth=depth, k_on=k_on)
    calls = harness.BOX_PATH.kernel_work(plan, p, st.flops_per_elem)
    exact = steps * int(np.prod([n - 2 for n in shape]))
    assert exact == p.interior_updates
    assert sum(c.cells for c in calls) == exact + \
        trapezoid_redundant_elements(st, shape, steps, tiles, depth)
    ops = [op for op in plan.ops if type(op).__name__ == "FusedKernel"]
    for op, c in zip(ops, calls):
        assert (c.cells, c.flops, c.bytes) == (op.elements, op.flops,
                                               op.hbm_bytes)


def test_box_too_small_is_refused():
    with pytest.raises(ValueError, match="too small"):
        work.box_work((4, 10, 10), 1, 2, (False,) * 3, (False,) * 3, 13, 4)


def test_box_warm_up_covers_every_shape_at_the_deployment_size():
    """Plans are geometry only: one round on the domain cut to three
    tiles along each axis of more than three equal ones has every op
    shape of the 1344^3 solve."""
    from repro import get_stencil

    cell = dataclasses.replace(spec.load_cell("box2d1r.ooc-49152"),
                               config=heat3d_config((4, 4, 1), 16, 4))
    p = harness.check_cell(cell)
    st = get_stencil(p.stencil)
    plan = harness.BOX_PATH.compile(p, st)
    warm = harness.warm_up_box_plan(p, st, plan)
    assert warm.shape == (1010, 1010, 1346) and warm.d == 9
    assert warm.op_counts()["HostCommit"] == 1
    assert harness._shape_keys(plan) <= harness._shape_keys(warm)


@pytest.mark.parametrize("change,message", [
    (dict(tiles=[2, 2]), "one positive count per axis"),
    (dict(tiles=[2, 0, 1]), "one positive count per axis"),
    (dict(time_depth=48), "not whole rounds of time_depth=48"),
    (dict(drop="time_depth"), "time_depth"),
    (dict(interior=[1344, 1344], tiles=[2, 2]),
     "not of the program's rank 3"),
])
def test_mismatched_box_cell_is_refused_before_setup(change, message):
    cfg, change = heat3d_config(), dict(change)
    if "drop" in change:
        del cfg["schedule"][change["drop"]]
    else:
        cfg["interior"] = change.pop("interior", cfg["interior"])
        cfg["schedule"].update(change)
    cell = dataclasses.replace(spec.load_cell("box2d1r.ooc-49152"),
                               config=cfg)
    made = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "make_domain", lambda *a, **k: made.append(a))
        with pytest.raises((SystemExit, ValueError), match=message):
            harness.run_cell(cell, SEED, 0.1, False, time.perf_counter(),
                             log=lambda _: None)
    assert not made


@pytest.mark.parametrize("name,shape,steps", [
    ("heat3d1r", (22, 20, 18), 5), ("gradient2d", (40, 36), 6)])
def test_reference_matches_the_programs_oracle(name, shape, steps):
    """A second witness: the program's own whole-domain oracle agrees
    with the reference's tap table (3-D heat) or named update
    (gradient) on every window and on the whole domain.  The named
    update restates the program's formula, so for gradient this shows
    the reference faithful to it, not the formula right."""
    from repro import get_stencil, run_reference

    cfg = heat3d_config() if name == "heat3d1r" else gradient_config()
    update = None
    if "update" in cfg:
        update = spec._update_module(
            ROOT / "bench" / "updates" / f"{cfg['update']}.py",
            "bench_update").update
    x = workload.make_domain(shape, 3)
    oracle = np.asarray(run_reference(jnp.asarray(x), get_stencil(name),
                                      steps))
    size = 8
    seams = [[n // 2] for n in shape]
    wins = reference.windows(shape, 1, (), seed=5, size=size, seams=seams)
    ref = reference.reference_windows(x, cfg, steps, wins, size=size,
                                      update=update)
    got = np.stack([oracle[tuple(slice(o, o + size) for o in origin)]
                    for _, *origin in wins])
    assert reference.max_rel_err(got, ref) <= 1e-6
    whole = reference.reference_windows(
        x, cfg, steps, [("all",) + (0,) * len(shape)], size=min(shape),
        update=update)[0]
    crop = tuple(slice(0, min(shape)) for _ in shape)
    assert reference.max_rel_err(oracle[crop], whole) <= 1e-6
    assert reference.frame_cells_changed(oracle, x, 1) == 0


def test_frame_cells_changed_counts_each_3d_cell_once():
    x = np.zeros((6, 7, 8), np.float32)
    out = x.copy()
    out[0, 0, 0] = 1.0       # a corner: on three faces, one cell
    out[2, 6, 3] = 1.0       # a face
    out[3, 3, 3] = 1.0       # interior: not frame
    assert reference.frame_cells_changed(out, x, 1) == 2


def test_high3_is_refused_for_a_named_update():
    update = spec._update_module(ROOT / "bench/updates/gradient.py",
                                 "bench_update").update
    x = workload.make_domain((20, 20), 1)
    with pytest.raises(ValueError, match="named update"):
        reference.reference_windows(x, gradient_config(), 2,
                                    [("all", 0, 0)], "high3", size=20,
                                    update=update)


def test_tap_table_of_another_rank_is_refused():
    x = workload.make_domain((12, 12, 12), 1)
    cfg = json.loads((ROOT / "bench/configs/box2d1r-f32.json").read_text())
    with pytest.raises(ValueError, match="rank 2 for a domain of rank 3"):
        reference.reference_windows(x, cfg, 2, [("all", 0, 0, 0)], size=12)
    with pytest.raises(ValueError, match="do not match radius"):
        reference.coefficients(dict(cfg, coefficients=[[1.0] * 3] * 2))
