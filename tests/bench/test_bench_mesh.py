"""The sharded path of the harness: a configuration with the engine
``sharded`` runs ``compile_sharded`` on a mesh of the cell's chips.

* The three one-chip cells keep the windows their check compared before
  the mesh path came (pinned for one seed), and the mesh cell's windows
  lie on both seams of its 2x2 mesh and where they cross.
* A cell whose mesh is not its chip count, whose steps are not whole
  rounds of ``k_ici``, or whose engine no path runs, is refused before
  set-up.
* A whole run of the tiny mesh cell on four fake CPU devices, with the
  look for a chip skipped: sound, it is ``correct`` and its readers read
  what the cell lists; with the shard program broken underneath in each
  way it can break, ``correct`` comes out false.
"""
import dataclasses
import json
import sys
import time

import pytest

from bench import harness, reference, spec, workload
from conftest import ROOT

sys.path.insert(0, str(ROOT / "tests"))
from _subproc import run_fake_device_subprocess  # noqa: E402

MESH = "box2d1r.mesh-2x2"
SEED = 2**33 + 17

# reference.windows of each one-chip cell for SEED, as the harness drew
# them before the sharded path was added
PINNED = {
    "box2d1r.ooc-49152": [
        ("corner", 0, 0), ("far-corner", 48898, 48898),
        ("chunk-boundary@6145", 6017, 44468),
        ("chunk-boundary@12289", 12161, 18728),
        ("chunk-boundary@18433", 18305, 25218),
        ("chunk-boundary@24577", 24449, 7598),
        ("chunk-boundary@30721", 30593, 27752),
        ("chunk-boundary@36865", 36737, 41268),
        ("chunk-boundary@43009", 42881, 38859),
        ("tile-seam", 24449, 24448), ("random0", 2488, 3538),
        ("random1", 28970, 20013), ("random2", 29623, 12438),
        ("random3", 11538, 6346)],
    "box2d4r.ooc-49152": [
        ("corner", 0, 0), ("far-corner", 48904, 48904),
        ("chunk-boundary@6148", 6020, 44473),
        ("chunk-boundary@12292", 12164, 18730),
        ("chunk-boundary@18436", 18308, 25222),
        ("chunk-boundary@24580", 24452, 7599),
        ("chunk-boundary@30724", 30596, 27755),
        ("chunk-boundary@36868", 36740, 41273),
        ("chunk-boundary@43012", 42884, 38863),
        ("tile-seam", 24452, 24448), ("random0", 2489, 3539),
        ("random1", 28974, 20015), ("random2", 29627, 12439),
        ("random3", 11540, 6347)],
    "box2d1r.incore-12800": [
        ("corner", 0, 0), ("far-corner", 12546, 12546),
        ("tile-seam", 6273, 6016), ("random0", 11410, 4805),
        ("random1", 6470, 1949), ("random2", 7120, 10588),
        ("random3", 9970, 638)],
}

# the mesh cell's windows for SEED, as the harness drew them before
# domains of any rank came
MESH_WINDOWS = [
    ("corner", 0, 0), ("far-corner", 48898, 48898),
    ("tile-seam", 24449, 24448), ("random0", 44468, 18728),
    ("random1", 25218, 7598), ("random2", 27752, 41268),
    ("random3", 38859, 2488), ("row-seam@24577", 24449, 3538),
    ("col-seam@24577", 28970, 24449),
    ("seam-cross@24577,24577", 24449, 24449)]


def _windows(cell_name, seed=SEED):
    from repro import get_stencil

    cell = spec.load_cell(cell_name)
    p = harness.solve_params(cell.config)
    path = harness.solve_path(p.engine)
    plan = path.compile(p, get_stencil(p.stencil))
    return p, reference.windows((p.Y, p.X), p.radius, seed=seed,
                                size=reference.window_size((p.Y, p.X)),
                                **path.seams(plan))


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_one_chip_cells_keep_their_windows(cell):
    assert _windows(cell)[1] == PINNED[cell]


def test_mesh_windows_lie_on_both_seams_and_where_they_cross():
    p, wins = _windows(MESH)
    assert wins == MESH_WINDOWS
    size = reference.window_size((p.Y, p.X))
    seam = p.Y // 2                     # 2x2 mesh of a square domain
    assert p.mesh == (2, 2) and p.chips == 4 and seam == 24577
    # the draws the one-chip cells make come first, in the same order
    assert wins[:7] == reference.windows((p.Y, p.X), p.radius, (), SEED,
                                         size=size)
    by_label = {label: (y0, x0) for label, y0, x0 in wins[7:]}
    assert list(by_label) == [f"row-seam@{seam}", f"col-seam@{seam}",
                              f"seam-cross@{seam},{seam}"]
    y0, _ = by_label[f"row-seam@{seam}"]
    assert y0 < seam < y0 + size
    _, x0 = by_label[f"col-seam@{seam}"]
    assert x0 < seam < x0 + size
    y0, x0 = by_label[f"seam-cross@{seam},{seam}"]
    assert y0 < seam < y0 + size and x0 < seam < x0 + size
    assert wins != _windows(MESH, SEED + 1)[1]


def _with(cell, **sched):
    cfg = json.loads(json.dumps(cell.config))
    cfg["schedule"].update(sched)
    return dataclasses.replace(cell, config=cfg)


@pytest.mark.parametrize("change,message", [
    (dict(chips=1), "asks for 1 chip"),
    (dict(mesh=[4, 2]), "asks for 4 chip"),
    (dict(k_ici=3), "not whole rounds of k_ici=3"),
    (dict(mesh=[2]), "not two positive sizes"),
    (dict(engine="no_such_engine"), "unknown engine"),
    (dict(drop="k_ici"), "k_ici"),
])
def test_mismatched_mesh_cell_is_refused_before_setup(change, message):
    cell = spec.load_cell(MESH)
    if "chips" in change:
        cell = dataclasses.replace(cell, chips=change["chips"])
    elif "engine" in change:
        cell = dataclasses.replace(cell, config=dict(
            cell.config, engine=change["engine"]))
    elif "drop" in change:
        cell = _with(cell)
        del cell.config["schedule"][change["drop"]]
    else:
        cell = _with(cell, **change)
    made = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "make_domain", lambda *a, **k: made.append(a))
        with pytest.raises(SystemExit, match=message):
            harness.run_cell(cell, SEED, 0.1, False, time.perf_counter(),
                             log=lambda _: None)
    assert not made


def test_every_cell_runs_on_its_chip_count():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert harness.check_cell(cell).chips == cell.chips == w["chips"]


def test_ici_readers_read_nothing_off_a_mesh():
    """A one-chip plan sends no halo, and a trace reduced with no
    collective pattern, or with one that matched nothing, has no
    collective time to read."""
    from types import SimpleNamespace

    from repro import compile_plan, get_stencil
    from repro.core.shard import compile_sharded

    from bench import trace

    cell = spec.load_cell(MESH)
    ici_bytes = cell.reader("ici_bytes_per_update")
    exposed = cell.reader("ici_exposed_pct")
    p = workload.SolveParams(engine="so2dr", stencil="box2d1r", radius=1,
                             shape=(130, 130), steps=8, d=4, s_tb=8, k_on=4)
    plan = compile_plan("so2dr", get_stencil("box2d1r"), 130, 130, 8, 4, 8, 4)
    assert ici_bytes(SimpleNamespace(stats=plan.stats(), params=p)) is None
    sharded = compile_sharded("box2d1r", 130, 130, 8, 4, (2, 2))
    value = ici_bytes(SimpleNamespace(stats=sharded.stats(), params=p))
    assert value == sharded.stats().ici_bytes / (8 * 128 * 128) > 0

    def summary(**kw):
        return trace.TraceSummary(window_s=2.0, busy_s=1.0, kernel_s=0.5,
                                  kernel_calls=1, chips=4, top_ops=[],
                                  idle_gaps=[], lines=[], **kw)

    assert exposed(SimpleNamespace(trace=None)) is None
    assert exposed(SimpleNamespace(trace=summary())) is None
    assert exposed(SimpleNamespace(trace=summary(
        collective_s=0.0, collective_calls=0))) is None
    assert exposed(SimpleNamespace(trace=summary(
        collective_s=0.1, collective_calls=2))) == pytest.approx(5.0)


# a whole run of the tiny mesh cell in a child with four fake CPU
# devices, which checks its own result and prints MESH-OK: FAULT breaks
# the shard program underneath; TRACED stands a summary in for the
# profiler's
CHILD = r"""
import sys, time
from pathlib import Path
sys.path[:0] = [{root!r}]
import jax
import numpy as np
assert len(jax.devices()) == 4, jax.devices()
# as bench/run.py: every compile to a persistent cache
jax.config.update("jax_compilation_cache_dir", str(Path({tiny!r}) / ".cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from bench import harness, peaks, spec, trace
from repro.core import distributed


def pytest_approx(v):
    import pytest
    return pytest.approx(v, rel=1e-12)

FAULT, TRACED = {fault!r}, {traced!r}
masked, execute = distributed.masked_local_steps, distributed.execute_sharded_plan
if FAULT == "state_unchanged":
    distributed.masked_local_steps = lambda ext, *a, **k: ext
elif FAULT == "exchange_left_out":
    distributed._shift = lambda x, *a, **k: x * 0
elif FAULT == "answer_altered":
    def altered(ext, st, k, *a):
        m = k * st.radius + st.radius
        return masked(ext, st, k, *a).at[m, m].add(0.01)
    distributed.masked_local_steps = altered
elif FAULT == "half_written_back":
    def half(plan, x, **kw):
        out = np.array(execute(plan, x, **kw))
        out[x.shape[0] // 2:] = x[x.shape[0] // 2:]
        return out
    distributed.execute_sharded_plan = half


class Summary:
    patterns = None
    def start(self): pass
    def stop(self): pass
    def close(self): pass
    def reduce(self, kernel_pattern, log, collective_pattern=None):
        Summary.patterns = (kernel_pattern, collective_pattern)
        return trace.TraceSummary(
            window_s=2.0, busy_s=1.5, kernel_s=1.0, kernel_calls=1, chips=4,
            top_ops=[], idle_gaps=[], lines=[], collective_s=0.25,
            collective_calls=8)


if TRACED:
    harness._Tracer = Summary
    row = peaks.lookup("TPU v5 lite")
    peaks.lookup = lambda kind: row
    peaks.measure_f32_flops = lambda log: 4e12
lines = []
cell = spec.load_cell("box2d1r.mesh-2x2", Path({tiny!r}))
res = harness.run_cell(cell, 2**33 + 7, 0.2, TRACED, time.perf_counter(),
                       log=lines.append)
assert list(res)[-1] == "checks" and res["device"]["count"] == 4, res
if FAULT != "none":
    assert res["correct"] is False and res["failed"] == 1, res["checks"]
    print("MESH-OK")
    sys.exit(0)
assert res["correct"] is True, res["checks"]
check = next(l for l in lines if l.startswith("check: "))
for label in ("row-seam@49", "col-seam@49", "seam-cross@49,49"):
    assert label in check, check
# set-up compiles the shard program, not runs it; the window's solves,
# at least three, trace it anew and find it in the cache; nothing else
# compiles
assert any(l.startswith("setup: warm-up of the shard program (compiled, "
                        "not run), 2 round(s) of k_ici=4 on 98x98 over a "
                        "2x2 mesh") for l in lines), lines
n = res["attempted"]
assert n >= cell.config["min_window_solves"] == 3
compiled = [l for l in lines if l.startswith("  compile window "
                                             "backend_compile_duration")]
assert compiled and all("global_fn" in l and f" n={{n}} " in l
                        for l in compiled), compiled
assert f"persistent compilation cache in window: hits={{n}} misses=0" in lines
m = res["metrics"]
if not TRACED:
    assert set(m) == {{"cell_updates_per_s", "setup_s"}}, m
else:
    assert set(m) == {{x.name for x in cell.per_layer}}, m
    assert Summary.patterns == (cell.config["kernel_pattern"],
                                cell.config["collective_pattern"])
    assert m["ici_exposed_pct"]["value"] == 12.5
    assert m["device_idle_pct"]["value"] == 25.0
    from repro.core.shard import compile_sharded
    plan = compile_sharded("box2d1r", 98, 98, 8, 4, (2, 2))
    assert m["ici_bytes_per_update"]["value"] == (
        plan.stats().ici_bytes / (8 * 96 * 96)) > 0
    assert m["link_bytes_per_update"]["value"] == 2 * 98 * 98 * 4 / (8 * 96 * 96)
    st = plan.stats()
    assert m["redundant_update_pct"]["value"] == (
        100 * (st.elements_computed - 8 * 96 * 96) / (8 * 96 * 96)) > 0
    # useful work of the 4 chips' calls of the window's solves, per
    # chip, over the stand-in summary's 1 s of kernel time
    from bench import work
    from repro.core.plan import ShardKernel
    least = sum(work.shard_work(op.gy0, op.gx0, op.h, op.w, 98, 98, 1,
                                op.steps, 17, 4).least_seconds(819e9, 4e12)
                for s in plan.streams for op in s
                if isinstance(op, ShardKernel))
    assert m["kernel_roofline_pct"]["value"] == pytest_approx(
        100 * n * least / 4)
print("MESH-OK")
"""


@pytest.mark.parametrize("fault,traced", [
    ("none", False), ("none", True), ("state_unchanged", False),
    ("exchange_left_out", False), ("answer_altered", False),
    ("half_written_back", False)])
def test_tiny_mesh_cell_on_four_fake_devices(tiny_root, fault, traced):
    code = CHILD.format(root=str(ROOT), tiny=str(tiny_root), fault=fault,
                        traced=traced)
    run_fake_device_subprocess(code, "MESH-OK", n_devices=4, timeout=600)
