"""The four cells read what they read before domains of any rank and the
box path came: their solve, warm-up plan, kernel calls' useful work and
tiny copies are pinned to the numbers the harness gave then (their
windows are pinned in ``test_bench_mesh``).  Plans are geometry only: no
array is touched."""
import json

import pytest

from bench import harness, spec
from conftest import cut_to_tiny

# each cell's SolveParams (shape, steps and its engine's schedule), its
# interior updates, its warm-up plan (shape, rows, chunks, steps; none
# on the mesh) and its kernel calls' useful work (calls, cells, flops,
# bytes)
PARENT = {
    "box2d1r.ooc-49152": dict(
        params=dict(engine="so2dr", stencil="box2d1r", radius=1,
                    shape=(49154, 49154), steps=320, d=8, s_tb=160, k_on=4),
        updates=773094113280, warm=((18434, 49154), 18434, 3, 160),
        work=(640, 790600089600, 13440201523200, 1581547645440)),
    "box2d4r.ooc-49152": dict(
        params=dict(engine="so2dr", stencil="box2d4r", radius=4,
                    shape=(49160, 49160), steps=40, d=8, s_tb=40, k_on=4),
        updates=96636764160, warm=((18440, 49160), 18440, 3, 40),
        work=(80, 98783723520, 15904179486720, 197741184000)),
    "box2d1r.incore-12800": dict(
        params=dict(engine="incore", stencil="box2d1r", radius=1,
                    shape=(12802, 12802), steps=640, d=1, s_tb=160, k_on=4),
        updates=104857600000, warm=((12802, 12802), 12802, 1, 160),
        work=(160, 104857600000, 1782579200000, 209780741120)),
    "box2d1r.mesh-2x2": dict(
        params=dict(engine="sharded", stencil="box2d1r", radius=1,
                    shape=(49154, 49154), steps=640, mesh=(2, 2), k_ici=4),
        updates=1546188226560, warm=None,
        work=(640, 1546376979200, 26288408646400, 3093131494400)),
}

# each configuration cut for the tests' tiny root: interior, steps,
# schedule
TINY = {
    "box2d1r-f32": (96, 8, {"d": 8, "s_tb": 4, "k_on": 4}),
    "box2d4r-f32": (128, 8, {"d": 8, "s_tb": 4, "k_on": 4}),
    "box2d1r-f32-incore": (96, 8, {"d": 1, "s_tb": 4, "k_on": 4}),
    "box2d1r-f32-mesh2x2": (96, 8, {"mesh": [2, 2], "k_ici": 4}),
}


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_cell_reads_what_it_read_before(cell):
    from repro import get_stencil

    want = PARENT[cell]
    c = spec.load_cell(cell)
    p = harness.solve_params(c.config)
    for key, value in want["params"].items():
        assert getattr(p, key) == value, key
    assert p.tiles is None and p.time_depth is None
    assert (p.Y, p.X) == p.shape
    assert p.interior_updates == want["updates"]
    path = harness.solve_path(p.engine)
    st = get_stencil(p.stencil)
    plan = path.compile(p, st)
    if want["warm"] is not None:
        warm, rows = harness.warm_up_plan(p, st, plan)
        assert (warm.shape, rows, warm.d, warm.n) == want["warm"]
    calls = path.kernel_work(plan, p, int(c.config["flops_per_cell"]))
    assert (len(calls), sum(w.cells for w in calls),
            sum(w.flops for w in calls),
            sum(w.bytes for w in calls)) == want["work"]


@pytest.mark.parametrize("name", sorted(TINY))
def test_tiny_copy_is_cut_as_before(name):
    from conftest import ROOT

    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    tiny = cut_to_tiny(json.loads(json.dumps(cfg)))
    assert (tiny["interior"], tiny["steps_per_solve"],
            tiny["schedule"]) == TINY[name]
    # nothing else of the configuration is cut
    for key in set(cfg) - {"interior", "steps_per_solve", "schedule"}:
        assert tiny[key] == cfg[key]
