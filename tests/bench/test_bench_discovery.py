"""A configuration, a traffic mix and a per-layer metric, each added as a
new file with its entry in ``BENCHMARK.json``, are found by name and
run, and no file that was there before is edited."""
import hashlib
import json
import time

from bench import harness, reference, spec

NEW_METRIC = '''"""Whole solves in the window (a test reader)."""


def read(ctx):
    return ctx.solves
'''


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_files_are_found_by_name(tiny_root):
    from repro import get_stencil

    before = _digests(tiny_root)
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())

    cfg = json.loads((tiny_root / "bench/configs/box2d1r-f32.json").read_text())
    st = get_stencil("box2d2r")
    cfg.update(name="box2d2r-f32", stencil="box2d2r", radius=2, points=25,
               flops_per_cell=49, interior=96,
               coefficients=[[float(v) for v in row] for row in st.coeffs])
    (tiny_root / "bench/configs/box2d2r-f32.json").write_text(json.dumps(cfg))
    (tiny_root / "bench/traffic/closed-signed.json").write_text(json.dumps(
        {"low": -1.0, "high": 1.0, "why": "signed field"}))
    (tiny_root / "bench/metrics/solves_in_window.py").write_text(NEW_METRIC)

    bench["configs"].append({"name": "box2d2r-f32", "source": "test",
                             "file": "bench/configs/box2d2r-f32.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "box2d2r.signed", "config":
                               "box2d2r-f32", "traffic": "closed-signed",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "solves_in_window", "unit": "solves",
                               "better": "higher", "source": "host_clock",
                               "layer": "executor",
                               "moves": "cell_updates_per_s",
                               "workloads": ["box2d2r.signed"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _digests(tiny_root)
    assert {k: v for k, v in after.items() if k in before} == before

    cell = spec.load_cell("box2d2r.signed", tiny_root)
    assert cell.config["stencil"] == "box2d2r"
    assert cell.traffic["low"] == -1.0
    assert "solves_in_window" in [m.name for m in cell.per_layer]
    # the new metric is listed for the new cell only
    old = spec.load_cell("box2d1r.ooc-49152", tiny_root)
    assert "solves_in_window" not in [m.name for m in old.per_layer]

    res = harness.run_cell(cell, 2**32 + 3, 0.1, False, time.perf_counter(),
                           log=lambda _: None)
    assert res["correct"] is True, res["checks"]

    ctx = harness.Context(cell=cell, params=None, plan=None, solves=3,
                          exec_wall_s=0.0, op_wall_s={}, trace=None,
                          peaks=None, f32_flops_per_s=None, log=print)
    assert cell.reader("solves_in_window")(ctx) == 3
    # readers with nothing to read return nothing, and are left out
    for name in ("kernel_roofline_pct", "device_idle_pct",
                 "barrier_wait_pct", "h2d_issue_pct"):
        assert cell.reader(name)(ctx) is None
    assert reference.coefficients(cell.config).shape == (5, 5)
