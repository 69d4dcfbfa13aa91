"""Sharded plans: per-device op streams with halo-exchange ops,
differentially tested against the shard_map oracle.

Three layers of evidence that one plan really drives the multi-chip
engine:

* differential execution — the lowered single-device simulator
  (``ShardedSimExecutor``, stage programs from ``lower_sharded``), the
  real ``shard_map``/``ppermute`` backend (``ShardMapExecutor``), the
  plan-free ``run_distributed`` oracle, and ``run_reference`` all agree
  to 1e-5 (multi-device cases in an 8-fake-device subprocess via
  ``tests/_subproc.py``);
* accounting — dry-run stats equal executed stats field-for-field for
  every sharded plan (mirroring ``tests/test_compress.py``);
* plan invariants (property tests on the hypothesis stub) — per-rank
  ICI bytes read off the HaloSend ops match the neighbour-count formula
  (and the legacy analytic ``collective_bytes_per_round`` for interior
  ranks), halo sends/recvs pair 1:1, and redundant ``elements_computed``
  follows the k_ici ghost-wedge formula.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as hs

from _subproc import run_fake_device_subprocess
from repro.core.analytic import RTX3080_PAPER, TPU_V5E
from repro.core.autotune import autotune_sharded
from repro.core.distributed import collective_bytes_per_round
from repro.core.executor import (
    DryRunExecutor, ShardMapExecutor, ShardedSimExecutor, get_executor,
)
from repro.core.lower import lower_sharded
from repro.core.plan import HaloRecv, HaloSend
from repro.core.reference import run_reference
from repro.core.shard import compile_sharded, ghost_wedge_elements
from repro.core.stencil import get_stencil

RNG = np.random.default_rng(31)

MESHES = [(1, 1), (2, 2), (3, 3), (4, 2), (1, 4)]
STENCILS = ["box2d1r", "box2d2r", "gradient2d"]


def _domain(Y=48, X=48, seed=None):
    rng = RNG if seed is None else np.random.default_rng(seed)
    return rng.standard_normal((Y, X)).astype(np.float32)


# ------------------------------------------------- differential execution


_SUBPROC = r"""
import numpy as np, jax, jax.numpy as jnp
from jax import make_mesh
from jax.sharding import AxisType
from repro.core.distributed import run_distributed
from repro.core.executor import ShardMapExecutor, ShardedSimExecutor
from repro.core.reference import run_reference
from repro.core.shard import compile_sharded
from repro.core.stencil import get_stencil

mesh = make_mesh((4, 2), ("data", "model"),
                 axis_types=(AxisType.Auto,) * 2)
rng = np.random.default_rng(7)
for name in ("box2d1r", "gradient2d", "box2d2r"):
    st = get_stencil(name)
    x = rng.standard_normal((64, 128)).astype(np.float32)
    for n, k in [(6, 1), (6, 3), (8, 4)]:
        plan = compile_sharded(name, 64, 128, n, k, (4, 2))
        ref = np.asarray(run_reference(jnp.asarray(x), st, n))
        dist = np.asarray(run_distributed(jnp.asarray(x), name, n, k, mesh))
        got_sm, s_sm = ShardMapExecutor(mesh=mesh).execute(plan, x)
        got_sim, s_sim = ShardedSimExecutor().execute(plan, x)
        assert np.abs(dist - ref).max() < 1e-5, ("oracle", name, n, k)
        assert np.abs(got_sm - dist).max() < 1e-5, ("shard_map", name, n, k)
        assert np.abs(got_sim - dist).max() < 1e-5, ("sim", name, n, k)
        assert np.abs(got_sim - ref).max() < 1e-5, ("sim/ref", name, n, k)
        assert s_sm == s_sim, (name, n, k)
print("SHARD_PLAN_OK")
"""


def test_sharded_plan_matches_shard_map_oracle_subprocess():
    """d=8 mesh=(4,2): sharded-plan execution through lower.py stage
    programs == shard_map backend == run_distributed == run_reference."""
    run_fake_device_subprocess(_SUBPROC, "SHARD_PLAN_OK")


@pytest.mark.parametrize("name", STENCILS)
@pytest.mark.parametrize("mesh", MESHES)
def test_sim_executor_matches_reference(name, mesh):
    """The lockstep simulator needs no real devices: every mesh shape
    runs in-process against the single-device oracle."""
    st = get_stencil(name)
    x = _domain()
    n, k = 6, 3
    plan = compile_sharded(name, 48, 48, n, k, mesh)
    ref = np.asarray(run_reference(jnp.asarray(x), st, n))
    out, _ = ShardedSimExecutor().execute(plan, x)
    assert np.abs(out - ref).max() < 1e-5, (name, mesh)


@settings(max_examples=15, deadline=None)
@given(
    name=hs.sampled_from(STENCILS),
    mesh=hs.sampled_from(MESHES),
    k_ici=hs.sampled_from([1, 2, 3]),
    seed=hs.integers(0, 2**16),
)
def test_dry_run_stats_equal_executed_stats(name, mesh, k_ici, seed):
    """Accounting is a property of the plan (mirrors test_compress):
    the zero-device dry run and the executing simulator report the same
    TransferStats field for field — including the new ICI fields."""
    x = _domain(seed=seed)
    plan = compile_sharded(name, 48, 48, 6, k_ici, mesh)
    _, dry = DryRunExecutor().execute(plan)
    _, run = ShardedSimExecutor().execute(plan, x)
    for f in dataclasses.fields(run):
        assert getattr(dry, f.name) == getattr(run, f.name), f.name
    if mesh != (1, 1):
        assert dry.ici_bytes > 0 and dry.halo_ops > 0


# ------------------------------------------------------- plan invariants


@settings(max_examples=20, deadline=None)
@given(
    name=hs.sampled_from(STENCILS),
    mesh=hs.sampled_from(MESHES),
    k_ici=hs.sampled_from([1, 2, 3]),
)
def test_per_rank_ici_bytes_match_collective_formula(name, mesh, k_ici):
    """Per-rank ICI bytes derived from the HaloSend ops equal the
    neighbour-count byte formula, and for interior ranks exactly the
    legacy analytic collective_bytes_per_round."""
    st = get_stencil(name)
    Y = X = 48
    plan = compile_sharded(name, Y, X, 6, k_ici, mesh)
    n_row, n_col = mesh
    ly, lx = Y // n_row, X // n_col
    hk = k_ici * st.radius
    full = collective_bytes_per_round((ly, lx), st.radius, k_ici, 4)
    total = 0
    for sh in plan.shards:
        nb_row = (sh.row > 0) + (sh.row + 1 < n_row)
        nb_col = (sh.col > 0) + (sh.col + 1 < n_col)
        expect = (nb_row * hk * lx + nb_col * hk * (ly + 2 * hk)) * 4
        got = plan.ici_bytes_per_round(sh.rank)
        assert got == expect, sh
        if nb_row == nb_col == 2:   # fully interior rank
            assert got == full, sh
        total += expect * plan.rounds
    s = plan.stats()
    assert s.ici_bytes == total
    assert plan.collective_bytes_per_round == max(
        plan.ici_bytes_per_round(r) for r in range(plan.n_ranks))
    assert sum(plan.per_rank_stats(r).ici_bytes
               for r in range(plan.n_ranks)) == total


@settings(max_examples=20, deadline=None)
@given(
    name=hs.sampled_from(STENCILS),
    mesh=hs.sampled_from(MESHES),
    k_ici=hs.sampled_from([1, 2, 3]),
)
def test_halo_sends_and_recvs_pair_exactly(name, mesh, k_ici):
    """Every HaloSend has exactly one matching HaloRecv in the
    destination rank's stream (same axis/depth/bytes/round), and the
    only unmatched recvs are the zero-fill mesh-edge pads."""
    plan = compile_sharded(name, 48, 48, 6, k_ici, mesh)
    sends, recvs, pads = [], [], 0
    for stream in plan.streams:
        for op in stream:
            if isinstance(op, HaloSend):
                assert op.nbytes > 0
                sends.append((op.rank, op.dst, op.axis, op.depth,
                              op.nbytes, op.round))
            elif isinstance(op, HaloRecv):
                if op.src < 0:
                    assert op.nbytes == 0
                    pads += 1
                else:
                    recvs.append((op.src, op.rank, op.axis, op.depth,
                                  op.nbytes, op.round))
    assert sorted(sends) == sorted(recvs)
    # 4 recv slots per rank per round; pads fill the missing neighbours
    assert pads + len(recvs) == 4 * plan.n_ranks * plan.rounds
    assert plan.stats().halo_ops == len(sends) + len(recvs)


@settings(max_examples=15, deadline=None)
@given(
    name=hs.sampled_from(STENCILS),
    mesh=hs.sampled_from(MESHES),
)
def test_ghost_wedge_redundancy_grows_with_k_ici(name, mesh):
    """Redundant elements_computed follows the ghost-wedge formula:
    each rank updates the interior part of its (l + 2*k*r - 2r) wide
    extended centre every step, so redundancy grows with the halo depth
    while exchanges shrink as 1/k."""
    st = get_stencil(name)
    Y = X = 48
    r = st.radius
    n = 6
    redundant = []
    for k_ici in (1, 2, 3):
        plan = compile_sharded(name, Y, X, n, k_ici, mesh)
        s = plan.stats()
        # independent re-derivation of the per-rank wedge clip: each
        # step updates the extended band's centre (inset r each side)
        # intersected with the global interior
        hk = k_ici * r
        expect = 0
        for sh in plan.shards:
            rows = min(sh.y1 + hk - r, Y - r) - max(sh.y0 - hk + r, r)
            cols = min(sh.x1 + hk - r, X - r) - max(sh.x0 - hk + r, r)
            expect += n * max(0, rows) * max(0, cols)
        assert s.elements_computed == expect, (name, mesh, k_ici)
        assert s.elements_computed == ghost_wedge_elements(
            Y, X, r, k_ici, n, mesh)
        assert s.exact_elements == n * (Y - 2 * r) * (X - 2 * r)
        redundant.append(s.redundant_elements)
    if mesh == (1, 1):
        assert redundant == [0, 0, 0]   # no wedges without neighbours
    else:
        assert redundant[0] < redundant[1] < redundant[2]


def test_breakdown_and_per_rank_stats():
    plan = compile_sharded("box2d2r", 48, 48, 6, 2, (2, 2))
    s = plan.stats()
    b = plan.breakdown()
    assert b["ici"] == s.ici_bytes > 0
    assert b["h2d"] == s.h2d_bytes == 48 * 48 * 4   # whole domain loaded once
    assert b["d2h"] == s.d2h_bytes == 48 * 48 * 4
    agg = [plan.per_rank_stats(r) for r in range(plan.n_ranks)]
    for field in ("h2d_bytes", "d2h_bytes", "ici_bytes", "halo_ops",
                  "kernel_calls", "flops", "elements_computed",
                  "exact_elements"):
        assert sum(getattr(p, field) for p in agg) == getattr(s, field), field
    counts = plan.op_counts()
    assert counts["ShardLoad"] == counts["ShardStore"] == plan.n_ranks
    assert counts["ShardKernel"] == plan.n_ranks * plan.rounds


def test_planner_rejects_infeasible_geometry():
    with pytest.raises(ValueError, match="divide evenly"):
        compile_sharded("box2d1r", 50, 48, 6, 1, (4, 2))
    with pytest.raises(ValueError, match="multiple of k_ici"):
        compile_sharded("box2d1r", 48, 48, 7, 2, (2, 2))
    with pytest.raises(ValueError, match="halo depth"):
        compile_sharded("box2d2r", 48, 48, 12, 6, (4, 1))  # hk=12 >= ly=12
    with pytest.raises(KeyError):
        compile_sharded("nope2d", 48, 48, 6, 1, (2, 2))


# ------------------------------------------------- lowering + registry


def test_lowered_streams_share_one_kernel_signature():
    """Uniform shards -> one compiled shard-kernel signature for every
    rank x round: the global origin is traced, not static."""
    plan = compile_sharded("box2d1r", 48, 48, 8, 2, (2, 2))
    ex = ShardedSimExecutor()
    out, _ = ex.execute(plan, _domain())
    es = ex.exec_stats
    assert es.executor == "sharded_sim"
    assert es.shape_buckets == 1
    assert es.kernel_compiles == 1
    n_kernels = plan.n_ranks * plan.rounds
    assert es.kernel_calls == n_kernels
    assert es.kernel_cache_hits == n_kernels - 1
    assert es.stage_count == len(plan.barriers)
    # re-running the same plan through the same executor is all hits
    out2, _ = ex.execute(plan, _domain(seed=1))
    assert ex.exec_stats.kernel_compiles == 0
    assert ex.exec_stats.kernel_cache_hits == n_kernels
    compiled = lower_sharded(plan)
    assert compiled.describe()["shape_buckets"] == 1
    assert compiled.n_slots == plan.n_ranks


def test_barrier_structure_orders_sends_before_recvs():
    """The global barrier structure is what makes lockstep execution
    deadlock-free: sends and recvs of one exchange never share a phase,
    and every phase's ops agree with its label."""
    plan = compile_sharded("box2d1r", 48, 48, 4, 2, (2, 2))
    phases = plan.phases()
    assert [label for label, _ in phases] == list(plan.barriers)
    for label, ops in phases:
        kinds = {type(op).__name__ for op in ops}
        if label.endswith("send"):
            assert kinds <= {"HaloSend"}
        elif label.endswith("recv"):
            assert kinds <= {"HaloRecv"}
        elif label.endswith("compute"):
            assert kinds == {"ShardKernel"}
        elif label == "load":
            assert kinds == {"ShardLoad"}
        elif label == "store":
            assert kinds == {"ShardStore"}
    for stream in plan.streams:
        assert [op.phase for op in stream] == sorted(op.phase for op in stream)


def test_executor_registry_has_sharded_executors():
    assert type(get_executor("sharded_sim")) is ShardedSimExecutor
    assert type(get_executor("shard_map")) is ShardMapExecutor
    # configuration these executors would silently drop is rejected
    for name in ("sharded_sim", "shard_map", "dry_run"):
        with pytest.raises(ValueError, match="fused_step/policy"):
            get_executor(name, fused_step=lambda *a: None)


def test_both_backends_reject_mismatched_dtype():
    """shard_map and the simulator must reject identically: a float64
    domain against an itemsize-4 plan is a byte-accounting lie, not a
    run (a (1,1) mesh keeps the shard_map path single-device)."""
    from repro.core.distributed import execute_sharded_plan

    plan = compile_sharded("box2d1r", 48, 48, 2, 1, (1, 1))
    x64 = _domain().astype(np.float64)
    with pytest.raises(ValueError, match="itemsize"):
        ShardedSimExecutor().execute(plan, x64)
    with pytest.raises(ValueError, match="itemsize"):
        execute_sharded_plan(plan, x64)


# ------------------------------------------------------------ autotune


def test_autotune_sharded_ranks_the_k_ici_trade():
    """With the latency term modeled, deeper k_ici buys fewer collective
    phases: the winner must beat the k=1 per-step-exchange baseline."""
    st = get_stencil("box2d2r")
    ranked = autotune_sharded(st, 512, 64, TPU_V5E, n_devices=8)
    assert ranked == sorted(ranked, key=lambda c: c.time_s)
    assert ranked[0].k_ici > 1
    assert {c.mesh for c in ranked} == {(1, 8), (2, 4), (4, 2), (8, 1)}
    best = ranked[0]
    base = min(c.time_s for c in ranked if c.k_ici == 1)
    assert best.time_s < base
    assert best.bottleneck in ("ici", "kernel")
    assert best.ici_bytes > 0 and best.redundancy > 0


def test_autotune_sharded_rejects_ici_less_hardware():
    with pytest.raises(ValueError, match="ICI"):
        autotune_sharded(get_stencil("box2d1r"), 64, 8, RTX3080_PAPER)


def test_autotune_sharded_skips_infeasible_candidates():
    """k_ici deeper than a shard must be skipped, not crash."""
    st = get_stencil("box2d4r")   # r=4: k=8 -> hk=32 >= ly=16 on (8,1)
    ranked = autotune_sharded(st, 128, 64, TPU_V5E, n_devices=8,
                              k_ici_grid=(1, 2, 4, 8))
    assert ranked
    assert all((c.mesh[0] == 1 or c.k_ici * 4 < 128 // c.mesh[0]) and
               (c.mesh[1] == 1 or c.k_ici * 4 < 128 // c.mesh[1])
               for c in ranked)


# ------------------------------------------------- golden-fixture pin


def test_sharded_plans_bit_identical_to_golden_fixture():
    """Every stencil x geometry x halo codec of the pre-hierarchy fixture
    must recompile to the exact same sharded schedule — shards, per-rank
    op streams, barriers, stats, breakdown, op counts, collective rates —
    and infeasible configs must fail with the exact same message.
    ``compile_hierarchical`` with generous capacity must return that very
    flat plan (expansion is a strict no-op when no shard needs it)."""
    import json
    import os
    import re

    from repro.core.compress import compress_plan
    from repro.core.hierarchy import compile_hierarchical

    def op_rec(op):
        t = type(op).__name__
        d = {"type": t}
        if t in ("ShardLoad", "ShardStore"):
            d.update(rank=op.rank, lo=list(op.box.lo), hi=list(op.box.hi),
                     nbytes=op.nbytes, round=op.round, phase=op.phase)
        elif t == "HaloSend":
            d.update(rank=op.rank, dst=op.dst, axis=op.axis, side=op.side,
                     depth=op.depth, nbytes=op.nbytes, round=op.round,
                     phase=op.phase)
        elif t == "HaloRecv":
            d.update(rank=op.rank, src=op.src, axis=op.axis, side=op.side,
                     depth=op.depth, nbytes=op.nbytes, round=op.round,
                     phase=op.phase)
        elif t == "ShardKernel":
            d.update(rank=op.rank, stencil=op.stencil, steps=op.steps,
                     gy0=op.gy0, gx0=op.gx0, h=op.h, w=op.w,
                     hbm_bytes=op.hbm_bytes, flops=op.flops,
                     elements=op.elements, round=op.round, phase=op.phase)
        elif t in ("HaloCompress", "HaloDecompress"):
            d.update(codec=op.codec, rank=op.rank, peer=op.peer,
                     axis=op.axis, side=op.side, direction=op.direction,
                     raw_nbytes=op.raw_nbytes, wire_nbytes=op.wire_nbytes,
                     round=op.round, phase=op.phase)
        return d

    path = os.path.join(os.path.dirname(__file__), "data",
                        "golden_sharded_plans.json")
    with open(path) as f:
        golden = json.load(f)
    assert golden, "golden fixture is empty"
    checked = errors = 0
    for key, rec in golden.items():
        stname, geom, meshs, codec = key.split("/")
        g = re.match(r"Y(\d+)X(\d+)n(\d+)k(\d+)", geom)
        Y, X, n, k = map(int, g.groups())
        mesh = tuple(map(int, re.match(r"mesh(\d+)x(\d+)", meshs).groups()))
        if "error" in rec:
            with pytest.raises(ValueError) as exc:
                compile_sharded(stname, Y, X, n, k, mesh)
            assert str(exc.value) == rec["error"], key
            errors += 1
            continue
        plan = compile_sharded(stname, Y, X, n, k, mesh)
        if codec != "identity":
            plan = compress_plan(plan, codec)
        m = rec["plan"]
        assert plan.codec == m["codec"], key
        assert plan.exact_elements == m["exact_elements"], key
        assert [dataclasses.asdict(s) for s in plan.shards] \
            == rec["shards"], key
        assert [[op_rec(op) for op in s] for s in plan.streams] \
            == rec["streams"], key
        assert [list(b) for b in plan.barriers] == rec["barriers"], key
        assert dataclasses.asdict(plan.stats()) == rec["stats"], key
        assert plan.breakdown() == rec["breakdown"], key
        assert plan.op_counts() == rec["op_counts"], key
        assert plan.collective_bytes_per_round \
            == rec["collective_bytes_per_round"], key
        assert plan.collective_wire_bytes_per_round \
            == rec["collective_wire_bytes_per_round"], key
        # the hierarchical compiler's flat path is a strict no-op
        hier = compile_hierarchical(
            stname, Y, X, n, k, mesh, c_dev=1 << 40,
            codec=None if codec == "identity" else codec)
        assert hier == plan, key
        checked += 1
    assert checked + errors == len(golden) and checked >= 36, \
        (checked, errors)
