"""Hierarchical plans: every level of the nesting proven differentially.

The SO2DR recursion — out-of-core streaming nested *inside* each device
shard — must change nothing observable but the traffic pattern:

* the fake-device simulator executing a hierarchical plan is
  bit-identical to the same plan compiled flat, across inner engines
  (so2dr / resreu / box_tb) and outer halo codecs, and matches the
  ``shard_map`` backend and ``run_reference`` to 1e-5 (subprocess, 8
  fake devices) on a mesh whose shards each need >= 3 inner chunks;
* dry-run accounting equals executed accounting at both levels (ICI and
  inner H2D/D2H) field for field;
* property tests (hypothesis, stub-backed on minimal containers): inner
  per-round H2D bytes are exactly the shard subdomain plus the chunk
  aprons, and lossless halo codecs round-trip bit-exactly;
* expansion is a strict no-op when a shard fits: ``compile_hierarchical``
  with generous capacity returns the flat ``ShardedPlan`` unchanged.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as stst

from _subproc import run_fake_device_subprocess
from repro.core.compress import compress_plan, get_codec
from repro.core.executor import DryRunExecutor, ShardedSimExecutor
from repro.core.hierarchy import (
    HierarchicalPlan, INNER_ENGINES, compile_hierarchical,
)
from repro.core.plan import ShardedPlan
from repro.core.reference import run_reference
from repro.core.shard import compile_sharded, shard_working_set
from repro.core.stencil import get_stencil

RNG = np.random.default_rng(17)

# global framed 48x48 on a (2,2) mesh: ly = lx = 24; star2d1r with
# k_ici = 2 gives hk = 2 (band 28x28), box2d2r gives hk = 4 (band 32x32)
Y = X = 48
MESH = (2, 2)
N, K_ICI = 8, 2
INNER_D = 3      # every shard streams through >= 3 inner chunks


def _domain(seed=None):
    rng = RNG if seed is None else np.random.default_rng(seed)
    return rng.standard_normal((Y, X)).astype(np.float32)


def _hier(stencil="star2d1r", engine="so2dr", codec=None, **kw):
    if engine == "box_tb":
        kw.setdefault("inner_tiles", (INNER_D, 2))
    else:
        kw.setdefault("inner_d", INNER_D)
    return compile_hierarchical(stencil, Y, X, N, K_ICI, MESH,
                                inner_engine=engine, codec=codec, **kw)


# ------------------------------------------------- differential execution


@pytest.mark.parametrize("codec", [None, "zrle"])
@pytest.mark.parametrize("engine", sorted(INNER_ENGINES))
@pytest.mark.parametrize("stencil", ["star2d1r", "box2d2r"])
def test_hier_sim_bit_identical_to_flat_and_matches_reference(
        stencil, engine, codec):
    """Chunked masked execution inside each shard is a pure reordering:
    the hierarchical plan's output equals the flat sharded plan's bit
    for bit (lossless codecs included), and both match the oracle."""
    x = _domain(seed=3)
    plan = _hier(stencil, engine, codec)
    assert isinstance(plan, HierarchicalPlan)
    assert plan.inner_chunks >= 3
    flat = compile_sharded(stencil, Y, X, N, K_ICI, MESH)
    got, s_got = ShardedSimExecutor().execute(plan, x)
    want, _ = ShardedSimExecutor().execute(flat, x)
    np.testing.assert_array_equal(got, want)
    ref = np.asarray(run_reference(jnp.asarray(x), get_stencil(stencil), N))
    scale = np.abs(ref).max() + 1e-6
    assert np.abs(got - ref).max() / scale < 1e-5
    assert s_got == plan.stats()


def test_hier_lossy_codec_stays_within_its_error_bound():
    x = _domain(seed=5)
    got, _ = ShardedSimExecutor().execute(_hier(codec="bf16"), x)
    want, _ = ShardedSimExecutor().execute(_hier(), x)
    scale = np.abs(want).max() + 1e-6
    err = np.abs(got - want).max() / scale
    assert 0 < err < 64 * get_codec("bf16").max_rel_error


_SUBPROC = r"""
import numpy as np, jax.numpy as jnp
from jax import make_mesh
from jax.sharding import AxisType
from repro.core.executor import ShardMapExecutor, ShardedSimExecutor
from repro.core.hierarchy import compile_hierarchical
from repro.core.reference import run_reference
from repro.core.stencil import get_stencil

mesh = make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
x = np.random.default_rng(7).standard_normal((48, 48)).astype(np.float32)
ref = np.asarray(run_reference(jnp.asarray(x), get_stencil("star2d1r"), 8))
scale = np.abs(ref).max() + 1e-6
for engine, kw in [("so2dr", dict(inner_d=3)), ("resreu", dict(inner_d=4)),
                   ("box_tb", dict(inner_tiles=(3, 2)))]:
    for codec in (None, "zrle"):
        plan = compile_hierarchical("star2d1r", 48, 48, 8, 2, (2, 2),
                                    inner_engine=engine, codec=codec, **kw)
        assert plan.inner_chunks >= 3, (engine, plan.inner_chunks)
        got_sm, s_sm = ShardMapExecutor(mesh=mesh).execute(plan, x)
        got_sim, s_sim = ShardedSimExecutor().execute(plan, x)
        assert np.abs(got_sm - ref).max() / scale < 1e-5, (engine, codec)
        assert np.abs(got_sim - ref).max() / scale < 1e-5, (engine, codec)
        assert np.abs(got_sim - got_sm).max() / scale < 1e-5, (engine, codec)
        assert s_sm == s_sim == plan.stats(), (engine, codec)
print("HIERARCHY_OK")
"""


def test_hier_sim_matches_shard_map_subprocess():
    """Every inner engine x {identity, zrle}: simulator == shard_map
    backend == run_reference on real fake devices, stats identical."""
    run_fake_device_subprocess(_SUBPROC, "HIERARCHY_OK")


# ------------------------------------------------- two-level accounting


def test_dry_run_stats_equal_executed_stats_at_both_levels():
    x = _domain()
    plan = _hier(codec="zrle")
    _, dry = DryRunExecutor().execute(plan)
    _, executed = ShardedSimExecutor().execute(plan, x)
    assert dataclasses.asdict(dry) == dataclasses.asdict(executed)
    # outer level: ICI fields come from the outer streams alone
    outer = plan.outer.stats()
    assert dry.ici_bytes == outer.ici_bytes
    assert dry.ici_wire_bytes == outer.ici_wire_bytes
    assert dry.halo_ops == outer.halo_ops
    # inner level: H2D/D2H roll up as (per-round inner plan) x rounds
    for field in ("h2d_bytes", "d2h_bytes", "h2d_wire_bytes",
                  "d2h_wire_bytes", "buffer_bytes"):
        inner_total = sum(getattr(plan.inner_stats(r), field)
                          for r in range(plan.n_ranks)) * plan.rounds
        assert getattr(dry, field) == inner_total, field


def test_hier_elements_account_for_inner_apron_overcompute():
    """Inner chunk aprons re-run masked updates the flat plan computes
    once: exact work is unchanged, total work strictly grows."""
    plan = _hier()
    flat = compile_sharded("star2d1r", Y, X, N, K_ICI, MESH)
    assert plan.exact_elements == flat.exact_elements
    assert plan.stats().elements_computed > flat.stats().elements_computed


def test_compressed_halos_cut_wire_bytes_not_payload():
    flat = compile_sharded("star2d1r", Y, X, N, K_ICI, MESH)
    z = compress_plan(flat, "zrle")
    assert z.stats().ici_bytes == flat.stats().ici_bytes
    assert z.stats().ici_wire_bytes < z.stats().ici_bytes
    assert flat.stats().ici_wire_bytes == flat.stats().ici_bytes
    # the hierarchical wrapper routes its outer halos the same way
    h = _hier(codec="zrle")
    assert h.stats().ici_wire_bytes == z.stats().ici_wire_bytes
    assert h.stats().ici_bytes == z.stats().ici_bytes


# ------------------------------------------------- strict no-op flat path


def test_fitting_shard_compiles_bit_identical_flat_plan():
    """Expansion is a strict no-op when every shard fits ``c_dev``: the
    planner returns the flat ShardedPlan itself, equal field-for-field
    to a direct compile_sharded call."""
    plan = compile_hierarchical("star2d1r", Y, X, N, K_ICI, MESH,
                                c_dev=1 << 30)
    flat = compile_sharded("star2d1r", Y, X, N, K_ICI, MESH)
    assert isinstance(plan, ShardedPlan)
    assert not isinstance(plan, HierarchicalPlan)
    assert plan == flat
    # with a codec: the no-op path still compresses the flat plan
    z = compile_hierarchical("star2d1r", Y, X, N, K_ICI, MESH,
                             c_dev=1 << 30, codec="zrle")
    assert z == compress_plan(flat, "zrle")


def test_capacity_derives_inner_chunks_and_stays_exact():
    x = _domain(seed=9)
    flat = compile_sharded("star2d1r", Y, X, N, K_ICI, MESH)
    hk = K_ICI * get_stencil("star2d1r").radius
    ws = shard_working_set(Y // 2, X // 2, hk, 4)
    plan = compile_hierarchical("star2d1r", Y, X, N, K_ICI, MESH,
                                c_dev=ws // 2)
    assert isinstance(plan, HierarchicalPlan)
    assert plan.inner_chunks >= 2 and plan.c_dev == ws // 2
    got, _ = ShardedSimExecutor().execute(plan, x)
    want, _ = ShardedSimExecutor().execute(flat, x)
    np.testing.assert_array_equal(got, want)


def test_trailing_hierarchical_plans_are_dry_run_only():
    plan = compile_hierarchical("star2d1r", Y, X, N, K_ICI, MESH,
                                inner_d=INNER_D, trailing=(64,))
    assert plan.stats().h2d_bytes > 0      # accounting still works
    with pytest.raises(ValueError, match="dry-run-only"):
        ShardedSimExecutor().execute(plan, _domain())


# ------------------------------------------------- property tests


@settings(max_examples=20, deadline=None)
@given(d=stst.integers(min_value=1, max_value=8),
       engine=stst.sampled_from(("so2dr", "resreu")))
def test_inner_h2d_bytes_sum_to_subdomain_plus_aprons(d, engine):
    """Per round, one shard's inner H2D traffic is exactly its band:
    resreu re-loads the 2*hk apron rows at every chunk seam
    ((ly + 2*hk*d) rows), so2dr loads every band row exactly once
    (fresh rows + the carry buffer replaces the re-load)."""
    plan = compile_hierarchical("star2d1r", Y, X, N, K_ICI, MESH,
                                inner_engine=engine, inner_d=d)
    hk = K_ICI * get_stencil("star2d1r").radius
    h = Y // MESH[0] + 2 * hk
    w = X // MESH[1] + 2 * hk
    itemsize = plan.itemsize
    for rank in range(plan.n_ranks):
        s = plan.inner_stats(rank)
        if engine == "resreu":
            assert s.h2d_bytes == (Y // MESH[0] + 2 * hk * d) * w * itemsize
        else:
            assert s.h2d_bytes == h * w * itemsize
        # owned region comes back exactly once per round, apron-free
        assert s.d2h_bytes == (Y // MESH[0]) * (X // MESH[1]) * itemsize


@settings(max_examples=20, deadline=None)
@given(codec=stst.sampled_from(("identity", "zrle")),
       rows=stst.integers(min_value=1, max_value=6),
       cols=stst.integers(min_value=3, max_value=40),
       seed=stst.integers(min_value=0, max_value=2**31))
def test_lossless_halo_codecs_round_trip_bit_exact(codec, rows, cols, seed):
    """A full halo-exchange round trip (encode -> wire -> decode) must
    reproduce every fp32 bit pattern, specials included."""
    c = get_codec(codec)
    rng = np.random.default_rng(seed)
    band = rng.standard_normal((rows, cols)).astype(np.float32)
    band[0, 0] = -0.0
    if rows * cols > 2:
        band.flat[1], band.flat[2] = np.inf, np.nan
    out = c.decode(c.encode(band), band.shape, band.dtype)
    assert np.array_equal(band.view(np.uint32), out.view(np.uint32))


def test_lossless_codec_bit_exact_through_executed_exchange():
    """End to end, not just per-band: a zrle-compressed hierarchical run
    equals the uncompressed run bit for bit."""
    x = _domain(seed=23)
    got, _ = ShardedSimExecutor().execute(_hier(codec="zrle"), x)
    want, _ = ShardedSimExecutor().execute(_hier(), x)
    assert np.array_equal(np.asarray(got).view(np.uint32),
                          np.asarray(want).view(np.uint32))


# ------------------------------------------------- validation surface


def test_unknown_inner_engine_and_bad_knobs_are_rejected():
    with pytest.raises(ValueError, match="inner engine"):
        compile_hierarchical("star2d1r", Y, X, N, K_ICI, MESH,
                             inner_engine="naive_tb", inner_d=2)
    with pytest.raises(ValueError, match="inner_tiles"):
        compile_hierarchical("star2d1r", Y, X, N, K_ICI, MESH,
                             inner_engine="so2dr", inner_tiles=(2, 2))
    with pytest.raises(ValueError):
        compile_hierarchical("star2d1r", Y, X, N, K_ICI, MESH,
                             inner_engine="so2dr", inner_d=10**6)
