"""The segmented round-major layout: each run of rounds at its own width
and K.

HBMC's colors can differ widely in width (the heat-step benchmark's four
colors hold 7,991, about 7,900, 434 and 99 lanes a round), and rounds of
one width can differ in K (the Poisson benchmark's first color gathers
one forward entry a row and three backward, its second three and one).
The sweep packs consecutive rounds into segments, each at its own lane
width and its own K per half (``sell.segment_bounds``), so padding lanes
and padding slots are neither gathered by the sweep nor carried by the
SpMV and vector work.  Pinned here:

  1. segment widths and K are the live maxima of their rounds, and the
     cuts fall where width or K parts;
  2. the segmented apply (single and batched) is the sequential
     substitution, and one segment (rounds of one width and one K per
     half, or ``max_segments=1``) is bitwise the single-width packing;
  3. the flat state round-trips through embed/extract, and the plan and
     its reports count lane occupancy over the layout's slots;
  4. the mesh plan stays bitwise the single-device plan with the same
     lane multiple, one tiled all-gather per step of every segment.
"""
import importlib.util
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.analysis import check_fused_tables
from repro.core import (build_plan, fuse_round_major, ic0, pack_factor,
                        round_major_layout, segment_bounds)
from repro.core.sell import MAX_SEGMENTS
from repro.core.matrices import laplace_2d
from repro.core.plan import _order_system
from repro.core.sell import stack_sweeps
from repro.core.trisolve import (build_round_major_preconditioner_from_rounds,
                                 sequential_backward, sequential_forward)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS, W = 8, 4
# the 20 x 20 grid's plan: its colors' rounds hold 21 to 20, 7 to 6 and 3
# lanes, and K changes inside each color
SEGMENTS_20 = ((6, 21), (3, 20), (3, 20), (3, 20), (1, 20), (1, 7), (4, 7),
               (2, 6), (1, 6), (7, 3), (1, 3))


def _load(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _system(nx, ny, method="hbmc"):
    sysd = _order_system(sp.csr_matrix(laplace_2d(nx, ny)), None, method,
                         BS, W)
    return sysd, ic0(sysd.a_bar)


def _fused(sysd, l_bar, **kw):
    fwd, bwd = pack_factor(l_bar, sysd.fwd_rounds, sysd.bwd_rounds,
                           sysd.drop)
    return fwd, bwd, fuse_round_major(fwd, bwd, **kw)


# ---------------------------------------------------------------------------
# 1. Where the cuts fall.
# ---------------------------------------------------------------------------

def test_unequal_colors_get_their_own_segments():
    """On a 20 x 20 grid the colors' rounds hold 21 to 20, 7 to 6 and 3
    lanes: the cuts fall at both color boundaries and where K parts inside
    a color, each segment at its rounds' own widest lane count and K."""
    sysd, l_bar = _system(20, 20)
    fwd, bwd, fused = _fused(sysd, l_bar)
    lay = fused.layout
    assert lay.segments == SEGMENTS_20
    kf, kb = np.maximum(fwd.live_k, 1), np.maximum(bwd.live_k[::-1], 1)
    key = np.stack([fwd.live, kf, kb], axis=1)
    start, starts = 0, []
    for f, b, (n, r) in zip(fused.fwd, fused.bwd, lay.segments):
        rounds = slice(start, start + n)
        if start:                               # width or K parts here
            assert (key[start] != key[start - 1]).any()
        assert r == fwd.live[rounds].max()
        assert f.cols.shape == (n, kf[rounds].max(), r)     # lanes minor
        assert b.cols.shape == (n, kb[rounds].max(), r)
        starts.append(start)
        start += n
    assert start == lay.n_steps == fwd.rows.shape[0]
    assert {2 * BS, 3 * BS} <= set(starts)      # the color boundaries
    assert [f.cols.shape[1] for f in fused.fwd] == [1, 2, 4, 3, 4, 2, 4, 3,
                                                    4, 3, 4]
    assert [b.cols.shape[1] for b in fused.bwd] == [4, 4, 2, 3, 2, 3, 2, 2,
                                                    1, 1, 1]


def _pattern_tables(a):
    """Forward and backward StepTables of a benchmark pattern (HBMC block
    32, w 8); the factor's values do not move the counts."""
    sysd = _order_system(sp.csr_matrix(a), None, "hbmc", 32, 8)
    return pack_factor(sp.tril(sysd.a_bar, format="csr"), sysd.fwd_rounds,
                       sysd.bwd_rounds, sysd.drop)


def _gathers(fused):
    """(slots, live slots) an apply gathers: missing positions read m."""
    halves = fused.fwd + fused.bwd
    return (sum(h.cols.size for h in halves),
            sum(int(np.count_nonzero(h.cols != fused.layout.m))
                for h in halves))


def test_heat_step_pattern_counts():
    """The heat2d benchmark's pattern (725 x 725, HBMC block 32, w 8): six
    segments, cut at the colors and where K parts inside the first and
    last, and 3,153,110 gathered values an apply where one width gathered
    8,182,784.  Rounds 32 and 33 (7,909 and 7,908 lanes) ride with round
    31 at 7,991."""
    fwd, bwd = _pattern_tables(laplace_2d(725, 725))
    fused = fuse_round_major(fwd, bwd)
    lay = fused.layout
    assert lay.segments == ((31, 7991), (3, 7991), (30, 7908), (32, 434),
                            (31, 99), (1, 99))
    assert [(f.cols.shape[1], b.cols.shape[1])
            for f, b in zip(fused.fwd, fused.bwd)] == [(1, 4), (3, 3),
                                                       (4, 3), (4, 3),
                                                       (3, 1), (4, 1)]
    assert lay.m == 525_990
    assert lay.lane_occupancy == pytest.approx(525_625 / 525_990)
    assert _gathers(fused) == (3_153_110, 2_099_600)
    single = fuse_round_major(fwd, bwd, max_segments=1)
    assert single.layout.segments == ((128, 7991),)
    assert sum(h.cols.size for h in single.fwd + single.bwd) == 8_182_784


@pytest.mark.parametrize("which, bounds, gathers", [
    # gallery('poisson', 256): 64 rounds of 1,024 lanes, cut where K parts
    ("poisson2d", [0, 1, 32, 63, 64], (264_192, 261_120)),
    # HPCG's 27-point operator on 48^3: 14 segments, 768 steps an apply
    ("hpcg3d", [0, 32, 60, 97, 127, 128, 192, 223, 225, 256, 289, 350, 352,
                353, 384], (4_633_334, 2_752_696)),
])
def test_benchmark_pattern_counts(which, bounds, gathers):
    """The other benchmark plans' cuts and their gathers an apply (slots,
    live); the steps an apply stay 2 x rounds whatever the cut."""
    if which == "poisson2d":
        a = _load("bench_matrices", "bench/matrices.py").poisson_2d(256)
    else:
        a = _load("hpcg3d_generator",
                  "bench/configs/hpcg3d.py").hpcg_27pt(48)
    fwd, bwd = _pattern_tables(a)
    fused = fuse_round_major(fwd, bwd)
    assert list(np.cumsum([0] + [n for n, _ in fused.layout.segments])) \
        == bounds
    assert _gathers(fused) == gathers
    assert fused.n_steps == fwd.rows.shape[0] == bounds[-1]


@pytest.mark.parametrize("widths,kf,kb,lm,want", [
    # one width, but K parts: a cut where the joined segment would gather
    # more than the slack allows
    ([8] * 6, [0, 1, 1, 3, 3, 3], [4, 4, 4, 1, 1, 1], 1, [0, 3, 6]),
    # a narrow color after a wide one opens a segment
    ([64] * 4 + [8] * 4, [2] * 8, [2] * 8, 1, [0, 4, 8]),
    # a width that differs by a lane or two rides along
    ([64, 64, 63, 63, 62], [2] * 5, [2] * 5, 1, [0, 5]),
    # ... but not when it needs another K in the whole segment
    ([64, 64, 63, 63], [1, 1, 4, 4], [1, 1, 4, 4], 1, [0, 2, 4]),
    # widths are compared after rounding up to the lane multiple
    ([13, 16, 15, 14], [2] * 4, [2] * 4, 8, [0, 4]),
    # one width and one K per half: one segment
    ([8] * 6, [2] * 6, [3] * 6, 1, [0, 6]),
    # a K that differs in one round rides along when it costs little
    ([8] * 8, [4] * 7 + [3], [4] * 8, 1, [0, 8]),
])
def test_segment_rule(widths, kf, kb, lm, want):
    assert segment_bounds(widths, kf, kb, lane_multiple=lm) == want


def test_segment_count_is_capped():
    widths = [2 ** (i // 2) for i in range(40)]   # 20 widths, 1 to 512
    free = segment_bounds(widths, [1] * 40, [1] * 40)
    capped = segment_bounds(widths, [1] * 40, [1] * 40, max_segments=4)
    assert len(free) - 1 > 4
    assert len(capped) - 1 <= 4
    assert capped[0] == 0 and capped[-1] == 40
    assert segment_bounds(widths, [1] * 40, [1] * 40,
                          max_segments=1) == [0, 40]


# ---------------------------------------------------------------------------
# 2. The segmented apply is the substitution; one width is bitwise today's.
# ---------------------------------------------------------------------------

# the 16 x 16 grid's rounds share one width and are cut where K parts
@pytest.mark.parametrize("batched, grid, segments", [
    (False, 20, len(SEGMENTS_20)), (True, 20, len(SEGMENTS_20)),
    (False, 16, 4), (True, 16, 4)],
    ids=["single", "batched", "single-one-width", "batched-one-width"])
def test_segmented_apply_matches_sequential(batched, grid, segments):
    sysd, l_bar = _system(grid, grid)
    pre, lay = build_round_major_preconditioner_from_rounds(
        l_bar, sysd.fwd_rounds, sysd.bwd_rounds, drop_mask=sysd.drop)
    assert pre.tables.n_segments == segments
    if grid == 16:
        assert {r for _, r in pre.tables.segments} == {16}
    rng = np.random.default_rng(0)
    r = rng.normal(size=(sysd.n_padded, 3) if batched else sysd.n_padded)
    if sysd.drop is not None:
        r[sysd.drop] = 0.0
    apply_ = pre.apply_batched if batched else pre
    z = lay.extract(np.asarray(apply_(jnp.asarray(lay.embed(r)))))
    live = (np.ones(sysd.n_padded, bool) if sysd.drop is None
            else ~sysd.drop)
    for j in range(3) if batched else [None]:
        rj = r if j is None else r[:, j]
        zj = z if j is None else z[:, j]
        want = sequential_backward(l_bar, sequential_forward(l_bar, rj))
        np.testing.assert_allclose(zj[live], want[live], rtol=1e-11,
                                   atol=1e-11)


@pytest.mark.parametrize("method, grid, max_segments", [
    ("hbmc", (16, 16), 1), ("mc", (16, 16), 1), ("natural", (16, 16), 1),
    # a chain in natural order: rounds of one lane and one K per half,
    # which the cut itself leaves one segment
    ("natural", (16, 1), MAX_SEGMENTS),
], ids=["hbmc", "mc", "natural", "chain"])
def test_one_width_is_one_segment_bitwise(method, grid, max_segments):
    """One segment (the cut's, for rounds of one width and one K per half;
    else ``max_segments=1``) stacks its halves into the single-width fused
    tables, built here from the StepTables."""
    sysd, l_bar = _system(*grid, method)
    fwd, bwd, fused = _fused(sysd, l_bar, max_segments=max_segments)
    assert fused.n_segments == 1
    lay = round_major_layout(fwd)
    m = lay.m
    k = max(fwd.cols.shape[-1], bwd.cols.shape[-1])

    def legacy(t):
        cols = np.full(t.cols.shape[:2] + (k,), m, dtype=np.int32)
        vals = np.zeros(t.cols.shape[:2] + (k,), dtype=t.vals.dtype)
        cols[:, :, :t.cols.shape[-1]] = lay.pos[t.cols]
        vals[:, :, :t.cols.shape[-1]] = t.vals
        return cols, vals

    (fc, fv), (bc, bv) = legacy(fwd), legacy(bwd)
    cols, vals, dinv = stack_sweeps(fused.fwd[0], fused.bwd[0], m)
    np.testing.assert_array_equal(cols, np.concatenate([fc, bc]))
    np.testing.assert_array_equal(vals, np.concatenate([fv, bv]))
    np.testing.assert_array_equal(dinv, np.concatenate([fwd.dinv,
                                                        bwd.dinv]))
    np.testing.assert_array_equal(fused.layout.rows, lay.rows)
    np.testing.assert_array_equal(fused.layout.pos, lay.pos)


def test_schedule_proof_covers_every_segment():
    sysd, l_bar = _system(20, 20)
    _, _, fused = _fused(sysd, l_bar)
    assert check_fused_tables(fused) == []
    # the last segment's backward half reads its own destination
    lay = fused.layout
    off, (n, r) = lay.offsets[-1], lay.segments[-1]
    dst = off + (n - 1) * r                 # backward step 0, lane 0
    fused.bwd[-1].cols[0, 0, 0] = dst       # (step, k, lane)
    fused.bwd[-1].vals[0, 0, 0] = 1.0
    vio = check_fused_tables(fused)
    assert any(v.kind == "premature-read" and v.edge == (dst, dst)
               and v.round == lay.n_steps for v in vio), \
        [str(v) for v in vio]


# ---------------------------------------------------------------------------
# 3. The flat state and its counters.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nb", [1, 3])
def test_embed_extract_roundtrip_on_the_flat_state(nb):
    sysd, l_bar = _system(20, 20)
    _, _, fused = _fused(sysd, l_bar)
    lay = fused.layout
    assert lay.segments == SEGMENTS_20
    assert lay.m == sum(n * r for n, r in lay.segments) == 403
    assert lay.offsets == (0, 126, 186, 246, 306, 326, 333, 361, 373, 379,
                           400)
    rng = np.random.default_rng(nb)
    v = rng.normal(size=(sysd.n_padded,) if nb == 1 else (sysd.n_padded, nb))
    if sysd.drop is not None:
        v[sysd.drop] = 0.0
    y = lay.embed(v)
    assert y.shape[0] == lay.m
    np.testing.assert_array_equal(lay.extract(y), v)
    assert not y[lay.rows == lay.n_slots - 1].any()


def test_plan_counts_occupancy_over_the_layout():
    a = laplace_2d(20, 20)
    plan = build_plan(a, block_size=BS, w=W)
    live = np.count_nonzero(plan._rm.rows != plan._rm.n_slots - 1)
    n_seg = len(SEGMENTS_20)
    assert plan.n_segments == n_seg
    assert plan.lane_occupancy == live / plan.slab_m
    assert plan.slab_m == 403
    b = np.random.default_rng(1).normal(size=a.shape[0])
    rep = plan.solve(b, rtol=1e-8)
    assert (rep.n_segments, rep.lane_occupancy) == (n_seg,
                                                    plan.lane_occupancy)
    rep_b = plan.solve_batched(b[:, None], rtol=1e-8)
    assert (rep_b.n_segments, rep_b.lane_occupancy) == (n_seg,
                                                        plan.lane_occupancy)
    assert rep.result.converged
    assert np.linalg.norm(a @ rep.x - b) < 1e-6 * np.linalg.norm(b)
    # one width, cut only where K parts
    uniform = build_plan(laplace_2d(16, 16), block_size=BS, w=W)
    assert (uniform.n_segments, uniform.lane_occupancy) == (4, 1.0)
    index = build_plan(a, block_size=BS, w=W, layout="index")
    assert index.n_segments == 1
    assert index.solve(b, rtol=1e-8).result.iterations == \
        rep.result.iterations


def test_pallas_plan_stays_one_segment():
    """The Pallas fused kernel takes uniform tables: its plan packs all
    rounds as one segment where the XLA plan of the same grid cuts
    eleven."""
    plan = build_plan(laplace_2d(20, 20), block_size=BS, w=W,
                      backend="pallas")
    assert plan.n_segments == 1
    assert plan._precond.tables.segments == ((32, 21),)
    assert plan.sweep_steps == 2 * 32


# ---------------------------------------------------------------------------
# 4. Mesh parity over segments (forced host devices, fresh process).
# ---------------------------------------------------------------------------

MESH_CODE = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.analysis import check_plan_collectives
from repro.core.plan import build_plan
from repro.core.matrices import laplace_2d

n_dev = len(jax.devices())
a = laplace_2d(20, 20)
rng = np.random.default_rng(2)
b, bb = rng.normal(size=a.shape[0]), rng.normal(size=(a.shape[0], 2))
mesh = jax.make_mesh((n_dev,), ("data",))
ref = build_plan(a, block_size=8, w=4, lane_multiple=n_dev)
dist = build_plan(a, block_size=8, w=4, mesh=mesh)
assert dist._rm.segments == ref._rm.segments
assert dist.n_segments >= 3, dist._rm.segments
assert all(r % n_dev == 0 for _, r in dist._rm.segments)
r_ref, r = ref.solve(b, rtol=1e-9), dist.solve(b, rtol=1e-9)
assert r.result.iterations == r_ref.result.iterations
assert np.array_equal(r.x, r_ref.x)
rb_ref, rb = ref.solve_batched(bb, rtol=1e-9), dist.solve_batched(bb, rtol=1e-9)
assert np.array_equal(rb.result.iterations, rb_ref.result.iterations)
assert np.array_equal(rb.x, rb_ref.x)
assert check_plan_collectives(dist) == []
print("SEGMENTED_PARITY", n_dev, dist._rm.segments, r.result.iterations)
"""


def test_mesh_plan_is_bitwise_the_single_device_plan():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(MESH_CODE)],
                         env=env, capture_output=True, text=True,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SEGMENTED_PARITY 4" in out.stdout
