"""The segmented round-major layout: each run of rounds at its own width.

HBMC's colors can differ widely in width (the heat-step benchmark's four
colors hold 7,991, about 7,900, 434 and 99 lanes a round).  The sweep
packs consecutive rounds into segments, each at its own lane width and
its own K per half (``sell.segment_bounds``), so padding lanes are
neither gathered by the sweep nor carried by the SpMV and vector work.
Pinned here:

  1. segment widths and K are the live maxima of their rounds, and the
     cut falls where the colors' widths part;
  2. the segmented apply (single and batched) is the sequential
     substitution, and a plan of one width is one segment bitwise equal
     to the single-width packing;
  3. the flat state round-trips through embed/extract, and the plan and
     its reports count lane occupancy over the layout's slots;
  4. the mesh plan stays bitwise the single-device plan with the same
     lane multiple, one tiled all-gather per step of every segment.
"""
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
from repro.core.matrices import laplace_2d
from repro.core.plan import _order_system
from repro.core.sell import stack_sweeps
from repro.core.trisolve import (build_round_major_preconditioner_from_rounds,
                                 sequential_backward, sequential_forward)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS, W = 8, 4


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
    """On a 20 x 20 grid the colors' rounds hold 21, 7 and 3 lanes: one
    segment each, at the colors' own widths and K."""
    sysd, l_bar = _system(20, 20)
    fwd, bwd, fused = _fused(sysd, l_bar)
    lay = fused.layout
    assert lay.segments == ((16, 21), (8, 7), (8, 3))
    kf, kb = fwd.live_k, bwd.live_k[::-1]
    start = 0
    for f, b, (n, r) in zip(fused.fwd, fused.bwd, lay.segments):
        rounds = slice(start, start + n)
        assert start % BS == 0                  # a color boundary
        assert r == fwd.live[rounds].max()
        assert f.cols.shape == (n, kf[rounds].max(), r)     # lanes minor
        assert b.cols.shape == (n, kb[rounds].max(), r)
        start += n
    assert start == lay.n_steps == fwd.rows.shape[0]
    assert [f.cols.shape[1] for f in fused.fwd] == [4, 4, 4]
    assert [b.cols.shape[1] for b in fused.bwd] == [4, 3, 1]


def test_heat_step_pattern_counts():
    """The heat2d benchmark's pattern (725 x 725, HBMC block 32, w 8):
    four segments, one per color, and 3,418,944 gathered values an apply
    where one width gathered 8,182,784."""
    sysd = _order_system(sp.csr_matrix(laplace_2d(725, 725)), None, "hbmc",
                         32, 8)
    fwd, bwd = pack_factor(sp.tril(sysd.a_bar, format="csr"),
                           sysd.fwd_rounds, sysd.bwd_rounds, sysd.drop)
    fused = fuse_round_major(fwd, bwd)
    lay = fused.layout
    assert lay.segments == ((32, 7991), (32, 7909), (32, 434), (32, 99))
    assert [(f.cols.shape[1], b.cols.shape[1])
            for f, b in zip(fused.fwd, fused.bwd)] == [(2, 4), (4, 3),
                                                       (4, 3), (4, 1)]
    assert lay.m == 525_856
    assert lay.lane_occupancy == pytest.approx(525_625 / 525_856)
    assert sum(h.cols.size for h in fused.fwd + fused.bwd) == 3_418_944
    single = fuse_round_major(fwd, bwd, max_segments=1)
    assert single.layout.segments == ((128, 7991),)
    assert sum(h.cols.size for h in single.fwd + single.bwd) == 8_182_784


@pytest.mark.parametrize("widths,kf,kb,lm,want", [
    # one width: one segment, whatever K does
    ([8] * 6, [0, 1, 1, 3, 3, 3], [4, 4, 4, 1, 1, 1], 1, [0, 6]),
    # a narrow color after a wide one opens a segment
    ([64] * 4 + [8] * 4, [2] * 8, [2] * 8, 1, [0, 4, 8]),
    # a width that differs by a lane or two rides along
    ([64, 64, 63, 63, 62], [2] * 5, [2] * 5, 1, [0, 5]),
    # ... but not when it needs another K in the whole segment
    ([64, 64, 63, 63], [1, 1, 4, 4], [1, 1, 4, 4], 1, [0, 2, 4]),
    # widths are compared after rounding up to the lane multiple
    ([13, 16, 15, 14], [2] * 4, [2] * 4, 8, [0, 4]),
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

@pytest.mark.parametrize("batched", [False, True], ids=["single", "batched"])
def test_segmented_apply_matches_sequential(batched):
    sysd, l_bar = _system(20, 20)
    pre, lay = build_round_major_preconditioner_from_rounds(
        l_bar, sysd.fwd_rounds, sysd.bwd_rounds, drop_mask=sysd.drop)
    assert pre.tables.n_segments == 3
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


@pytest.mark.parametrize("method", ["hbmc", "mc", "natural"])
def test_one_width_is_one_segment_bitwise(method):
    """Rounds of one width pack as one segment whose stacked halves are
    the single-width fused tables, built here from the StepTables."""
    sysd, l_bar = _system(16, 16, method)
    fwd, bwd, fused = _fused(sysd, l_bar)
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
    assert lay.m == sum(n * r for n, r in lay.segments) == 16 * 21 + 8 * 7 \
        + 8 * 3
    assert lay.offsets == (0, 16 * 21, 16 * 21 + 8 * 7)
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
    assert plan.n_segments == 3
    assert plan.lane_occupancy == live / plan.slab_m
    assert plan.slab_m == 16 * 21 + 8 * 7 + 8 * 3
    b = np.random.default_rng(1).normal(size=a.shape[0])
    rep = plan.solve(b, rtol=1e-8)
    assert (rep.n_segments, rep.lane_occupancy) == (3, plan.lane_occupancy)
    rep_b = plan.solve_batched(b[:, None], rtol=1e-8)
    assert (rep_b.n_segments, rep_b.lane_occupancy) == (3,
                                                        plan.lane_occupancy)
    assert rep.result.converged
    assert np.linalg.norm(a @ rep.x - b) < 1e-6 * np.linalg.norm(b)
    uniform = build_plan(laplace_2d(16, 16), block_size=BS, w=W)
    assert (uniform.n_segments, uniform.lane_occupancy) == (1, 1.0)
    index = build_plan(a, block_size=BS, w=W, layout="index")
    assert index.n_segments == 1
    assert index.solve(b, rtol=1e-8).result.iterations == \
        rep.result.iterations


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
