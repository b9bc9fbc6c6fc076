"""Multi-device semantics, via subprocesses with forced host device counts
(jax pins the device count at first init, so these must be fresh processes).

Covers: distributed ICCG (solver sharded over a mesh) iterating identically
to single-device, and one solver iteration lowering on a mesh.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


PARITY_CODE = """
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from repro.core.plan import build_plan
    from repro.core.matrices import laplace_2d

    n_dev = {n_dev}
    assert len(jax.devices()) == n_dev
    a = laplace_2d(13, 17)               # n=221: padding in every ordering
    n = a.shape[0]
    rng = np.random.default_rng(0)
    b = rng.normal(size=n)
    bb = rng.normal(size=(n, 3))
    mesh = jax.make_mesh((n_dev,), ("data",))
    for method in ("hbmc", "bmc"):
        # single-device oracle with MATCHED lane padding: the distributed
        # sweep's per-lane arithmetic is identical, so everything —
        # iteration counts AND solutions — must agree bitwise
        ref = build_plan(a, method=method, block_size=8, w=4,
                         lane_multiple=n_dev)
        dist = build_plan(a, method=method, block_size=8, w=4, mesh=mesh)
        r_ref, r = ref.solve(b, rtol=1e-9), dist.solve(b, rtol=1e-9)
        assert r.x.shape == (n,)
        assert r.result.iterations == r_ref.result.iterations
        assert np.array_equal(r.x, r_ref.x)
        rb_ref = ref.solve_batched(bb, rtol=1e-9)
        rb = dist.solve_batched(bb, rtol=1e-9)
        assert np.array_equal(rb.result.iterations, rb_ref.result.iterations)
        assert np.array_equal(rb.x, rb_ref.x)
        # and against the DEFAULT (unpadded) plan the solve still converges
        # to the same solution (lane padding may perturb reduction
        # rounding, so this check is tolerance-based)
        base = build_plan(a, method=method, block_size=8, w=4)
        rp = base.solve(b, rtol=1e-9)
        err = np.linalg.norm(r.x - rp.x) / np.linalg.norm(rp.x)
        assert err < 1e-8, err
        print("PARITY", method, n_dev, r.result.iterations,
              list(rb.result.iterations))
"""


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_distributed_plan_matches_single_device(n_dev):
    """Distributed plan == single-device plan, bitwise: iteration counts and
    solutions for hbmc/bmc x single/batched at every device count."""
    out = run_py(textwrap.dedent(PARITY_CODE.format(n_dev=n_dev)),
                 n_devices=n_dev)
    assert out.count("PARITY") == 2


SPMV_PALLAS_CODE = """
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from repro.core.plan import build_plan
    from repro.core.matrices import laplace_2d

    n_dev = {n_dev}
    assert len(jax.devices()) == n_dev
    a = laplace_2d(13, 17)               # n=221: padded tail slices
    n = a.shape[0]
    rng = np.random.default_rng(1)
    b = rng.normal(size=n)
    bb = rng.normal(size=(n, 3))
    mesh = jax.make_mesh((n_dev,), ("data",))
    kw = dict(method="hbmc", block_size=8, w=4, spmv_format="sell",
              mesh=mesh)
    px = build_plan(a, **kw)
    pp = build_plan(a, spmv_backend="pallas", **kw)
    rx, rp = px.solve(b), pp.solve(b)
    assert rx.result.iterations == rp.result.iterations
    assert np.array_equal(rx.x, rp.x)
    rbx, rbp = px.solve_batched(bb), pp.solve_batched(bb)
    assert np.array_equal(rbx.result.iterations, rbp.result.iterations)
    assert np.array_equal(rbx.x, rbp.x)
    print("SPMV_PALLAS", n_dev, rx.result.iterations)
"""


@pytest.mark.parametrize("n_dev", [2, 4])
def test_sharded_pallas_spmv_matches_xla(n_dev):
    """spmv_backend='pallas' under a REAL multi-shard mesh (sell_spmv_block
    per device inside shard_map) reproduces the sharded xla SpMV bitwise —
    the >1-device counterpart of the 1-device mesh test in
    tests/test_spmv.py."""
    out = run_py(textwrap.dedent(SPMV_PALLAS_CODE.format(n_dev=n_dev)),
                 n_devices=n_dev)
    assert "SPMV_PALLAS" in out


def test_distributed_iccg_returns_caller_ordering():
    """Regression (padded-state leak): the seed-era distributed path fed the
    padded HBMC system into pcg and returned the internal padded/permuted
    vector.  The shim must return the solution in the caller's ordering,
    shape (n,), on a system whose padded size differs from n."""
    code = textwrap.dedent("""
        import jax
        jax.config.update("jax_enable_x64", True)
        import numpy as np
        from repro.core import solve_iccg
        from repro.core.partition import distributed_iccg
        from repro.core.matrices import laplace_2d

        a = laplace_2d(13, 17)            # n=221 -> padded size > n
        n = a.shape[0]
        b = np.random.default_rng(0).normal(size=n)
        ref = solve_iccg(a, b, method="hbmc", block_size=8, w=4, rtol=1e-9)
        mesh = jax.make_mesh((4,), ("data",))
        rep = distributed_iccg(a, b, mesh, block_size=8, w=4, rtol=1e-9)
        assert rep.n_padded > n           # padding actually exercised
        assert rep.x.shape == (n,)
        assert rep.result.x.shape == (n,)
        err = np.linalg.norm(rep.x - ref.x) / np.linalg.norm(ref.x)
        print("LEAK-REGRESSION ERR", err)
        assert err < 1e-8
        # A x = b in the ORIGINAL ordering is the leak-proof check
        res = np.linalg.norm(a @ rep.x - b) / np.linalg.norm(b)
        assert res < 1e-8
    """)
    out = run_py(code, n_devices=4)
    assert "LEAK-REGRESSION" in out


def test_distributed_refactor_zero_retrace():
    """plan.refactor under a mesh swaps sharded device arrays without
    retracing the jitted PCG, and warm solves do zero host-side setup."""
    code = textwrap.dedent("""
        import jax
        jax.config.update("jax_enable_x64", True)
        import numpy as np
        import repro.core.plan as plan_mod
        from repro.core.plan import build_plan
        from repro.core.matrices import laplace_2d

        a = laplace_2d(13, 17)
        n = a.shape[0]
        b = np.random.default_rng(0).normal(size=n)
        mesh = jax.make_mesh((2,), ("data",))
        plan = build_plan(a, method="hbmc", block_size=8, w=4, mesh=mesh)
        r1 = plan.solve(b, rtol=1e-9)
        count = plan.setup_count

        names = ("_order_system", "ic0_structure", "_build_spmv_ops",
                 "_pack_spmv", "_build_preconditioner")
        saved = {name: getattr(plan_mod, name) for name in names}

        def boom(*a_, **k_):
            raise AssertionError("setup ran during a warm mesh solve")
        for name in names:
            setattr(plan_mod, name, boom)
        warm = plan.solve(b, rtol=1e-9)          # zero host-side setup
        assert plan.setup_count == count
        np.testing.assert_array_equal(warm.x, r1.x)
        for name, fn in saved.items():
            setattr(plan_mod, name, fn)

        # refactor: new values, same pattern -> sharded arrays swapped,
        # jitted PCG reused without a retrace (ordering + symbolic analysis
        # must not rerun either)
        plan_mod._order_system = boom
        plan_mod.ic0_structure = boom
        a2 = a.copy(); a2.data = a2.data * 1.1
        plan.refactor(a2)
        r2 = plan.solve(b, rtol=1e-9)
        assert plan._trace_count == 1, plan._trace_count
        plan_mod._order_system = saved["_order_system"]
        plan_mod.ic0_structure = saved["ic0_structure"]
        ref = plan_mod.build_plan(a2, method="hbmc", block_size=8, w=4,
                                  lane_multiple=2).solve(b, rtol=1e-9)
        np.testing.assert_array_equal(r2.x, ref.x)
        print("RETRACE OK", plan._trace_count)
    """)
    out = run_py(code, n_devices=2)
    assert "RETRACE OK 1" in out


def test_solver_step_lowers_on_mesh():
    """Bonus dry-run: one ICCG iteration (the paper's kernel) lowers and
    compiles with the tables sharded over the mesh data axis — and the
    lowered module contains BOTH triangular sweeps (regression: the
    seed-era iteration used the unpreconditioned (r, r) pairings, which
    lowered a plain-CG kernel with zero trisolve loops)."""
    code = textwrap.dedent("""
        import jax
        jax.config.update("jax_enable_x64", True)
        import numpy as np, jax.numpy as jnp
        from repro.core import (block_multicolor_ordering, hbmc_from_bmc,
                                pad_system_hbmc, ic0, pack_factor_hbmc)
        from repro.core.trisolve import DeviceTables
        from repro.core.partition import lower_solver_step
        from repro.core.sell import pack_ell
        from repro.core.matrices import laplace_2d

        a = laplace_2d(32, 32)
        bmc = block_multicolor_ordering(a, 8)
        hb = hbmc_from_bmc(bmc, 4)
        a_hb, _ = pad_system_hbmc(a, None, hb)
        l = ic0(a_hb)
        fwd_h, bwd_h = pack_factor_hbmc(l, hb)
        fwd = DeviceTables.from_host(fwd_h)
        bwd = DeviceTables.from_host(bwd_h)
        cols, vals = pack_ell(a_hb)
        mesh = jax.make_mesh((8,), ("data",))
        lowered = lower_solver_step(fwd, bwd, jnp.asarray(cols),
                                    jnp.asarray(vals), mesh)
        # the fwd and bwd substitution fori_loops — a plain-CG lowering
        # (the seed bug) has none
        n_while = lowered.as_text().count("while")
        assert n_while >= 2, n_while
        compiled = lowered.compile()
        txt = compiled.as_text()
        assert "all-gather" in txt or "all-reduce" in txt
        ca = compiled.cost_analysis()   # list of dicts on newer jax
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        print("SOLVER LOWERED", ca.get("flops"), "whiles", n_while)
    """)
    out = run_py(code)
    assert "SOLVER LOWERED" in out
