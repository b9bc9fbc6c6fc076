"""Pure-jnp oracles for the Pallas kernels (bit-exact semantics).

Jitted, as the kernels are: the CPU compiler then fuses each
multiply-reduce the same way in both (see ``core.iccg.k_sum``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.iccg import k_sum


@jax.jit
def hbmc_trisolve_ref(cols: jax.Array, vals: jax.Array, dinv: jax.Array,
                      q: jax.Array) -> jax.Array:
    """Round-major triangular solve, fori_loop + dynamic_update_slice."""
    s_, r_, k_ = cols.shape
    y0 = jnp.zeros((s_ * r_,), dtype=vals.dtype)

    def body(s, y):
        g = jnp.take(y, cols[s], axis=0, fill_value=0)     # (R, K)
        acc = k_sum(vals[s], g, axis=-1)
        t = (q[s] - acc) * dinv[s]
        return jax.lax.dynamic_update_slice(y, t, (s * r_,))

    return jax.lax.fori_loop(0, s_, body, y0)


@jax.jit
def hbmc_trisolve_batched_ref(cols: jax.Array, vals: jax.Array,
                              dinv: jax.Array, q: jax.Array) -> jax.Array:
    """Multi-RHS round-major triangular solve.  q: (S, R, B) -> (S*R, B)."""
    s_, r_, k_ = cols.shape
    b_ = q.shape[-1]
    y0 = jnp.zeros((s_ * r_, b_), dtype=vals.dtype)

    def body(s, y):
        g = jnp.take(y, cols[s], axis=0, fill_value=0)     # (R, K, B)
        acc = k_sum(vals[s][..., None], g, axis=1)         # (R, B)
        t = (q[s] - acc) * dinv[s][:, None]
        return jax.lax.dynamic_update_slice(y, t, (s * r_, 0))

    return jax.lax.fori_loop(0, s_, body, y0)


@jax.jit
def sell_spmv_ref(vals: jax.Array, cols: jax.Array, x: jax.Array) -> jax.Array:
    """SELL-w SpMV oracle.  vals/cols: (n_slices, K, w); x: (n,)."""
    g = jnp.take(x, cols, axis=0, fill_value=0)            # (S, K, w)
    return k_sum(vals, g, axis=1).reshape(-1)


@jax.jit
def sell_spmv_batched_ref(vals: jax.Array, cols: jax.Array,
                          x: jax.Array) -> jax.Array:
    """Multi-RHS SELL-w SpMV oracle.  x: (n, B) -> (n_slices*w, B)."""
    g = jnp.take(x, cols, axis=0, fill_value=0)            # (S, K, w, B)
    return k_sum(vals[..., None], g, axis=1).reshape(-1, x.shape[-1])


@jax.jit
def hbmc_trisolve_fused_ref(cols: jax.Array, vals: jax.Array,
                            dinv: jax.Array, q: jax.Array) -> jax.Array:
    """Fused fwd+bwd round-major solve oracle.  cols: (2S, R, K); q: (S, R).

    Mirrors the fused kernel step for step: one buffer, forward half fills
    y slice by slice, backward half overwrites it in place in reverse slice
    order (see kernels/hbmc_trisolve.py for why that is safe).

    Deliberately NOT shared with core.trisolve._sweeps: this
    oracle reproduces the kernel's exact op order (elementwise multiply +
    jnp.sum -> bit-exact in interpret mode, asserted in tests), while the
    XLA production path contracts with einsum, which is faster on CPU but
    reassociates the K-reduction.  The K-reduction runs in k order
    (``k_sum``), as the kernel adds one product plane per k.
    """
    s2, r_, k_ = cols.shape
    s_ = s2 // 2
    y0 = jnp.zeros((s_ * r_,), dtype=vals.dtype)

    def body(g, y):
        g_fwd = jnp.take(y, cols[g], axis=0, fill_value=0)     # (R, K)
        acc = k_sum(vals[g], g_fwd, axis=-1)
        dest = jnp.where(g < s_, g, s2 - 1 - g) * r_
        q_cur = jnp.where(g < s_, q[jnp.minimum(g, s_ - 1)],
                          jax.lax.dynamic_slice(y, (dest,), (r_,)))
        t = (q_cur - acc) * dinv[g]
        return jax.lax.dynamic_update_slice(y, t, (dest,))

    return jax.lax.fori_loop(0, s2, body, y0)


@jax.jit
def hbmc_trisolve_fused_batched_ref(cols: jax.Array, vals: jax.Array,
                                    dinv: jax.Array, q: jax.Array
                                    ) -> jax.Array:
    """Multi-RHS fused oracle.  cols: (2S, R, K); q: (S, R, B) -> (S*R, B)."""
    s2, r_, k_ = cols.shape
    s_ = s2 // 2
    b_ = q.shape[-1]
    y0 = jnp.zeros((s_ * r_, b_), dtype=vals.dtype)

    def body(g, y):
        g_fwd = jnp.take(y, cols[g], axis=0, fill_value=0)     # (R, K, B)
        acc = k_sum(vals[g][..., None], g_fwd, axis=1)         # (R, B)
        dest = jnp.where(g < s_, g, s2 - 1 - g) * r_
        zero = jnp.zeros_like(dest)
        q_cur = jnp.where(g < s_, q[jnp.minimum(g, s_ - 1)],
                          jax.lax.dynamic_slice(y, (dest, zero), (r_, b_)))
        t = (q_cur - acc) * dinv[g][:, None]
        return jax.lax.dynamic_update_slice(y, t, (dest, zero))

    return jax.lax.fori_loop(0, s2, body, y0)
