"""solve_vcc's kernel dispatch path must match its jnp oracle path on CPU,
and the dispatcher must take traced scalars under jit and vmap."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import vcc

_vcc_problem = vcc.synthetic_problem


def test_solve_vcc_interpret_kernel_matches_ref():
    """The vcc_pgd kernel path INSIDE solve_vcc (Pallas interpreter on
    CPU) must match the jnp oracle path: same inner-loop math, two
    dispatch targets."""
    p = _vcc_problem()
    ref = vcc.solve_vcc(p, inner_iters=40, outer_iters=4, use_pallas=False)
    ker = vcc.solve_vcc(p, inner_iters=40, outer_iters=4, interpret=True)
    np.testing.assert_allclose(np.asarray(ker.delta), np.asarray(ref.delta),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ker.vcc), np.asarray(ref.vcc),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ker.shaped),
                                  np.asarray(ref.shaped))
    np.testing.assert_allclose(float(ker.objective), float(ref.objective),
                               rtol=1e-5)


def test_solve_vcc_traced_scalars_under_jit_and_vmap():
    """The dispatcher accepts traced temp/lambda_e: solve_vcc must jit and
    vmap cleanly through kernels.vcc_pgd.ops (the old wrapper called
    float() on them and could not)."""
    p = _vcc_problem(n=6)
    sol_eager = vcc.solve_vcc(p, inner_iters=10, outer_iters=2)
    sol_jit = jax.jit(lambda q: vcc.solve_vcc(q, inner_iters=10,
                                              outer_iters=2))(p)
    np.testing.assert_allclose(np.asarray(sol_jit.delta),
                               np.asarray(sol_eager.delta),
                               rtol=1e-5, atol=1e-6)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           _vcc_problem(n=6, seed=1),
                           _vcc_problem(n=6, seed=2))
    solb = vcc.solve_vcc_batched(stacked, inner_iters=10, outer_iters=2)
    assert solb.delta.shape == (2, 6, 24)
