"""The reference's gate-level Design #2 against the program's product
table, over every pair of operands, and its gather-free matmul against a
plain sum of table lookups."""
import jax.numpy as jnp
import numpy as np

import design2
import reference


def test_product_equals_program_table():
    from repro.core import lut
    a = np.arange(256)[:, None]
    b = np.arange(256)[None, :]
    assert np.array_equal(design2.product(a, b), lut.build_lut("design2"))


def test_error_statistics_of_design2():
    # the error is one-directional, as the paper's Design #2: never above
    # the exact product, mean about -404
    a = np.arange(256)[:, None]
    err = design2.product(a, a.T) - a * a.T
    assert err.max() == 0
    assert -410 < err.mean() < -400


def test_approx_matmul_is_a_sum_of_products():
    rng = np.random.default_rng(3)
    x = rng.integers(0, 256, (5, 40))
    w = rng.integers(0, 256, (40, 7))
    table = design2.product(np.arange(256)[:, None], np.arange(256)[None])
    want = table[x[:, :, None], w[None]].sum(1)
    got = reference.approx_matmul(jnp.asarray(x, jnp.int32),
                                  jnp.asarray(w, jnp.int32))
    assert np.array_equal(np.asarray(got), want)
