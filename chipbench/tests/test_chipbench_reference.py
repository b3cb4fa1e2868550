"""The reference scan against float64 numpy; the control's precision."""
import numpy as np

from chipbench import control, datagen, reference


def _numpy_topk(Q, X, k):
    D = np.sqrt(((Q[:, None, :].astype(np.float64)
                  - X[None].astype(np.float64)) ** 2).sum(-1))
    idx = np.argsort(D, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(D, idx, 1), idx


def test_reference_equals_float64_numpy():
    X = np.asarray(datagen.make("manifold", 3, 0, 6000))
    Q = np.asarray(datagen.make("manifold", 3, 1, 37))
    # column blocks of 1000 (6 of them) and a padded last query block
    d, i = reference.exact_topk(Q, X, 10, query_block=16, col_cap=1000)
    want_d, want_i = _numpy_topk(Q, X, 10)
    assert i.shape == (37, 10)
    np.testing.assert_array_equal(i, want_i)
    np.testing.assert_allclose(d, want_d, rtol=2e-5)


def test_col_block_divides_n():
    for n in (10_000_000, 1_000_000, 6000, 97):
        b = reference.col_block_for(n, 8192)
        assert n % b == 0 and b <= 8192


def test_seed_above_32_bits_draws_other_data():
    a = np.asarray(datagen.make("manifold", 5, 0, 64))
    b = np.asarray(datagen.make("manifold", 5 + 2 ** 32, 0, 64))
    c = np.asarray(datagen.make("manifold", 5, 0, 64))
    assert not np.allclose(a, b)
    np.testing.assert_array_equal(a, c)


def test_device_generator_matches_the_numpy_original_in_shape_and_scale():
    a = np.asarray(datagen.make("manifold", 1, 0, 20000))
    from repro.data.synthetic import manifold

    b = manifold(20000, seed=1)
    na, nb = np.linalg.norm(a, axis=1).mean(), np.linalg.norm(b, axis=1).mean()
    assert a.shape == b.shape == (20000, 96)
    assert abs(na - nb) / nb < 0.05


def test_high_dot_is_three_bf16_passes():
    rng = np.random.default_rng(0)
    a = rng.normal(size=(8, 96)).astype(np.float32)
    b = rng.normal(size=(16, 96)).astype(np.float32)
    exact = a.astype(np.float64) @ b.T.astype(np.float64)
    got = np.asarray(control.high_dot(a, b))
    err = np.max(np.abs(got - exact)) / np.max(np.abs(exact))
    f32 = np.max(np.abs(a @ b.T - exact)) / np.max(np.abs(exact))
    # well above float32 rounding, well below one bf16 pass (2**-8)
    assert 10 * f32 < err < 2.0 ** -12
