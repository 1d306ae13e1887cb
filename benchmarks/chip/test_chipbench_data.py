"""The benchmark's copy of the synthetic data generators makes the
program's images to the bit, and its cache hands back what it rendered."""
import numpy as np
import pytest

from chipbench import data


@pytest.mark.parametrize("generator,program", [
    ("extended_mnist", "make_extended_mnist"),
    ("not_mnist", "make_not_mnist"),
])
def test_copy_matches_the_program_generator(generator, program):
    from repro.data import synthetic
    for seed in (1, 2 ** 31 + 5):
        want = getattr(synthetic, program)(n_per_class=7, seed=seed)
        x, y = data.GENERATORS[generator](7, seed)
        assert x.dtype == want.x.dtype and y.dtype == want.y.dtype
        np.testing.assert_array_equal(x, want.x)
        np.testing.assert_array_equal(y, want.y)


def test_cache_returns_what_was_rendered(tmp_path):
    x, y = data.make("not_mnist", 3, 11, str(tmp_path))
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "not_mnist-3-11.x.npy", "not_mnist-3-11.y.npy"]
    x2, y2 = data.make("not_mnist", 3, 11, str(tmp_path))
    np.testing.assert_array_equal(x, x2)
    np.testing.assert_array_equal(y, y2)
    assert x.shape == (60, 28, 28) and list(np.bincount(y)) == [3] * 20


def test_partitions_cover_the_rows_once():
    x = np.arange(40, dtype=np.float32)
    y = np.arange(40) % 4
    for how in ("iid", "contiguous"):
        parts = data.partition(x, y, 4, how, seed=3)
        rows = np.concatenate([p[0] for p in parts])
        assert sorted(rows.tolist()) == x.tolist()
    assert parts[0][0].tolist() == list(range(10))
    with pytest.raises(ValueError, match="unknown partition"):
        data.partition(x, y, 4, "dirichlet", seed=3)
