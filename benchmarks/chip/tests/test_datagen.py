"""The benchmark's copies of the traffic generators equal the program's
output for seed 0."""

import numpy as np

import datagen


def test_calories_table_equals_program():
    from repro.data import CaloriesDatasetConfig, make_calories_tabular
    want = make_calories_tabular(CaloriesDatasetConfig(seed=0))
    got = datagen.calories_table(0)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_har_windows_equal_program():
    from repro.data import HARDatasetConfig, make_har_windows
    want = make_har_windows(HARDatasetConfig(seed=0))
    got = datagen.har_windows(0)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_dirichlet_split_equals_program():
    from repro.data import dirichlet_partition
    y = datagen.calories_table(0)[1]
    for alpha in (100.0, 0.5, 0.01):
        want = dirichlet_partition(y, num_clients=6, alpha=alpha, seed=0)
        got = datagen.dirichlet_split(y, 6, alpha=alpha, seed=0)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)


def test_neighbour_draws_equal_make_fleet():
    import dataclasses
    from repro.core import make_fleet
    for p in (1.0, 0.9):
        want = [dataclasses.asdict(d) for d in make_fleet(5, seed=1, p_has_model=p)]
        assert datagen.neighbour_draws(5, seed=1, p_has_model=p) == want


def test_fixed_size_cuts_and_repeats():
    idx = np.arange(5)
    np.testing.assert_array_equal(datagen.fixed_size(idx, 3), [0, 1, 2])
    np.testing.assert_array_equal(datagen.fixed_size(idx, 7), [0, 1, 2, 3, 4, 0, 1])
