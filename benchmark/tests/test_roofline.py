import json
import os

import numpy as np
import pytest

import roofline


def rows(n=1, words=2 * roofline.CONTAINER_WORDS):
    return np.zeros((n, words), dtype=np.uint32)


def test_empty_row_costs_nothing():
    assert roofline.least_bytes(rows()).tolist() == [0]


def test_hundred_scattered_bits_are_an_array():
    r = rows()
    r[0, np.arange(100) * 7] = 1           # 100 bits, none adjacent
    assert roofline.least_bytes(r).tolist() == [200]


def test_full_container_is_one_run():
    r = rows()
    r[0, :roofline.CONTAINER_WORDS] = 0xFFFFFFFF
    card, runs = roofline.container_stats(r)
    assert card.tolist() == [[65536, 0]] and runs.tolist() == [[1, 0]]
    assert roofline.least_bytes(r).tolist() == [4]


def test_dense_random_container_is_a_bitmap():
    rng = np.random.default_rng(0)
    r = rng.integers(0, 1 << 32, size=(1, roofline.CONTAINER_WORDS),
                     dtype=np.uint32)
    assert roofline.least_bytes(r).tolist() == [roofline.BITMAP_BYTES]


def test_a_run_across_a_container_edge_counts_on_both_sides():
    r = rows()
    r[0, roofline.CONTAINER_WORDS - 1] = 0x80000000
    r[0, roofline.CONTAINER_WORDS] = 0x1
    _, runs = roofline.container_stats(r)
    assert runs.tolist() == [[1, 1]]


def test_runs_across_words_inside_a_container():
    r = rows()
    r[0, 3] = 0xF0000000
    r[0, 4] = 0x0000000F                    # one run of 8 across two words
    r[0, 9] = 0b0101                        # two more
    _, runs = roofline.container_stats(r)
    assert runs.tolist() == [[3, 0]]


def test_peaks_know_the_v5e_and_refuse_the_unknown():
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peak("cpu")
