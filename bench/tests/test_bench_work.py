"""Work counts and the peaks table, against hand counts at small shapes."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import work  # noqa: E402

SMALL = {"rows": 1000, "dim": 48, "encoding": "mtmc", "cl": 32,
         "mcam": {"string_len": 24}}


def test_levels_and_stored_bits():
    assert work.levels(SMALL) == 97                  # 3 * 32 + 1
    with pytest.raises(ValueError):
        work.levels(dict(SMALL, encoding="b4e"))


@pytest.mark.parametrize("mode,extra_bytes", [
    ("ideal", 0.0),
    # 4 queries x k=2 rows x (2 segments x 32 words x 24 cells) x 2 bits
    ("two_phase", 4 * 2 * 2 * 32 * 24 * 2 / 8),
])
def test_search_least_work(mode, extra_bytes):
    ops, nbytes = work.search_work(SMALL, batch=4, k=2, mode=mode)
    assert ops == 2 * 4 * 1000 * 48
    # 97 levels need 7 bits: 1000 rows x 48 dims x 7 bits
    assert nbytes == 1000 * 48 * 7 / 8 + extra_bytes


def test_search_least_work_refuses_unknown_mode():
    with pytest.raises(ValueError):
        work.search_work(SMALL, batch=4, k=2, mode="full")


def test_least_seconds_takes_the_binding_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(1000.0, 50.0, peak) == 10.0   # compute-bound
    assert work.least_seconds(100.0, 50.0, peak) == 5.0     # memory-bound


def test_peaks_table_names_the_v5e_and_refuses_others():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
