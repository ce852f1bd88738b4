"""Work functions against bytes and flops counted by hand at tiny shapes."""

import pytest

from yardstick import work

PEAK = {"hbm_bytes_per_s": 1000.0}


def test_stencil_counts_one_read_one_write_two_flops_a_tap():
    # 2x3x4 = 24 points, float32: 24*4 read + 24*4 written; 7 taps
    assert work.stencil((2, 3, 4), taps=7) == (192.0, 2 * 7 * 24)


def test_penta_counts_two_passes_and_sixteen_flops_a_point():
    assert work.penta((8, 8)) == (2 * 64 * 4, 16 * 64)
    assert work.penta((8, 8), sweeps=2) == (4 * 64 * 4, 32 * 64)


def test_ch_xsweep_reads_two_fields_writes_one():
    # RHS: 34 taps * 2 flops; one sweep: 16 flops
    assert work.ch_xsweep((4, 4)) == (3 * 16 * 4, (68 + 16) * 16)


def test_ch_step_counts_rhs_two_sweeps_and_update():
    assert work.ch_step((4, 4), itemsize=8) == (3 * 16 * 8, (68 + 32 + 6) * 16)


@pytest.mark.parametrize("bytes_,want", [(1000.0, 1.0), (250.0, 0.25)])
def test_floor_is_the_bandwidth_side(bytes_, want):
    assert work.floor_s(bytes_, PEAK) == want
