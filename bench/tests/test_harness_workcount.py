"""The least-bytes counts of the planning kernels on known shapes."""

import harness  # noqa: F401  (puts the benchmark on sys.path)
import workcount


def test_candidate_rows():
    assert workcount.candidate_rows(8, 4, bypass=False) == 4
    assert workcount.candidate_rows(8, 4, bypass=True) == 8
    assert workcount.candidate_rows(3, 4, bypass=False) == 7
    assert workcount.candidate_rows(4, 4, bypass=False) == 15


def test_fused_chain_scan_bytes_by_hand():
    # 2 cells x 3 steps x 2 planes, 4 candidate rows, no bypass:
    # tables 2 * (2*3*8 + 3*2*8 + 8) = 208, candidates 2*4*2 = 16,
    # per step 2 * 3 * (2*8 + 1) = 102.
    assert workcount.fused_chain_scan_bytes(2, 3, 2, 4, bypass=False) == 326
    # Bypass adds a relay depth per plane and step: 2*3*2*8 more.
    assert workcount.fused_chain_scan_bytes(2, 3, 2, 4, bypass=True) == 422


def test_timing_scan_bytes_by_hand():
    # 1 instance x 2 steps x 2 planes: in 2*2*8 + 2*2*8 + 2*2*8 = 96,
    # out 2*8 + 2*8 + 2 = 34.
    assert workcount.timing_scan_bytes(1, 2, 2) == 130
    assert workcount.timing_scan_bytes(3, 2, 2) == 390


def test_the_chip_cell_counts_scale_with_the_problem():
    one = workcount.fused_chain_scan_bytes(1024, 127, 4, 15, bypass=False)
    two = workcount.fused_chain_scan_bytes(2048, 127, 4, 15, bypass=False)
    assert two == 2 * one
    assert one > 1024 * 127 * 4 * 8  # at least the splits written once
