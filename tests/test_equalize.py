"""Tests for PEB estimation + the n-control loop (core/equalize.py)."""
import numpy as np
import pytest

from repro.core import equalize as E
from repro.core.fragment import EpochRecords


def test_peb_row_formulas():
    c = np.array([3, -4, 0, 0], dtype=np.int64)
    # CS (Eq. 4): sqrt(sum(c^2)/w) = sqrt(25/4)
    assert E.peb_row(c, "cs") == pytest.approx(np.sqrt(25 / 4))
    # CMS: sum(c)/w
    c2 = np.array([3, 4, 0, 1], dtype=np.int64)
    assert E.peb_row(c2, "cms") == pytest.approx(8 / 4)


def test_peb_epoch_averages_subepochs():
    counters = np.stack([np.full(8, 2, np.int64),
                         np.full(8, 4, np.int64)])
    rec = EpochRecords(0, 0, 2, counters, "cms", False)
    assert E.peb_epoch(rec) == pytest.approx(3.0)  # mean of 2 and 4


def test_peb_um_uses_level0():
    counters = np.zeros((4, 2, 8), np.int64)
    counters[0] += 4   # level 0
    counters[1] += 100  # deeper levels must be ignored
    rec = EpochRecords(0, 0, 2, counters, "um", False)
    assert E.peb_epoch(rec) == pytest.approx(np.sqrt(16 * 8 / 8))


def test_next_n_control_loop():
    # Eq. 6: double when peb > 2*target, halve when < target/2
    assert E.next_n(4, peb=10.0, rho_target=1.0) == 8
    assert E.next_n(4, peb=0.4, rho_target=1.0) == 2
    assert E.next_n(4, peb=1.5, rho_target=1.0) == 4
    assert E.next_n(1, peb=0.001, rho_target=1.0) == 1   # floor
    assert E.next_n(E.N_MAX, peb=1e9, rho_target=1.0) == E.N_MAX  # cap


def test_window_replay_rescales_frozen_observations():
    """Window mode replays E PEBs that were all observed at the frozen
    n: the walk rescales each to the current n and settles in the band
    instead of doubling E times on one stale reading."""
    frozen, peb, target = 1, 10.0, 1.0
    n = frozen
    for _ in range(8):                     # one 8-epoch window
        n = E.next_n_observed(n, peb, frozen, target)
    assert n == 8 and target / 2 <= peb * frozen / n <= 2 * target
    assert E.next_n_observed(4, 10.0, 4, 1.0) == E.next_n(4, 10.0, 1.0)


def test_control_loop_converges():
    """Simulate rho ~ V/(n^2 w): the loop reaches a fixed point with
    peb in [target/2, 2*target]."""
    v_over_w = 256.0
    n, target = 1, 1.0
    for _ in range(20):
        peb = v_over_w / n ** 2
        n2 = E.next_n(n, peb, target)
        if n2 == n:
            break
        n = n2
    assert target / 2 <= v_over_w / n ** 2 <= 2 * target
