"""The ``store_blocks_per_pread`` reader on hand-built counter deltas:
block fetches over preads in the window, and nothing where the store
counts no preads (a program without the counter) or issued none."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402

READ = harness.reader(os.path.join(BENCH, "metrics"),
                      "store_blocks_per_pread")


def _ctx(store0, store1):
    return harness.Context(counters0={"store": store0},
                           counters1={"store": store1}, steps=4, chips=1)


def test_blocks_per_pread_on_a_hand_built_delta():
    value = READ(_ctx({"block_fetches": 1_000, "preads": 600},
                      {"block_fetches": 99_400, "preads": 40_000}))
    assert value == pytest.approx(98_400 / 39_400, rel=1e-12)


@pytest.mark.parametrize("store0, store1", [
    ({"block_fetches": 10}, {"block_fetches": 90}),       # no such counter
    ({"block_fetches": 10, "preads": 5},
     {"block_fetches": 10, "preads": 5}),                  # no reads
    ({}, {}),                                              # no store
])
def test_blocks_per_pread_finds_nothing(store0, store1):
    assert READ(_ctx(store0, store1)) is None
