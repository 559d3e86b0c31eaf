"""A whole run with the timed path broken underneath comes out not
correct, once for each fault a serving cell on one chip can have
(`bench.faults`): a token altered where it is produced, and a decode step
that returns the K/V pool unchanged."""
import time

import pytest

from bench import faults, harness

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["float-decode", "fused-decode"])
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_path_is_not_correct(root, monkeypatch, cell, fault):
    faults.FAULTS[fault](monkeypatch.setattr)
    r = harness.run(cell, 3, 0.0, False, time.perf_counter(), root=root,
                    require_tpu=False, log=lambda m: None)
    assert not r["correct"]
    assert r["failed"] == 0          # whole answers, wrong tokens
    gap = r["check"][tiny.LIMITS[cell][0]]
    assert gap["value"] > gap["limit"]
