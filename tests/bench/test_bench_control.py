"""The control fails the comparison that decides ``correct``: the
reference at the precision below the configuration's, put in the
program's place on the prompts and tokens the program served, reads a
widest gap above the limit, while the program's own reading stays under it.
At the small CPU size of `tiny`; the readings at the cells' own size are
`bench/control.py`'s, on the chip."""
import pytest

from bench import harness

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["float-decode", "fused-decode"])
@pytest.mark.parametrize("seed", [2, 4])
def test_control_is_not_correct(root, cell, seed):
    spec = harness.resolve_cell(harness.load_benchmark(root), cell, root)
    config, mix, limits = spec["config"], spec["mix"], spec["limits"]
    ref = spec["reference"]
    sched = harness.build(config, mix, seed)
    harness.warm(sched, mix, seed)
    calls = harness.window(sched, mix, seed, 0.0)["calls"]
    del sched
    program = harness.check(ref, config, limits, seed, calls,
                            mix["slot_tokens"])
    control = harness.check(ref, config, limits, seed, calls,
                            mix["slot_tokens"], harness.control_lower(config))
    assert harness.passed(program)
    assert not harness.passed(control)
    name = tiny.LIMITS[cell][0]
    assert control[name]["value"] > 3 * program[name]["value"]


def test_swapped_weights_serve_as_a_fresh_build(root):
    """`bench/control.py` reads many seeds in one process by putting each
    seed's weights into one scheduler: it serves the same tokens as a
    scheduler built for that seed."""
    from bench import control, traffic

    spec = harness.resolve_cell(harness.load_benchmark(root),
                                "fused-decode", root)
    config, mix = spec["config"], spec["mix"]
    reqs = harness.program_requests(
        traffic.call(mix, 9, 0, config["vocab_size"]))
    fresh = harness.build(config, mix, 9).serve(reqs)
    sched = harness.build(config, mix, 8)
    control.swap_weights(sched, 9)
    assert sched.serve(reqs) == fresh
