"""The control of the logit-gap check, at the program's CPU size: the
reference computed with float8 operands, put in the program's place,
reads far above the program on the same requests and comes out not
correct by the harness's own comparison, where the program is correct."""

from __future__ import annotations

import pytest

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("control"),
                          gap_limit=0.1)


@pytest.mark.parametrize("seed", [3, 2**31 + 7, 977])
def test_control_fails_where_the_program_passes(root, seed):
    import control

    r = control.readings("t.tiny.tinyqa", seed, 2.0, require_chip=False,
                         root=root)
    limit = r["checks"]["logit_gap"]["limit"]
    assert r["program_gap"] <= limit, r
    assert r["control_gap"] > limit, r
    assert r["control_gap"] >= 3 * r["program_gap"], r
    assert r["program_correct"] and not r["control_correct"], r
