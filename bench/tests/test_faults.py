"""A run with the timed path broken underneath must come out not
correct. The harness's look for a chip is skipped; everything else runs
as on the chip, at the program's CPU size."""

from __future__ import annotations

import pytest

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("faults"))


@pytest.mark.parametrize("cell", ["t.tiny.tinyzipf", "t.tiny.tinyqa"])
def test_sound_run_is_correct(root, cell):
    result = tiny.run(root, cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("cell", ["t.tiny.tinyzipf", "t.tiny.tinyqa"])
def test_token_altered_where_it_is_produced(root, cell, monkeypatch):
    """Every decode step's tokens go through the scheduler's host copy;
    one altered token must fail the comparison with the reference."""
    from repro.serving import serve

    real = serve.BatchScheduler.step

    def step(self):
        done = real(self)
        for req in done:
            if len(req.generated) > 2:
                req.generated[2] = (req.generated[2] + 1) % \
                    self.bundle.cfg.vocab_size
        return done

    monkeypatch.setattr(serve.BatchScheduler, "step", step)
    result = tiny.run(root, cell)
    assert not result["correct"]
    assert result["checks"]["logit_gap"]["value"] > \
        result["checks"]["logit_gap"]["limit"]


def test_cache_hit_with_other_tokens(root, monkeypatch):
    """A hit whose clone differs from what its miss stored fails."""
    from repro.core import process

    real = process.Process._maybe_use_cache

    def use_cache(self):
        code = real(self)
        if code is not None and "tokens" in self.outputs:
            import numpy as np

            from repro.core.datatypes import ArrayData
            bad = np.asarray(self.outputs["tokens"].value)[::-1].copy()
            self.outputs["tokens"] = ArrayData(bad)
        return code

    monkeypatch.setattr(process.Process, "_maybe_use_cache", use_cache)
    result = tiny.run(root, "t.tiny.tinyzipf")
    assert not result["correct"]
    assert result["checks"]["hit_differs"]["value"] > 0
