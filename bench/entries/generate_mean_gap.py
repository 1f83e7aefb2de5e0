"""The ``generate`` entry (``entries/generate.py``) with one more check,
``logit_gap_mean``: the mean amount by which a compared token's
float32-reference logit lies below the reference's best, over the same
sampled requests as ``logit_gap``.

It is for models whose widest gap cannot tell the program's precision
from the control's. With many routed experts the top choices often lie
within a bfloat16 rounding of each other. A flipped choice moves that
token's hidden state, and over a few hundred compared tokens the widest
gap reaches what the float8 control reaches. The mean counts how far all
compared tokens lie below the best: a few flips move it little, and a
lower precision moves it much. A mix that names this entry holds the same
requests as one that names ``generate``.
"""

from __future__ import annotations

import numpy as np


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best at its position."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(chosen)), chosen]


class _Kept:
    """The architecture module, keeping the float32 reference's logits
    that the ``generate`` entry computes for its own check."""

    def __init__(self, module):
        self.module = module
        self.logits = None

    def __getattr__(self, name):
        return getattr(self.module, name)

    def logits_at(self, *args, **kwargs):
        self.logits = self.module.logits_at(*args, **kwargs)
        return self.logits


class _Spec:
    """The harness's ``Spec``, handing out the module as a ``_Kept``."""

    def __init__(self, spec):
        self.spec = spec
        self.kept = None

    def __getattr__(self, name):
        return getattr(self.spec, name)

    def reference(self, name):
        self.kept = _Kept(self.spec.reference(name))
        return self.kept


def run(spec, cell: dict, seed: int, seconds: float, trace: bool,
        workdir, t_start: float):
    view = _Spec(spec)
    out = spec.entry("generate").run(view, cell, seed, seconds, trace,
                                     workdir, t_start)
    out.arch = view.kept.module
    # the float32 reference's logits at the compared tokens, kept for the
    # control (``control_checks``)
    out.check_logits = view.kept.logits
    value = (float(gaps(out.check_logits, out.check_batch[2]).mean())
             if out.check_logits is not None else float("inf"))
    out.checks["logit_gap_mean"] = {
        "value": value,
        "limit": spec.limits(cell["name"])["logit_gap_mean"]}
    return out


def control_checks(run, seed: int) -> dict:
    """The run's checks with the control in the program's place: the
    reference computed with float8 operands chooses each compared token,
    and both gaps are read against the float32 reference's logits that
    the run kept."""
    tokens, rows, _served = run.check_batch
    chosen = run.arch.logits_at(run.model, seed, tokens, rows,
                                "fp8").argmax(axis=-1)
    d = gaps(run.check_logits, chosen)
    return {**run.checks,
            "logit_gap": {**run.checks["logit_gap"],
                          "value": float(d.max())},
            "logit_gap_mean": {**run.checks["logit_gap_mean"],
                               "value": float(d.mean())}}
