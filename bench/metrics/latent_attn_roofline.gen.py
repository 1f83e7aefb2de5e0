"""Latent decode kernel's share of its roofline (byte-bound): the dense
kernel's reader (``readers.decode_attn_roofline``) over a view of the
trace that holds only the ops named ``latent_decode_attention``, so the
grouped matmul's custom calls inside the same decode steps are not
counted. The useful work is the active requests' latent-cache reads in
every layer, as the architecture module counts them
(``decode_attention_work``). Reads nothing where no such kernel ran."""

from __future__ import annotations

import copy
import dataclasses

from benchlib import readers

KERNEL = "latent_decode_attention"


def read(run):
    if run.trace is None:
        return None
    dev = run.trace.devices[0]
    ops = [op for op in dev.ops if op.name.lstrip("%").startswith(KERNEL)]
    view = copy.copy(run)
    view.trace = dataclasses.replace(
        run.trace, devices=[dataclasses.replace(dev, ops=ops)])
    return readers.decode_attn_roofline(view)
