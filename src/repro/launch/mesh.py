"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
forces 512 host devices while tests/benches must see 1.
"""

from __future__ import annotations

from repro.configs.devices import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods of
    256 as (pod=2, data=16, model=16) — 'pod' is the DCN-connected axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# TPU v5e hardware model used by the roofline analysis (per chip).
HW = {
    "peak_bf16_flops": 197e12,     # FLOP/s
    "hbm_bandwidth": 819e9,        # B/s
    "ici_link_bandwidth": 50e9,    # B/s per link
    "hbm_bytes": 16 * 1024**3,
    "dcn_bandwidth": 6.25e9,       # B/s per host (cross-pod axis)
}
