"""Analytic roofline layer: hardware constants + per-step cost analysis.

``repro.roofline.hw`` pins the accelerator/host capacity constants;
``repro.roofline.analysis`` derives compute/memory/collective roofline
terms from the analytic cost model (``analytic_roofline``), counts the
collectives of a compiled program (``parse_collectives``) and derives the
Synergy-style host-resource demand of a training configuration
(``analytic_host_profile``) — the source of the bridge families' host
rows in ``repro.bridge.profiles.derive_host``.
"""

from repro.roofline.analysis import (  # noqa: F401
    CollectiveStats,
    Roofline,
    analytic_host_profile,
    parse_collectives,
)
