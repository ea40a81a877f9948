"""jax-API helpers shared by src/ and benchmarks/."""

from __future__ import annotations


def xla_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict, empty where XLA gives none."""
    return dict(compiled.cost_analysis() or {})
