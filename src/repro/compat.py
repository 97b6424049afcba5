"""JAX version compatibility shim (single choke point).

``Compiled.cost_analysis()`` returns the metrics dict, or ``None`` where a
backend reports nothing; :func:`cost_analysis_dict` turns the latter into
an empty dict so callers can ``.get()`` a metric unconditionally.
"""
from __future__ import annotations

__all__ = ["cost_analysis_dict"]


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (empty when unreported)."""
    return compiled.cost_analysis() or {}
