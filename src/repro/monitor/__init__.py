"""Monitoring (paper §3.6): internal state dashboards + DAG visualization,
and the program's tracer (``repro.monitor.trace``).

The dashboard renderers import the workflow layer, so they load on first
use: the model code imports the tracer without the orchestrator."""
from __future__ import annotations

_DASHBOARD = ("render_dashboard", "workflow_graph_dot")


def __getattr__(name: str):
    if name in _DASHBOARD:
        from repro.monitor import dashboard

        return getattr(dashboard, name)
    raise AttributeError(f"module 'repro.monitor' has no attribute {name!r}")
