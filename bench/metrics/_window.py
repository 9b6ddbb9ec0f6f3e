"""Shared by the readers: the window steps that fall in the trace."""
from __future__ import annotations


def traced_steps(rec):
    """Window steps dispatched and read back inside the traced window."""
    lo, hi = rec.trace_span
    return [s for s in rec.loop.steps if s["t0"] >= lo and s["t_ready"] <= hi]


STEP_MODULE = "jit_step"      # serve.make_decode_step's jitted function
