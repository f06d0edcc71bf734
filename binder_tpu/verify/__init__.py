"""Serving-plane verification: incremental invariant checking +
mutation-to-glass propagation tracing (ISSUE 16).

``Verifier`` (checker.py) re-verifies only what each mutation can
affect, off the same invalidation feed the zone drain takes, with a
sampled time-budgeted background audit for drift the delta feed cannot
see.  ``PropagationTracer`` (tracer.py) stamps each mutation with a
trace context at the store event and folds per-stage latencies into
``binder_propagation_seconds``.
"""
from binder_tpu.verify.checker import INVARIANTS, Verifier
from binder_tpu.verify.tracer import STAGES, PropagationTracer

__all__ = ["INVARIANTS", "STAGES", "PropagationTracer", "Verifier"]
