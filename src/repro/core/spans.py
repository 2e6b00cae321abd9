"""Stable names for the layers of a round, as a profiler trace shows them.

Device spans (``ROUND_*``) are ``jax.named_scope``s inside
``FedComLoc._round_impl``: they only write op metadata, so the compiled
program and every number it computes stay the same, and a device trace
(``jax.profiler.trace``) files each operation under the span it ran in.
Host spans (``ENGINE_*``) are ``jax.profiler.TraceAnnotation``s around
the steps of ``RoundEngine.run_rounds`` and ``round``; with the profiler
off they cost nothing measurable.  The wire kernels name their own
``pallas_call``s (``repro.kernels``), so the threshold walk and the
compaction show apart inside ``round.encode``.
"""

from __future__ import annotations

import jax

ROUND_SAMPLE = "round.sample"            # cohort, step counts, client plan
ROUND_STATE_GATHER = "round.state_gather"  # cohort rows of h (and e)
ROUND_LOCAL_PHASE = "round.local_phase"  # the masked local-step scan
ROUND_ENCODE = "round.encode"            # the uplink's compression
ROUND_AGGREGATE = "round.aggregate"      # policy, decode, server mean
ROUND_DOWNLINK = "round.downlink"        # the delta-coded broadcast
ROUND_STATE_UPDATE = "round.state_update"  # h (and e) updates, scatter

ROUND_SPANS = (ROUND_SAMPLE, ROUND_STATE_GATHER, ROUND_LOCAL_PHASE,
               ROUND_ENCODE, ROUND_AGGREGATE, ROUND_DOWNLINK,
               ROUND_STATE_UPDATE)

ENGINE_PLAN_COHORTS = "engine.plan_cohorts"  # host replay of the cohorts
ENGINE_DISPATCH = "engine.dispatch"      # the call into the jitted rounds
ENGINE_FETCH_METRICS = "engine.fetch_metrics"  # waits for the device

ENGINE_SPANS = (ENGINE_PLAN_COHORTS, ENGINE_DISPATCH, ENGINE_FETCH_METRICS)


def span(name: str):
    """A device span: ``jax.named_scope(name)``."""
    return jax.named_scope(name)


def host_span(name: str):
    """A host span: ``jax.profiler.TraceAnnotation(name)``."""
    return jax.profiler.TraceAnnotation(name)
