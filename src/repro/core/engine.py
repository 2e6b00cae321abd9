"""Round drivers shared by every FL algorithm (DESIGN.md §3.4).

Algorithms define one jit-able ``_round_impl(state, key) -> (state, metrics)``
where ``metrics`` is a flat dict of jnp values — scalars plus fixed-shape
per-client vectors (``client_steps`` / ``client_uplink_bits``, DESIGN.md
§5) — that **includes** ``uplink_bits`` / ``downlink_bits`` computed
in-graph from the payloads actually produced that round, and ``sim_time``
(the straggler-aware simulated round wall-clock).  :class:`RoundEngine`
then provides the two execution modes:

* ``round(state, key)`` — one jitted call per round, metrics pulled to host
  each round (interactive / debugging path);
* ``run_rounds(state, key, num_rounds)`` — the fused engine: ``lax.scan``
  over whole communication rounds inside ONE jit, in-graph bit/metric
  accumulation, a single host round-trip per chunk.  Bit-identical to
  calling ``round`` R times: the key chain inside the scan is exactly the
  host loop's ``key, sub = jax.random.split(key)``.

Both record into ``self.meter`` (a :class:`repro.core.comm.CommMeter`), so
histories and bits-axes are identical whichever driver ran.  Each driver
opens the host spans of ``repro.core.spans`` around its steps (cohort
planning, dispatch, the metrics fetch that waits for the device), so a
profiler trace names the host's part of every idle gap.

``set_policy`` binds one of the three aggregation policies (DESIGN.md §7:
``sync`` / ``semi_sync(K)`` / ``async_buffered``); the round
implementations read ``self.policy`` at trace time, so both drivers — and
the ``shard_map`` mesh path — run the same policy-resolved graph.
``set_wire`` binds the §8 wire mode the same way: ``"account"`` moves
dense trees and only the ``BitsReport`` ledger claims compression;
``"packed"`` makes the uplink move real packed payloads
(``repro.compress.wire``) and adds measured ``uplink_payload_bytes`` /
``client_payload_bytes`` metrics that must reconcile with the accounted
bits in-graph.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.core.spans import (
    ENGINE_DISPATCH, ENGINE_FETCH_METRICS, ENGINE_PLAN_COHORTS, host_span)

PyTree = Any

WIRE_MODES = ("account", "packed")

DOWNLINK_MODES = ("dense", "account", "packed")


def validate_downlink(downlink: Optional[str], compressor) -> str:
    """Resolve + check a downlink mode (DESIGN.md §10) at construction time.

    ``"dense"`` (default) keeps today's semantics: the broadcast is the
    raw fp32 model and ``downlink_bits`` accounts it at full width.
    ``"account"`` and ``"packed"`` both delta-code the broadcast against
    the clients' last-received reference through a *downlink* compressor:
    account mode applies the transform (dense buffers move, the
    ``BitsReport`` ledger claims the compression), packed mode moves the
    real packed broadcast payload (``repro.compress.wire``) and must
    reconcile measured bytes against accounted bits in-graph.  Packed
    needs a wire-codec-supported compressor; both need *a* compressor
    (pass ``Identity()`` for an explicit dense-codec downlink).
    """
    downlink = "dense" if downlink is None else downlink
    if downlink not in DOWNLINK_MODES:
        raise ValueError(
            f"downlink must be one of {DOWNLINK_MODES}, got {downlink!r}")
    if downlink != "dense":
        if compressor is None:
            raise ValueError(
                f'downlink="{downlink}" needs a downlink compressor '
                "(downlink_compressor=...; Identity() for the dense codec)")
        if downlink == "packed":
            from repro.compress import wire as wire_mod
            wire_mod.check_supported(compressor)
    return downlink


def validate_wire(wire: Optional[str], compressor, schedule) -> str:
    """Resolve + check a wire mode (DESIGN.md §8) at construction time.

    ``"account"`` (default) keeps today's semantics: dense trees move,
    only the ``BitsReport`` ledger claims compression.  ``"packed"``
    requires a compressor the wire layer can pack
    (``repro.compress.wire.check_supported``) and a schedule without
    per-client compressor overrides — overrides change payload *shapes*,
    which static packed buffers cannot carry.
    """
    wire = "account" if wire is None else wire
    if wire not in WIRE_MODES:
        raise ValueError(f"wire must be one of {WIRE_MODES}, got {wire!r}")
    if wire == "packed":
        from repro.compress import wire as wire_mod
        wire_mod.check_supported(compressor)
        if schedule is not None and schedule.profile.comp_params:
            raise ValueError(
                "packed wire mode cannot carry per-client compressor "
                f"overrides {sorted(schedule.profile.comp_params)} (static "
                "payload capacity); run per-client overrides in account "
                "mode")
    return wire


class RoundEngine:
    """Mixin: host-stepped ``round`` + fused ``run_rounds`` over _round_impl."""

    def _setup_engine(self) -> None:
        from repro.core import aggregation, client_store
        self.policy = aggregation.validate_policy(
            getattr(self, "policy", None), self.cfg.clients_per_round)
        self.store = client_store.resolve_store(getattr(self, "store", None))
        self.wire = validate_wire(getattr(self, "wire", None),
                                  getattr(self, "comp", None),
                                  getattr(self, "sched", None))
        self.down_comp = getattr(self, "down_comp", None)
        self.downlink = validate_downlink(getattr(self, "downlink", None),
                                          self.down_comp)
        self._validate_downlink_combo()
        self._mesh = None
        self._mesh_axis = "clients"
        self._fused_cache: Dict[int, Any] = {}
        self._rebind_impl()

    # ------------------------------------------------------------------ #

    def _rebind_impl(self) -> None:
        """(Re)derive ``self._impl`` and clear the jit caches.

        Always wraps the round in a *fresh* function object: pjit's trace
        cache keys on the wrapped callable, and the ``self._round_impl``
        bound method compares equal across accesses — re-jitting it
        directly after a ``set_policy``/``set_wire`` rebind can silently
        reuse a graph traced under the previous binding.
        """
        from repro.core import distributed
        if self._mesh is None:
            impl = lambda state, key: self._round_impl(state, key)
        else:
            impl = distributed.shard_round(
                self._round_impl, self._mesh, self.cfg.clients_per_round,
                self._mesh_axis)
        self._impl = impl
        self._round = jax.jit(impl)
        self._fused_cache = {}

    # ------------------------------------------------------------------ #

    def set_wire(self, wire: str) -> "RoundEngine":
        """Bind a wire mode (DESIGN.md §8) — ``"account"`` | ``"packed"``.

        ``_round_impl`` reads ``self.wire`` at trace time, so switching
        modes clears the jit caches (like ``set_policy``); rebinding the
        mode already bound is a no-op.  Returns ``self``.
        """
        wire = validate_wire(wire, getattr(self, "comp", None),
                             getattr(self, "sched", None))
        if wire == self.wire:
            return self
        self.wire = wire
        self._rebind_impl()
        return self

    # ------------------------------------------------------------------ #

    def _validate_downlink_combo(self) -> None:
        """Algorithm-specific downlink compatibility hook (no-op here);
        overridden where a mode combination is ill-defined (e.g. FedComLoc
        variant="global" already compresses the broadcast its own way)."""

    def set_downlink(self, downlink: str,
                     compressor=None) -> "RoundEngine":
        """Bind a downlink mode (DESIGN.md §10) —
        ``"dense"`` | ``"account"`` | ``"packed"``.

        ``compressor`` replaces the bound downlink compressor when given
        (required if none was bound at construction and the mode needs
        one).  The downlink reference state ``y`` lives in the algorithm
        state, so this must be called **before** ``init`` — states built
        under a different mode have a different structure.  Returns
        ``self``.
        """
        comp = compressor if compressor is not None else self.down_comp
        downlink = validate_downlink(downlink, comp)
        if downlink == self.downlink and comp is self.down_comp:
            return self
        self.downlink = downlink
        self.down_comp = comp
        self._validate_downlink_combo()
        self._rebind_impl()
        return self

    # ------------------------------------------------------------------ #

    def set_policy(self, policy) -> "RoundEngine":
        """Bind an aggregation policy (DESIGN.md §7) — ``None`` = sync.

        ``_round_impl`` reads ``self.policy`` at trace time, so rebinding
        to a *different* policy clears the jit caches (like ``use_mesh``);
        rebinding the policy already bound is a no-op.  Returns ``self``.
        """
        from repro.core import aggregation
        policy = aggregation.validate_policy(
            policy, self.cfg.clients_per_round)
        if policy == self.policy:
            return self
        self.policy = policy
        self._rebind_impl()
        return self

    # ------------------------------------------------------------------ #

    def use_mesh(self, mesh: Optional["jax.sharding.Mesh"],
                 axis: str = "clients"):
        """Bind (or, with ``None``, unbind) a client-axis mesh.

        With a mesh bound, both drivers run ``_round_impl`` under
        ``shard_map`` with the sampled-client axis split across the mesh's
        ``axis`` devices (DESIGN.md §6) — same trajectory contract as the
        fused engine: metric scalars bit-identical, params allclose.
        Rebinding to a *different* mesh clears the jit caches; rebinding
        the mesh already bound is a no-op (so drivers may pass ``mesh=``
        on every call without triggering recompiles).  Returns ``self``
        for chaining.
        """
        if (mesh is self._mesh
                or (mesh is not None and self._mesh is not None
                    and mesh == self._mesh)):
            return self
        if mesh is not None and self.store.host_side:
            # an ordered host callback cannot run inside the shard_map
            # round body — the §11 HostStore is a single-process backend
            raise ValueError(
                "host-side client stores (HostStore) cannot run under a "
                "client-axis mesh; use the in-memory store with meshes, or "
                "drop the mesh for out-of-core populations")
        if (mesh is not None and getattr(getattr(self, "sched", None),
                                         "uses_host_sampler", False)):
            # same restriction for the §12 host-side cohort sampler
            raise ValueError(
                "host-side cohort sampling (sampler='tree') cannot run "
                "under a client-axis mesh; use sampler='gumbel' with "
                "meshes")
        self._mesh = mesh
        self._mesh_axis = axis
        self._rebind_impl()
        return self

    # ------------------------------------------------------------------ #

    #: per-round key fanout: ``_round_impl`` draws its sampling key as
    #: ``jax.random.split(key, fanout)[0]``.  Algorithms override this
    #: (it depends on the bound downlink mode) so the §12 cohort planner
    #: can replay the key chain host-side; ``None`` disables planning.
    _round_key_fanout: Optional[int] = None

    def _plan_cohorts(self, state, key: jax.Array, num_rounds: int,
                      stepped: bool = False):
        """Replay the upcoming rounds' sampling-key chain host-side and
        hand the cohort schedule to a prefetching :class:`HostStore`.

        The fused scan derives round r's key as r applications of
        ``key, sub = jax.random.split(key)`` and its sampling key as
        ``split(sub, fanout)[0]`` — all deterministic before the scan
        launches.  Tree-sampler schedules draw each cohort in O(s log n)
        (memoised, so the in-graph callback reuses the exact arrays);
        neutral schedules replay the uniform ``jax.random.choice``
        eagerly.  Gumbel schedules are not replayed (that would be the
        O(n) work §12 removes) — the store then runs write-behind only.
        The plan is a performance hint: a misprediction costs a prefetch
        miss, never a wrong row (see ``client_store`` hazard rules).
        """
        store, sched = self.store, getattr(self, "sched", None)
        if (not getattr(store, "prefetch", False) or self._mesh is not None
                or sched is None or self._round_key_fanout is None):
            return
        if sched.availability is not None and not sched.uses_host_sampler:
            return
        s = self.cfg.clients_per_round
        t0 = int(state.round)
        cohorts = []
        for r in range(num_rounds):
            if stepped:
                sub = key           # round() receives the round key itself
            else:
                key, sub = jax.random.split(key)
            k_sample = jax.random.split(sub, self._round_key_fanout)[0]
            if sched.uses_host_sampler:
                clients, _ = sched.plan_cohort_host(k_sample, s, t0 + r)
            else:
                clients = np.asarray(jax.random.choice(
                    k_sample, sched.n_clients, (s,), replace=False))
            cohorts.append(clients)
        store.submit_cohort_plan(cohorts)

    # ------------------------------------------------------------------ #

    def round(self, state, key: jax.Array) -> Tuple[Any, Dict[str, Any]]:
        """Run one communication round; returns (state, metrics dict).

        Scalar metrics come back as python floats; per-client vector
        metrics (e.g. ``client_uplink_bits``, DESIGN.md §5) as numpy
        arrays.
        """
        with host_span(ENGINE_PLAN_COHORTS):
            self._plan_cohorts(state, key, 1, stepped=True)
        with host_span(ENGINE_DISPATCH):
            state, metrics = self._round(state, key)
        with host_span(ENGINE_FETCH_METRICS):
            out = {k: (np.asarray(v) if getattr(v, "ndim", 0) else float(v))
                   for k, v in metrics.items()}
            self.meter.record_round(
                uplink_bits=out.get("uplink_bits", 0.0),
                downlink_bits=out.get("downlink_bits", 0.0))
        return state, out

    # ------------------------------------------------------------------ #

    def _fused(self, num_rounds: int):
        fn = self._fused_cache.get(num_rounds)
        if fn is None:
            def run(state, key):
                def body(carry, _):
                    state, key = carry
                    key, sub = jax.random.split(key)
                    state, metrics = self._impl(state, sub)
                    return (state, key), metrics

                (state, _), metrics = jax.lax.scan(
                    body, (state, key), None, length=num_rounds)
                return state, metrics

            fn = jax.jit(run)
            self._fused_cache[num_rounds] = fn
        return fn

    def run_rounds(self, state, key: jax.Array, num_rounds: int
                   ) -> Tuple[Any, Dict[str, np.ndarray]]:
        """Run ``num_rounds`` communication rounds in ONE jit call.

        Returns ``(state, metrics)`` with each metric stacked over a leading
        ``(num_rounds,)`` axis (per-round values; ``uplink_bits`` /
        ``downlink_bits`` are the exact per-round wire costs, per-client
        vector metrics stack to ``(num_rounds, s)``).  The caller's
        key-advance convention is
        the host loop's: after this call, advance your key by
        ``num_rounds`` ``jax.random.split`` steps to stay on the same chain.
        """
        num_rounds = int(num_rounds)
        if num_rounds <= 0:
            raise ValueError("num_rounds must be positive")
        with host_span(ENGINE_PLAN_COHORTS):
            self._plan_cohorts(state, key, num_rounds)
        with host_span(ENGINE_DISPATCH):
            state, metrics = self._fused(num_rounds)(state, key)
        with host_span(ENGINE_FETCH_METRICS):
            self.meter.record_rounds(
                uplink_bits=metrics.get("uplink_bits"),
                downlink_bits=metrics.get("downlink_bits"),
                num_rounds=num_rounds)
            metrics = {k: np.asarray(v) for k, v in metrics.items()}
        return state, metrics
