"""FedComLoc (paper Algorithm 1) — Scaffnew + compression, three variants.

Faithful mapping of Algorithm 1:

* server pre-decides communication iterations via Bernoulli(p) coins; the
  run between two heads of the coin is one *local phase* whose length is
  Geometric(p) — we draw that length directly (``local_steps="geometric"``),
  or fix it to round(1/p) (``local_steps="fixed"``, the deterministic setting
  used for the headline experiments, matching the paper's "average of 10
  local iterations per round" with p = 0.1);
* line 7  (FedComLoc-Local):  g_i evaluated at C(x_i);
* line 8  (FedComLoc-Com):    uplink iterate compressed, x^_i <- C(x^_i);
* line 11 (FedComLoc-Global): averaged iterate compressed before broadcast;
* line 16: h_i <- h_i + (p/gamma)(x_{t+1} - x^_{i,t+1}) — only communication
  iterations change h_i (otherwise x_{t+1} = x^_{i,t+1});
* client sampling: S resampled at every communication round (the paper's
  experimental setting: 10 of 100 clients per global round).  Non-sampled
  clients keep their control variates; they re-enter from the current server
  model.  With full participation and C = Identity this is exactly Scaffnew.

Communication accounting is **in-graph** (repro.compress.BitsReport): every
round's metrics carry the exact uplink/downlink wire cost of the payloads
produced that round — per-client TopK nnz, per-tensor Q_r norms, and under
error feedback the bits of the *transmitted innovation*, not the dense
model.  Rounds run either one-jit-per-round (``round``) or fused R-per-jit
(``run_rounds``, inherited from :class:`repro.core.engine.RoundEngine`),
under any of the three aggregation policies (``sync`` / ``semi_sync(K)`` /
``async_buffered`` — repro.core.aggregation, DESIGN.md §7).

State layout: the server model ``x`` is stored once (all clients restart a
round from the broadcast model); control variates ``h`` are stacked with a
leading client axis.  All per-round compute is one jitted function.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.compress import Compressor, Identity, dense_bits
from repro.core import aggregation, comm
from repro.core.clients import (
    NULL_CTX, ClientAxisCtx, ClientSchedule, apply_downlink, keep_where,
    masked_mean, mean_over_active, payload_metrics, per_client, tree_where,
    validate_schedule, vmap_compress)
from repro.core.engine import RoundEngine
from repro.core.fed_data import FederatedData
from repro.core.spans import (
    ROUND_AGGREGATE, ROUND_DOWNLINK, ROUND_ENCODE, ROUND_LOCAL_PHASE,
    ROUND_SAMPLE, ROUND_STATE_GATHER, ROUND_STATE_UPDATE, span)

PyTree = Any
LossFn = Callable[[PyTree, jax.Array, jax.Array], jax.Array]

VARIANTS = ("none", "com", "local", "global")


class FedComLocState(NamedTuple):
    x: PyTree          # server model (broadcast value)
    h: PyTree          # control variates, stacked (n_clients, ...)
    round: jax.Array   # communication rounds completed
    e: PyTree = ()     # per-client error-feedback memory (beyond-paper)
    mom: PyTree = ()   # server momentum buffer (beyond-paper)
    y: PyTree = ()     # clients' last-received model (downlink != "dense")


@dataclasses.dataclass(frozen=True)
class FedComLocConfig:
    gamma: float = 0.1                 # local stepsize
    p: float = 0.1                     # communication probability
    n_clients: int = 100
    clients_per_round: int = 10
    batch_size: int = 32
    variant: str = "com"               # none | com | local | global
    local_steps: str = "fixed"         # fixed | geometric
    max_local_steps: Optional[int] = None  # cap (geometric); default 4/p
    # ---- beyond-paper extensions (EXPERIMENTS.md §Beyond) ---------------- #
    error_feedback: bool = False       # leaky delta-EF on the Com uplink
    ef_decay: float = 0.7              # EF memory leak (1.0 diverges here)
    server_momentum: float = 0.0       # Polyak momentum on the server mean

    def __post_init__(self):
        if self.variant not in VARIANTS:
            raise ValueError(f"variant must be one of {VARIANTS}")
        if not (0 < self.p <= 1):
            raise ValueError("p must be in (0, 1]")
        if self.n_clients <= 0:
            raise ValueError("n_clients must be positive")
        if not (0 < self.clients_per_round <= self.n_clients):
            # jax.random.choice(..., replace=False) fails opaquely (or
            # silently misbehaves) outside this range — reject up front.
            raise ValueError(
                f"clients_per_round must be in [1, n_clients]: got "
                f"{self.clients_per_round} with n_clients={self.n_clients}")
        if self.local_steps not in ("fixed", "geometric"):
            raise ValueError('local_steps must be "fixed" or "geometric"')
        if self.error_feedback and self.variant != "com":
            raise ValueError("error_feedback applies to the Com variant")
        if not (0.0 <= self.server_momentum < 1.0):
            raise ValueError("server_momentum must be in [0, 1)")

    @property
    def steps_cap(self) -> int:
        if self.max_local_steps is not None:
            return self.max_local_steps
        if self.local_steps == "fixed":
            return max(1, round(1.0 / self.p))
        return max(1, round(4.0 / self.p))


class FedComLoc(RoundEngine):
    """Algorithm 1.  ``variant="none"`` with Identity compression = Scaffnew."""

    def __init__(self, loss_fn: LossFn, data: FederatedData,
                 config: FedComLocConfig,
                 compressor: Compressor | None = None,
                 schedule: ClientSchedule | None = None,
                 policy: aggregation.AggregationPolicy | None = None,
                 wire: str = "account",
                 downlink: str = "dense",
                 downlink_compressor: Compressor | None = None,
                 store=None,
                 meter_mode: str = "host"):
        self.loss_fn = loss_fn
        self.data = data
        self.cfg = config
        self.policy = policy
        self.wire = wire
        self.downlink = downlink
        self.down_comp = downlink_compressor
        self.store = store
        self.comp = compressor if compressor is not None else Identity()
        if config.variant == "none" and not isinstance(self.comp, Identity):
            raise ValueError('variant="none" requires the Identity compressor')
        self.sched = validate_schedule(
            schedule if schedule is not None
            else ClientSchedule.homogeneous(config.n_clients),
            config.n_clients, self.comp)
        self.meter = comm.CommMeter(mode=meter_mode)
        self._setup_engine()

    # ------------------------------------------------------------------ #

    def _validate_downlink_combo(self) -> None:
        if self.downlink == "dense":
            return
        if self.cfg.variant == "global":
            raise ValueError(
                'variant="global" already compresses the broadcast its own '
                "way (line 11); combine the downlink seam with the other "
                "variants, or keep variant='global' with downlink='dense'")
        if self.cfg.server_momentum > 0:
            raise ValueError(
                "server_momentum extrapolates the broadcast point, which "
                "the delta-coded downlink reference cannot track stably; "
                "use downlink='dense' with momentum")

    def init(self, params0: PyTree) -> FedComLocState:
        # per-client state lives behind the §11 store contract: the
        # in-memory backend returns the familiar stacked arrays, the host
        # backend a version token (rows stay host-side)
        n = self.cfg.n_clients
        e = (self.store.init_slot("e", params0, n)
             if self.cfg.error_feedback else ())
        mom = (jax.tree_util.tree_map(jnp.zeros_like, params0)
               if self.cfg.server_momentum > 0 else ())
        y = params0 if self.downlink != "dense" else ()
        return FedComLocState(x=params0, h=self.store.init_slot(
                                  "h", params0, n),
                              round=jnp.zeros((), jnp.int32), e=e, mom=mom,
                              y=y)

    # ------------------------------------------------------------------ #

    def _num_local_steps(self, key: jax.Array) -> jax.Array:
        cap = self.cfg.steps_cap
        if self.cfg.local_steps == "fixed":
            return jnp.asarray(cap, jnp.int32)
        # Geometric(p) truncated at cap: #iterations until the coin lands 1.
        u = jax.random.uniform(key)
        g = jnp.floor(jnp.log1p(-u) / jnp.log1p(-self.cfg.p)).astype(jnp.int32) + 1
        return jnp.clip(g, 1, cap)

    @property
    def _round_key_fanout(self):
        # must mirror _round_impl's split below (§12 cohort planner)
        return 6 if self.downlink != "dense" else 5

    def _round_impl(self, state: FedComLocState, key: jax.Array,
                    ctx: ClientAxisCtx = NULL_CTX):
        cfg, sched = self.cfg, self.sched
        dl_on = self.downlink != "dense"
        if dl_on:
            # one extra key for the downlink codec; the dense-mode split
            # stays exactly 5-way so existing trajectories never move
            (k_sample, k_steps, k_local, k_up, k_down,
             k_dl) = jax.random.split(key, 6)
        else:
            k_sample, k_steps, k_local, k_up, k_down = jax.random.split(
                key, 5)
            k_dl = None
        s = cfg.clients_per_round
        s_loc = ctx.local_count(s)
        with span(ROUND_SAMPLE):
            # §11: availability-aware cohort sampling (the neutral path is
            # the historical uniform choice, same key consumption)
            clients_full, avail_full = sched.sample_cohort(
                k_sample, s, state.round)
            num_steps = self._num_local_steps(k_steps)
            # Client-heterogeneity layer (DESIGN.md §5): per-client step
            # counts (straggler deadline), participation mask, compressor
            # overrides.  The full (s,) plan is computed replicated
            # (metrics use it); the per-client compute below runs on this
            # shard's slice (§6).
            plan = sched.plan(clients_full, num_steps, available=avail_full)
            plan_l = ctx.shard_tree(plan)
            clients = ctx.shard(clients_full)
            partf_plan_full = plan.participating.astype(jnp.float32)
            ov_names = sched.comp_override_names
            ov_vals = [plan_l.comp_overrides[n] for n in ov_names]

        ef_on = cfg.variant == "com" and cfg.error_feedback
        with span(ROUND_STATE_GATHER):
            h_s = self.store.gather("h", state.h, clients)
            e_s = self.store.gather("e", state.e, clients) if ef_on else None
        # §10: with a delta-coded downlink the cohort restarts from the
        # model the clients actually HOLD (state.y — last-received), not
        # the server's exact iterate; every client-side anchor below
        # (local phase start, EF innovation, FedBuff delta) uses ref.
        ref = state.y if dl_on else state.x

        def local_step(carry, inp):
            x_i, loss_acc = carry
            step_idx, k_step = inp
            active = step_idx < plan_l.steps        # (s_loc,) per-client mask

            def one_client(x_c, h_c, client, kc, *ov):
                kb, kcomp = jax.random.split(kc)
                xb, yb = self.data.sample_batch(kb, client, cfg.batch_size)
                x_eval = (self.comp.apply(x_c, kcomp,
                                          **dict(zip(ov_names, ov)))
                          if cfg.variant == "local" else x_c)
                loss, g = jax.value_and_grad(self.loss_fn)(x_eval, xb, yb)
                x_new = jax.tree_util.tree_map(
                    lambda xc, gc, hc: xc - cfg.gamma * (gc - hc),
                    x_c, g, h_c)
                return x_new, loss

            # split the full (s,) key chain, then slice: client i sees the
            # same key at every device count
            keys = ctx.shard(jax.random.split(k_step, s))
            x_new, losses = jax.vmap(one_client)(x_i, h_s, clients, keys,
                                                 *ov_vals)
            x_i = jax.tree_util.tree_map(
                lambda new, old: jnp.where(per_client(active, new), new, old),
                x_new, x_i)
            loss_acc = loss_acc + mean_over_active(losses, active, ctx)
            return (x_i, loss_acc), None

        with span(ROUND_LOCAL_PHASE):
            x0 = jax.tree_util.tree_map(
                lambda p: jnp.broadcast_to(p, (s_loc,) + p.shape), ref)
            cap = cfg.steps_cap
            step_keys = jax.random.split(k_local, cap)
            (x_hat, loss_sum), _ = jax.lax.scan(
                local_step, (x0, jnp.zeros(())),
                (jnp.arange(cap), step_keys))

        # --- communication (theta_t = 1) --------------------------------- #
        # Exact wire accounting: the dense payload is 32 bits/scalar; the
        # compressed payloads report their own cost in-graph (BitsReport),
        # per client — a dropped straggler transmits nothing.
        dense = dense_bits(state.x)
        client_up = jnp.full((s_loc,), dense, jnp.float32)
        up_bits = jnp.asarray(s * dense)
        down_bits = jnp.asarray(s * dense)
        e_new = state.e
        innov = sent = payload = None
        wire_on = self.wire == "packed"
        with span(ROUND_ENCODE):
            if cfg.variant == "com":
                up_keys = ctx.shard(jax.random.split(k_up, s))
                if cfg.error_feedback:
                    # EF on the uplink *innovation*: transmit
                    # C(x^_i - x_prev + e_i); the server reconstructs
                    # x_prev + mean(sent).  Deltas after a local phase are
                    # small in magnitude, so TopK keeps far more of their
                    # energy than it keeps of the raw iterates; the
                    # residual stays in e_i.  The uplink bits are those of
                    # the transmitted innovation.
                    innov = jax.tree_util.tree_map(
                        lambda xh, x0_, e: xh - x0_[None] + e,
                        x_hat, ref, e_s)
                    if wire_on:
                        # decode happens once, server-side, after the
                        # gather — the client rows the h/e updates need
                        # are sliced back out of the full decoded stack
                        payload, up_rep = ctx.encode_payload(
                            self.comp, plan_l, innov, up_keys)
                    else:
                        sent, up_rep = vmap_compress(self.comp, plan_l,
                                                     innov, up_keys)
                        x_hat = jax.tree_util.tree_map(
                            lambda x0_, snt: x0_[None] + snt, ref, sent)
                elif wire_on:
                    # §8 packed uplink: the client boundary emits the wire
                    # payload; the round carries on with its (gathered)
                    # decode.
                    payload, up_rep = ctx.encode_payload(
                        self.comp, plan_l, x_hat, up_keys)
                else:
                    x_hat, up_rep = vmap_compress(self.comp, plan_l, x_hat,
                                                  up_keys)
                # (s_loc,): the vmap axis is on the report's leaves
                client_up = up_rep.total_bits
                up_bits = None                 # recomputed from client_up
            elif wire_on:
                # uncompressed-uplink variants still move a real (dense)
                # buffer
                payload, _ = ctx.encode_payload(None, plan_l, x_hat)

        # --- aggregation policy (DESIGN.md §7) --------------------------- #
        # The full (s,) bits each plan-participant would transmit feed the
        # finish-time clock; the policy outcome (participation, staleness,
        # weights, sim_time) is computed replicated, so it is bit-identical
        # at every §6 device count.
        with span(ROUND_AGGREGATE):
            pol = aggregation.resolve_policy(
                self.policy, sched, plan,
                ctx.all_clients(client_up) * partf_plan_full, ctx)
            out, part, may_exclude = pol.out, pol.part, pol.may_exclude
            client_up = pol.client_up         # excluded clients send nothing
            if up_bits is None or may_exclude:
                up_bits = client_up.sum()
            if wire_on:
                # §8 packed uplink: the only cross-shard traffic is the
                # masked packed-payload gather; decode happens ONCE,
                # server-side, on the full (s,) stack — the client rows the
                # h/e updates need are sliced back out of it (an excluded
                # client's masked zero row never lands in state: the §5/§7
                # keep-old guards below are gated on the same participation
                # mask).
                dec_full = ctx.gather_decoded_payload(payload, out.partf)
                if ef_on:
                    sent = ctx.shard_tree(dec_full)
                    srv_hat = jax.tree_util.tree_map(
                        lambda x0_, sf: x0_[None] + sf, ref, dec_full)
                    x_hat = ctx.shard_tree(srv_hat)
                else:
                    # non-com variants ship the raw iterate: decode is the
                    # identity and the local x_hat already equals its rows
                    srv_hat = dec_full
                    if cfg.variant == "com":
                        x_hat = ctx.shard_tree(srv_hat)
            delta_combine = aggregation.uses_delta_combine(self.policy)
            if wire_on:
                # server aggregation from the decoded full stack, with the
                # unsharded formula (bit-identical at any device count)
                if delta_combine:
                    delta = jax.tree_util.tree_map(
                        lambda xh, x0_: xh - x0_[None], srv_hat, ref)
                    x_bar = jax.tree_util.tree_map(
                        lambda x0_, u: x0_ + u, state.x,
                        aggregation.async_weighted_sum(out, delta, NULL_CTX))
                elif may_exclude:
                    x_bar = tree_where(
                        out.n_selected > 0,
                        masked_mean(srv_hat, out.weight, NULL_CTX,
                                    weight_sum=out.n_selected),
                        state.x)
                else:
                    x_bar = jax.tree_util.tree_map(
                        lambda t: t.mean(axis=0), srv_hat)
            elif delta_combine:
                # FedBuff server application in delta form: each buffer
                # flush applies its staleness-discounted mean of anchor
                # deltas
                delta = jax.tree_util.tree_map(
                    lambda xh, x0_: xh - x0_[None], x_hat, ref)
                x_bar = jax.tree_util.tree_map(
                    lambda x0_, u: x0_ + u, state.x,
                    aggregation.async_weighted_sum(out, delta, ctx))
            elif may_exclude:
                # if every sampled client was excluded, the server keeps
                # its model
                x_bar = tree_where(out.n_selected > 0,
                                   masked_mean(x_hat, pol.weight, ctx,
                                               weight_sum=out.n_selected),
                                   state.x)
            else:
                x_bar = ctx.mean_clients(x_hat)

        # §10 downlink seam: delta-code the new broadcast against the
        # cohort's reference, once; clients decode under the mesh (this
        # body IS the shard_map/GSPMD region) and adopt y_new.
        y_new = state.y
        dl_extras = {}
        with span(ROUND_DOWNLINK):
            if cfg.variant == "global":
                x_bar, down_rep = self.comp.compress(x_bar, k_down)
                down_bits = down_rep.total_bits * s
            if dl_on:
                y_new, down_bits, dl_extras = apply_downlink(
                    self.downlink, self.down_comp, ctx, state.y, x_bar, k_dl,
                    s)
        bcast = y_new if dl_on else x_bar

        with span(ROUND_STATE_UPDATE):
            if ef_on:
                # leaky memory: undecayed EF diverges inside Scaffnew (the
                # residual integrates against the control variates — see
                # the EXPERIMENTS.md §Beyond decay study); 0.7 is the sweet
                # spot.
                e_s_new = jax.tree_util.tree_map(
                    lambda c, snt: cfg.ef_decay * (c - snt), innov, sent)
                if may_exclude:    # an excluded client never transmitted
                    e_s_new = keep_where(part, e_s_new, e_s)
                e_new = self.store.scatter("e", state.e, clients, e_s_new,
                                           ctx)
            # line 16: h_i += (p/gamma) (x_{t+1} - x^_{i,t+1}) for i in S —
            # x_{t+1} is the value clients ADOPT (the decoded y under a
            # compressed downlink) and the pre-momentum mean otherwise: the
            # extrapolation below must not leak into the control variates
            # (it destabilises them; see tests).
            h_s_new = jax.tree_util.tree_map(
                lambda h, xh, xb_: h + (cfg.p / cfg.gamma) * (xb_[None] - xh),
                h_s, x_hat, bcast)
            if may_exclude:   # an excluded client keeps its control variate
                h_s_new = keep_where(part, h_s_new, h_s)
            h_new = self.store.scatter("h", state.h, clients, h_s_new, ctx)

        # beyond-paper: Polyak momentum on the broadcast point only
        mom_new = state.mom
        if cfg.server_momentum > 0:
            with span(ROUND_AGGREGATE):
                delta = jax.tree_util.tree_map(
                    lambda xb_, x0_: xb_ - x0_, x_bar, state.x)
                mom_new = jax.tree_util.tree_map(
                    lambda m, d_: cfg.server_momentum * m
                    + (1 - cfg.server_momentum) * d_, state.mom, delta)
                x_bar = jax.tree_util.tree_map(
                    lambda x0_, m: x0_ + m, state.x, mom_new)

        metrics = {
            "train_loss": loss_sum / jnp.maximum(plan.steps.max(), 1),
            "num_local_steps": num_steps,
            "uplink_bits": up_bits,
            "downlink_bits": down_bits,
            "client_steps": plan.steps,           # (s,) per-client schedule
            "client_uplink_bits": client_up,      # (s,) exact per-client wire
            "client_finish": out.finish,          # (s,) sim-clock arrivals
            "sim_time": out.sim_time,
            **aggregation.policy_metrics(out),
        }
        if wire_on:
            # measured packed bytes (§8): the static payload size, masked
            # in-graph by participation — a dropped client transmits a
            # zero-length payload, not a buffer of zeros counted as sent
            metrics.update(payload_metrics(payload, out.partf))
        metrics.update(dl_extras)
        return (FedComLocState(x=x_bar, h=h_new, round=state.round + 1,
                               e=e_new, mom=mom_new, y=y_new), metrics)
