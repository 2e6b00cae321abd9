"""Downlink codec path (DESIGN.md §10): broadcast compression + LoCoDL.

Five contracts, mirroring tests/test_wire.py for the reverse direction:

1. mode validation — a non-dense downlink requires a compressor; packed
   additionally requires a wire-supported one; FedComLoc's Global variant
   and server momentum (which extrapolate past the value clients adopt)
   are rejected with a compressed downlink;
2. ``downlink="account"`` and ``downlink="packed"`` are bit-identical on
   one device — params exactly equal, ``downlink_bits`` exactly equal —
   for every algorithm, because decode(encode(delta)) IS the transform
   output (the §8 wire contract applied to the broadcast);
3. measured broadcast bytes reconcile in-graph with the accounted bits:
   ``downlink_payload_bytes * 8 - downlink_bits == s * padding`` with the
   same closed-form word-padding slack TestReconcile pins per codec;
4. LoCoDL: collapses to Scaffnew's cohort mean under Identity/lam=1/sync;
   its unconditional key chain keeps the sampling/uplink trajectory
   identical across downlink modes; fused rounds == one-jit-per-round;
   excluded stragglers keep their pre-round iterate and control variate;
5. the broadcast decodes under real meshes: a >1-shard client mesh
   reproduces the single-device packed-downlink round, and
   ``ModelShardCtx.encode_broadcast``/``decode_broadcast`` on a composed
   clients x model mesh match the unsharded wire bit-for-bit (the §9
   shard-local layout, one buffer per model shard).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import Compose, Identity, Int8Sync, QuantQr, TopK, wire
from repro.core import aggregation, fed_data
from repro.core.aggregation import AggregationPolicy
from repro.core.baselines import FedAvg, FedConfig, FedDyn, Scaffold
from repro.core.clients import ClientProfile, ClientSchedule
from repro.core.fedcomloc import FedComLoc, FedComLocConfig
from repro.core.locodl import LoCoDL, LoCoDLConfig
from repro.launch.mesh import make_client_mesh

jax.config.update("jax_platform_name", "cpu")

N_DEV = len(jax.devices())
N, D, S, R = 6, 10, 4, 3


def quadratic_setup(seed=0):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(N, D))
    b = rng.normal(size=(N,))
    reps = 8
    x = np.repeat(A, reps, axis=0).astype(np.float32)
    y = np.repeat(b, reps).astype(np.float32)
    parts = [np.arange(i * reps, (i + 1) * reps) for i in range(N)]
    return fed_data.from_numpy_partition(x, y, parts)


def sq_loss(params, xb, yb):
    return 0.5 * jnp.mean((xb @ params["w"] - yb) ** 2)


DATA = quadratic_setup()
P0 = {"w": jnp.zeros((D,), jnp.float32)}
DROP_SCHED = ClientSchedule(
    profile=ClientProfile.lognormal(N, speed_sigma=1.0, seed=3),
    deadline=3.0, drop_stragglers=True)

# (name, downlink compressor) — every wire-supported codec family x scope
DOWN_CODECS = [
    ("identity", Identity()),
    ("topk", TopK(density=0.3)),
    ("topk-global", TopK(density=0.3, scope="global")),
    ("qr-r4", QuantQr(r=4)),
    ("qr-global", QuantQr(r=4, scope="global")),
    ("compose", Compose(TopK(0.3), QuantQr(4))),
    ("int8", Int8Sync()),
]


def build(alg_name, downlink="dense", down_comp=None, policy=None,
          schedule=None, **kw):
    if alg_name == "fedcomloc":
        cfg = FedComLocConfig(gamma=0.05, p=0.25, n_clients=N,
                              clients_per_round=S, batch_size=4,
                              variant="com")
        return FedComLoc(sq_loss, DATA, cfg, TopK(0.5), schedule=schedule,
                         policy=policy, downlink=downlink,
                         downlink_compressor=down_comp, **kw)
    if alg_name == "locodl":
        cfg = LoCoDLConfig(gamma=0.05, p=0.25, lam=0.5, n_clients=N,
                           clients_per_round=S, batch_size=4)
        return LoCoDL(sq_loss, DATA, cfg, TopK(0.5), schedule=schedule,
                      policy=policy, downlink=downlink,
                      downlink_compressor=down_comp, **kw)
    cfg = FedConfig(gamma=0.05, local_steps=4, n_clients=N,
                    clients_per_round=S, batch_size=4)
    cls = {"fedavg": FedAvg, "scaffold": Scaffold, "feddyn": FedDyn}[alg_name]
    ckw = {"compressor": TopK(0.5)} if alg_name == "fedavg" else {}
    return cls(sq_loss, DATA, cfg, schedule=schedule, policy=policy,
               downlink=downlink, downlink_compressor=down_comp,
               **ckw, **kw)


ALGS = ("fedcomloc", "locodl", "fedavg", "scaffold", "feddyn")


def run(alg):
    state, metrics = alg.run_rounds(alg.init(P0), jax.random.PRNGKey(7), R)
    return np.asarray(state.x["w"]), metrics


# --------------------------------------------------------------------------- #
# 1. validation
# --------------------------------------------------------------------------- #

class TestValidation:
    def test_non_dense_requires_compressor(self):
        with pytest.raises(ValueError, match="compressor"):
            build("fedavg", downlink="account")

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="downlink"):
            build("fedavg", downlink="sparse", down_comp=TopK(0.5))

    def test_packed_requires_wire_supported(self):
        class Opaque:
            def compress(self, tree, key=None):
                return tree, None

        with pytest.raises((ValueError, TypeError)):
            build("fedavg", downlink="packed", down_comp=Opaque())

    def test_fedcomloc_global_variant_rejected(self):
        cfg = FedComLocConfig(gamma=0.05, p=0.25, n_clients=N,
                              clients_per_round=S, batch_size=4,
                              variant="global")
        with pytest.raises(ValueError, match="lobal"):
            FedComLoc(sq_loss, DATA, cfg, TopK(0.5), downlink="account",
                      downlink_compressor=TopK(0.5))

    def test_fedcomloc_momentum_rejected(self):
        cfg = FedComLocConfig(gamma=0.05, p=0.25, n_clients=N,
                              clients_per_round=S, batch_size=4,
                              variant="com", server_momentum=0.5)
        with pytest.raises(ValueError, match="momentum"):
            FedComLoc(sq_loss, DATA, cfg, TopK(0.5), downlink="account",
                      downlink_compressor=TopK(0.5))

    def test_set_downlink_rebinds(self):
        alg = build("fedavg")
        alg.set_downlink("account", TopK(0.5))
        assert alg.downlink == "account"
        w1, m1 = run(alg)
        w2, m2 = run(build("fedavg", downlink="account",
                           down_comp=TopK(0.5)))
        np.testing.assert_array_equal(w1, w2)
        np.testing.assert_array_equal(m1["downlink_bits"],
                                      m2["downlink_bits"])

    def test_locodl_lam_validated(self):
        with pytest.raises(ValueError, match="lam"):
            LoCoDLConfig(lam=0.0)
        with pytest.raises(ValueError, match="lam"):
            LoCoDLConfig(lam=1.5)


# --------------------------------------------------------------------------- #
# 2. account == packed, bit-identical, every algorithm
# --------------------------------------------------------------------------- #

class TestAccountPackedParity:
    @pytest.mark.parametrize("alg_name", ALGS)
    def test_bit_identical(self, alg_name):
        wa, ma = run(build(alg_name, downlink="account",
                           down_comp=QuantQr(r=4)))
        wp, mp = run(build(alg_name, downlink="packed",
                           down_comp=QuantQr(r=4)))
        np.testing.assert_array_equal(wa, wp)
        for k in ("downlink_bits", "uplink_bits", "client_uplink_bits"):
            np.testing.assert_array_equal(ma[k], mp[k], err_msg=k)
        assert "downlink_payload_bytes" not in ma
        pad = mp["downlink_payload_bytes"] * 8 - mp["downlink_bits"]
        assert (pad >= 0).all()

    @pytest.mark.parametrize("alg_name", ALGS)
    def test_compressed_downlink_cheaper_than_dense(self, alg_name):
        _, md = run(build(alg_name))
        _, mc = run(build(alg_name, downlink="account",
                          down_comp=QuantQr(r=4)))
        assert float(np.sum(mc["downlink_bits"])) < \
            float(np.sum(md["downlink_bits"]))

    def test_dense_metrics_carry_no_payload_keys(self):
        _, m = run(build("fedcomloc"))
        assert "downlink_payload_bytes" not in m


# --------------------------------------------------------------------------- #
# 3. in-graph reconcile: closed-form padding per codec, scaled by cohort
# --------------------------------------------------------------------------- #

def expected_pad_bits(comp, tree):
    """Word-padding slack of one broadcast payload, from the wire spec —
    the same closed forms TestReconcile pins (uplink direction)."""
    spec = jax.eval_shape(
        lambda t: wire.encode(comp, t, jax.random.PRNGKey(0))[0],
        tree).spec
    shapes = [np.asarray(leaf).shape if hasattr(leaf, "shape")
              else leaf.shape
              for leaf in jax.tree_util.tree_leaves(tree)]
    b = 1 + spec.r
    if spec.codec in ("dense", "topk", "int8"):
        return 0.0
    if spec.codec == "qr":
        sizes = ([sum(int(np.prod(s)) for s in shapes)]
                 if spec.scope == "global"
                 else [int(np.prod(s)) for s in shapes])
        return float(sum((32 * -(-n // 32) - n) * b for n in sizes))
    return float(sum((32 * -(-c // 32) - c) * b for c in spec.caps))


class TestDownlinkReconcile:
    @pytest.mark.parametrize("name,comp", DOWN_CODECS)
    @pytest.mark.parametrize("alg_name", ("fedcomloc", "locodl"))
    def test_bytes_reconcile_with_bits(self, alg_name, name, comp):
        """packed broadcast: bytes*8 - bits == s * closed-form padding,
        every round.  TopK deltas are dense-support here (continuous
        random data never produces exact zeros), so topk slack is 0."""
        _, m = run(build(alg_name, downlink="packed", down_comp=comp))
        slack = np.asarray(m["downlink_payload_bytes"]) * 8 \
            - np.asarray(m["downlink_bits"])
        np.testing.assert_allclose(slack, S * expected_pad_bits(comp, P0))

    def test_downlink_meter_accumulates_payload(self):
        alg = build("fedavg", downlink="packed", down_comp=QuantQr(r=4))
        _, m = run(alg)
        assert alg.meter.downlink_bits == pytest.approx(
            float(np.sum(m["downlink_bits"])))


# --------------------------------------------------------------------------- #
# 4. LoCoDL semantics
# --------------------------------------------------------------------------- #

class TestLoCoDL:
    def test_collapses_to_scaffnew_mean(self):
        """Identity links + lam=1 + full participation: the communication
        step IS Scaffnew's averaging — every client lands on y, and y is
        the cohort mean of the local iterates."""
        cfg = LoCoDLConfig(gamma=0.05, p=0.25, lam=1.0, n_clients=N,
                           clients_per_round=N, batch_size=4)
        alg = LoCoDL(sq_loss, DATA, cfg, Identity())
        st, _ = alg.round(alg.init(P0), jax.random.PRNGKey(3))
        np.testing.assert_allclose(np.asarray(st.xs["w"]),
                                   np.asarray(st.x["w"])[None].repeat(N, 0),
                                   rtol=1e-6, atol=1e-7)

    def test_uplink_chain_invariant_across_downlink_modes(self):
        """One unconditional key split: switching the downlink codec moves
        the broadcast, never the sampling/local/uplink randomness."""
        runs = {dl: run(build("locodl", downlink=dl,
                              down_comp=None if dl == "dense"
                              else QuantQr(r=4)))
                for dl in ("dense", "account", "packed")}
        for dl in ("account", "packed"):
            np.testing.assert_array_equal(
                runs["dense"][1]["client_uplink_bits"],
                runs[dl][1]["client_uplink_bits"])
            np.testing.assert_array_equal(
                runs["dense"][1]["client_steps"],
                runs[dl][1]["client_steps"])

    def test_dense_equals_identity_account(self):
        """C_dn = Identity under "account" is a no-op on values: the
        trajectory equals dense mode exactly (same key chain), only the
        accounting path differs — and Identity accounts dense bits."""
        wd, md = run(build("locodl"))
        wi, mi = run(build("locodl", downlink="account",
                           down_comp=Identity()))
        np.testing.assert_array_equal(wd, wi)
        np.testing.assert_array_equal(md["downlink_bits"],
                                      mi["downlink_bits"])

    def test_fused_matches_per_round(self):
        for policy, sched in ((None, None),
                              (AggregationPolicy.semi_sync(2), DROP_SCHED),
                              (AggregationPolicy.async_buffered(2, 0.5),
                               DROP_SCHED)):
            alg = build("locodl", downlink="packed", down_comp=QuantQr(4),
                        policy=policy, schedule=sched)
            st_f, _ = alg.run_rounds(alg.init(P0), jax.random.PRNGKey(7), R)
            st_l, key = alg.init(P0), jax.random.PRNGKey(7)
            for _ in range(R):
                key, sub = jax.random.split(key)
                st_l, _ = alg.round(st_l, sub)
            np.testing.assert_allclose(np.asarray(st_f.x["w"]),
                                       np.asarray(st_l.x["w"]),
                                       rtol=1e-6, atol=1e-7)

    def test_excluded_clients_keep_state(self):
        """semi_sync(1) with a straggler schedule: an excluded client's
        iterate and control variate rows are exactly its pre-round rows."""
        alg = build("locodl", policy=AggregationPolicy.semi_sync(1),
                    schedule=DROP_SCHED)
        st0 = alg.init(P0)
        st1, m = alg.round(st0, jax.random.PRNGKey(11))
        agg = float(np.asarray(m["clients_aggregated"]))
        assert agg <= S
        # only aggregated clients may move: excluded + never-sampled rows
        # stay exactly at their pre-round values (x AND h)
        changed_x = np.any(
            np.asarray(st1.xs["w"]) != np.asarray(st0.xs["w"]), axis=1)
        changed_h = np.any(
            np.asarray(st1.h["w"]) != np.asarray(st0.h["w"]), axis=1)
        assert changed_x.sum() <= agg
        assert changed_h.sum() <= agg

    def test_loss_decreases(self):
        # last-5 / first-5 mean loss ratio after 30 rounds.  One key is a
        # noisy witness: over keys 0-9 the ratio spans 0.27-0.82 (mean
        # 0.43) under jax 0.9's random stream, and a single-key "halves"
        # bound flips with the stream.  So: every key must decrease the
        # loss, and the mean over keys 0-3 (0.35 today) must halve it.
        ratios = []
        for seed in range(4):
            alg = build("locodl", downlink="account", down_comp=QuantQr(r=8))
            st, key = alg.init(P0), jax.random.PRNGKey(seed)
            losses = []
            for _ in range(30):
                key, sub = jax.random.split(key)
                st, m = alg.round(st, sub)
                losses.append(float(m["train_loss"]))
            ratios.append(np.mean(losses[-5:]) / np.mean(losses[:5]))
        assert max(ratios) < 0.9, ratios
        assert np.mean(ratios) < 0.5, ratios


# --------------------------------------------------------------------------- #
# 5. meshes: >1-shard client decode + model-sharded broadcast (§9)
# --------------------------------------------------------------------------- #

@pytest.mark.skipif(N_DEV < 2, reason="needs >= 2 devices")
class TestShardedDownlink:
    @pytest.mark.parametrize("alg_name", ("fedcomloc", "locodl"))
    def test_client_mesh_matches_single_device(self, alg_name):
        w1, m1 = run(build(alg_name, downlink="packed",
                           down_comp=QuantQr(r=4)))
        alg = build(alg_name, downlink="packed", down_comp=QuantQr(r=4))
        alg.use_mesh(make_client_mesh(2))
        ws, ms = run(alg)
        np.testing.assert_allclose(w1, ws, rtol=1e-6, atol=1e-7)
        for k in ("downlink_bits", "downlink_payload_bytes"):
            np.testing.assert_array_equal(m1[k], ms[k], err_msg=k)

    @pytest.mark.parametrize("comp", [TopK(0.3), QuantQr(r=4), Identity()],
                             ids=["topk", "qr", "dense"])
    def test_model_sharded_broadcast_roundtrip(self, comp):
        """ModelShardCtx.encode_broadcast/decode_broadcast on a composed
        clients x model mesh: shard-local buffers, bit-identical to the
        unsharded wire (tie-free leaves force identical TopK support).
        qr dither keys are shard-folded (the documented §9 contract), so
        its VALUES compare by quantization-error magnitude while the bit
        accounting still matches exactly."""
        from repro.core.distributed import ModelShardCtx

        rng = np.random.default_rng(5)
        shapes = {"embed": {"embedding": (64, 16)},
                  "mlp": {"wi": {"kernel": (16, 96)}},
                  "q": {"bias": (40,)},
                  "norm": {"scale": (33,)}}

        def leaf(shape):
            n = int(np.prod(shape))
            mags = rng.permutation(n).astype(np.float32) + 1.0
            signs = rng.choice(np.asarray([-1.0, 1.0], np.float32), n)
            return jnp.asarray((signs * mags).reshape(shape))

        tree = jax.tree_util.tree_map(
            leaf, shapes, is_leaf=lambda x: isinstance(x, tuple))
        ctx = ModelShardCtx(make_client_mesh(1, model=2))
        key = jax.random.PRNGKey(2)
        payload, rep = ctx.encode_broadcast(comp, tree, key)
        dec = ctx.decode_broadcast(payload)
        ref_payload, ref_rep = wire.encode(comp, tree, key)
        ref = wire.decode(ref_payload)
        qr_dither = isinstance(comp, QuantQr)
        for (kp, x), a, b in zip(
                jax.tree_util.tree_leaves_with_path(tree),
                jax.tree_util.tree_leaves(ref),
                jax.tree_util.tree_leaves(dec)):
            if qr_dither:
                e_ref = float(jnp.linalg.norm(x - a))
                e_got = float(jnp.linalg.norm(x - b))
                assert e_got <= 1.5 * e_ref + 1e-6, \
                    (jax.tree_util.keystr(kp), e_ref, e_got)
            else:
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=jax.tree_util.keystr(kp))
        for f in ("value_bits", "index_bits", "meta_bits"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref_rep, f)),
                np.asarray(getattr(rep, f)), err_msg=f)
