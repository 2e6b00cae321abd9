"""Wire codec layer (DESIGN.md §8): packed payloads, reconcile, rounds.

Four contracts:

1. pack/unpack round-trips exactly at the edges — k=0 (empty support) and
   k=n (dense), r=1 and r=8, bf16 leaves, odd sizes that don't fill a
   uint32 word, and under ``vmap`` over a client axis — with the Pallas
   kernel (interpret mode) matching the jnp reference bit-for-bit;
2. ``decode(encode(tree))`` equals the transform's ``compress`` output and
   the returned ``BitsReport`` equals the transform's, for every supported
   compressor x scope;
3. measured payload bytes reconcile **in-graph** with the accounted bits:
   ``payload.nbytes == ceil(report.total_bits / 8)`` up to the documented
   word-padding slack (closed forms pinned below);
4. ``wire="packed"`` rounds match account-only rounds — params allclose,
   accounted bit metrics identical — for all four algorithms, and a
   deadline-dropped / policy-excluded client contributes a zero-length
   (fully masked) payload under ``semi_sync`` and ``async_buffered``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import (
    Compose, Identity, Int8Sync, QuantQr, TopK, wire)
from repro.compress.report import INDEX_BITS
from repro.core import aggregation, fed_data
from repro.core.baselines import FedAvg, FedConfig, FedDyn, Scaffold
from repro.core.clients import ClientProfile, ClientSchedule, mask_payload
from repro.core.fedcomloc import FedComLoc, FedComLocConfig
from repro.kernels import pack_codes as pack_kernel
from repro.kernels import ref as kref

jax.config.update("jax_platform_name", "cpu")


def tree_of(seed, shapes, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {f"p{i}": jax.random.normal(k, s).astype(dtype)
            for i, (k, s) in enumerate(zip(keys, shapes))}


# odd sizes that don't fill a uint32 word (33, 67), plus a word-aligned one
SHAPES = [(33,), (8, 8), (67,)]


# --------------------------------------------------------------------------- #
# 1. pack/unpack kernels
# --------------------------------------------------------------------------- #

class TestPackCodes:
    @pytest.mark.parametrize("n", [1, 31, 32, 33, 100, 1024, 1030])
    @pytest.mark.parametrize("b", [1, 2, 9, 17, 32])
    def test_roundtrip_and_kernel_parity(self, n, b):
        rng = np.random.default_rng(n * 37 + b)
        hi = 2 ** min(b, 31)
        codes = jnp.asarray(rng.integers(0, hi, n), jnp.uint32)
        words = kref.pack_codes(codes, b)
        assert words.shape == (-(-n // 32) * b,)
        np.testing.assert_array_equal(
            np.asarray(kref.unpack_codes(words, b, n)), np.asarray(codes))
        # Pallas kernel (interpret) is bit-identical to the reference
        np.testing.assert_array_equal(
            np.asarray(pack_kernel.pack_codes(codes, b, interpret=True)),
            np.asarray(words))
        np.testing.assert_array_equal(
            np.asarray(pack_kernel.unpack_codes(words, b, n,
                                                interpret=True)),
            np.asarray(codes))

    def test_vmap(self):
        rng = np.random.default_rng(0)
        codes = jnp.asarray(rng.integers(0, 32, (4, 45)), jnp.uint32)
        words = jax.vmap(lambda c: kref.pack_codes(c, 5))(codes)
        back = jax.vmap(lambda w: kref.unpack_codes(w, 5, 45))(words)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))

    def test_validation(self):
        with pytest.raises(ValueError):
            kref.pack_codes(jnp.zeros((4,), jnp.uint32), 33)
        with pytest.raises(ValueError):
            kref.unpack_codes(jnp.zeros((3,), jnp.uint32), 2, 100)


# --------------------------------------------------------------------------- #
# 2. encode/decode == transform, report identical
# --------------------------------------------------------------------------- #

CODECS = [
    ("identity", Identity(), False),
    ("topk", TopK(density=0.1), False),
    ("topk-k1", TopK(density=0.01), False),        # k = max(1, ...) floor
    ("topk-dense", TopK(density=1.0), False),      # k = n: dense payload
    ("topk-global", TopK(density=0.3, scope="global"), False),
    ("qr-r1", QuantQr(r=1), True),
    ("qr-r8", QuantQr(r=8), True),
    ("qr-global", QuantQr(r=4, scope="global"), True),
    ("compose", Compose(TopK(0.25), QuantQr(4)), True),
    ("compose-global", Compose(TopK(0.2, scope="global"),
                               QuantQr(3, scope="global")), True),
    ("compose-dense", Compose(TopK(1.0), QuantQr(4)), True),
    ("int8", Int8Sync(), True),
]


def assert_wire_matches_transform(comp, tree, rng, exact=True):
    out_t, rep_t = comp.compress(tree, rng)
    payload, rep_w = wire.encode(comp, tree, rng)
    dec = wire.decode(payload)
    for k in tree:
        if exact:
            np.testing.assert_array_equal(np.asarray(out_t[k]),
                                          np.asarray(dec[k]), err_msg=k)
        else:
            np.testing.assert_allclose(np.asarray(out_t[k]),
                                       np.asarray(dec[k]), err_msg=k)
    for f in ("value_bits", "index_bits", "meta_bits"):
        assert float(getattr(rep_t, f)) == float(getattr(rep_w, f)), f
    assert float(wire.padding_bits(payload, rep_w)) >= 0
    return payload, rep_w


def assert_tie_rule(comp, tree, rng):
    """Compose(TopK, QuantQr) wire vs transform where magnitude ties at
    the threshold overflow the capacity: the decode equals the transform on
    the lowest-index ``cap`` survivors and is zero past them, the bit
    reports agree (both count every tie), and the slack is the documented
    closed form, in which each tie past the capacity counts negative."""
    out_t, rep_t = comp.compress(tree, rng)
    payload, rep_w = wire.encode(comp, tree, rng)
    dec = wire.decode(payload)
    support, _ = comp.first.compress(tree)
    overflow = slots_free = 0
    for k in tree:
        sup = np.asarray(support[k]).reshape(-1) != 0
        cap = comp.first._k(sup.size)
        shipped = sup & (np.cumsum(sup) <= cap)
        want = np.where(shipped, np.asarray(out_t[k]).reshape(-1), 0)
        np.testing.assert_array_equal(
            want, np.asarray(dec[k]).reshape(-1), err_msg=k)
        overflow += max(0, int(sup.sum()) - cap)
        slots_free += cap - int(sup.sum())
    assert overflow > 0
    for f in ("value_bits", "index_bits", "meta_bits"):
        assert float(getattr(rep_t, f)) == float(getattr(rep_w, f)), f
    r = comp.second.r
    word_slack = sum(
        (-(-cap // 32) * 32 - cap) * (1 + r)
        for cap in payload.spec.caps)
    assert float(wire.padding_bits(payload, rep_w)) == (
        word_slack + slots_free * (INDEX_BITS + 1 + r))


class TestEncodeDecode:
    @pytest.mark.parametrize("name,comp,needs_rng", CODECS)
    @pytest.mark.parametrize("seed", range(3))
    def test_matches_transform(self, name, comp, needs_rng, seed):
        tree = tree_of(seed, SHAPES)
        rng = jax.random.PRNGKey(seed + 100) if needs_rng else None
        assert_wire_matches_transform(comp, tree, rng)

    @pytest.mark.parametrize("name,comp,needs_rng", [
        ("topk", TopK(density=0.2), False),
        ("qr-r4", QuantQr(r=4), True),
        ("compose", Compose(TopK(0.25), QuantQr(4)), True),
        ("int8", Int8Sync(), True),
    ])
    def test_bf16_leaves(self, name, comp, needs_rng):
        tree = tree_of(7, SHAPES, dtype=jnp.bfloat16)
        rng = jax.random.PRNGKey(9) if needs_rng else None
        if name == "compose":
            # bf16 keeps 8 mantissa bits, so leaf p0 (k = 8 of 33) holds an
            # exact magnitude tie at the TopK threshold (|x| = 1.1015625
            # twice): the transform keeps both tied entries, 9 survivors,
            # and the wire's static capacity ships the lowest-index 8 —
            # the §8 tie rule, pinned here in place of equality.
            assert_tie_rule(comp, tree, rng)
            return
        payload, rep = assert_wire_matches_transform(comp, tree, rng)
        if name == "topk":
            # bf16 values ship 16 bits each — both in the report and in the
            # packed value buffer (satellite: dtype-derived value bits)
            nnz = float(rep.index_bits) / 32
            assert float(rep.value_bits) == nnz * 16
            for idx, vals in payload.data:
                assert vals.dtype == jnp.bfloat16

    def test_empty_support(self):
        """k=0 edge: an all-zero tree (an EF innovation that vanished)
        packs to sentinel-only slots and decodes to zeros, with 0 bits
        accounted."""
        z = {k: jnp.zeros_like(v) for k, v in tree_of(0, SHAPES).items()}
        payload, rep = wire.encode(TopK(density=0.1), z)
        dec = wire.decode(payload)
        assert all(np.all(np.asarray(v) == 0) for v in dec.values())
        assert float(rep.total_bits) == 0
        # documented slack: every static slot is empty
        caps = payload.spec.caps
        assert float(wire.padding_bits(payload, rep)) == sum(
            c * (32 + 32) for c in caps)

    def test_vmap_client_axis(self):
        comp = Compose(TopK(0.25), QuantQr(4))
        tree = tree_of(3, SHAPES)
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.stack([x, 2 * x, -x, 0 * x]), tree)
        keys = jax.random.split(jax.random.PRNGKey(5), 4)
        payload, rep = jax.vmap(
            lambda t, k: wire.encode(comp, t, k))(stacked, keys)
        dec = jax.vmap(wire.decode)(payload)
        out_t, rep_t = jax.vmap(comp.compress)(stacked, keys)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(out_t[k]),
                                          np.asarray(dec[k]))
        np.testing.assert_array_equal(np.asarray(rep_t.total_bits),
                                      np.asarray(rep.total_bits))
        # spec (and so nbytes) stays per-client under vmap
        assert payload.nbytes == wire.payload_nbytes(comp, tree)

    def test_unbatched_unit_buffers_have_static_shapes(self):
        p, _ = wire.encode(TopK(density=0.1), tree_of(0, SHAPES))
        for (idx, vals), cap in zip(p.data, p.spec.caps):
            assert idx.shape == (cap,) and idx.dtype == jnp.uint32
            assert vals.shape == (cap,)


# --------------------------------------------------------------------------- #
# 3. in-graph reconcile: nbytes == ceil(total_bits / 8) + bounded slack
# --------------------------------------------------------------------------- #

class TestReconcile:
    @pytest.mark.parametrize("name,comp,needs_rng", CODECS)
    def test_in_graph_reconcile(self, name, comp, needs_rng):
        """Inside jit: measured bytes equal ceil(accounted bits / 8) plus
        the documented slack — 0 for dense/int8/full-support TopK (random
        continuous data fills every slot), and the exact word-padding
        closed form for packed-code units."""
        tree = tree_of(11, SHAPES)
        rng = jax.random.PRNGKey(12) if needs_rng else None

        @jax.jit
        def roundtrip(t, k):
            payload, rep = wire.encode(comp, t, k)
            return rep.total_bits, wire.padding_bits(payload, rep)

        total_bits, pad = roundtrip(tree, rng)
        measured = wire.payload_nbytes(comp, tree) * 8
        assert float(total_bits) + float(pad) == measured
        spec = jax.eval_shape(
            lambda t: wire.encode(comp, t, jax.random.PRNGKey(0))[0],
            tree).spec
        b = 1 + spec.r
        if spec.codec in ("dense", "topk", "int8"):
            expected_pad = 0.0          # full support, byte-granular
        elif spec.codec == "qr":        # word padding over each unit's size
            sizes = ([sum(int(np.prod(s)) for s in SHAPES)]
                     if spec.scope == "global"
                     else [int(np.prod(s)) for s in SHAPES])
            expected_pad = sum((32 * -(-n // 32) - n) * b for n in sizes)
        else:                           # topk_qr: padding over the cap slots
            expected_pad = sum((32 * -(-c // 32) - c) * b for c in spec.caps)
        assert float(pad) == expected_pad
        # the documented bound: < 32*b bits of word padding per unit
        n_units = 1 if spec.scope == "global" else len(SHAPES)
        assert float(pad) < 32 * b * n_units + 1


# --------------------------------------------------------------------------- #
# 4. wire rounds == account rounds
# --------------------------------------------------------------------------- #

def quadratic_setup(n_clients=6, d=10, seed=0):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(n_clients, d))
    b = rng.normal(size=(n_clients,))
    reps = 8
    x = np.repeat(A, reps, axis=0).astype(np.float32)
    y = np.repeat(b, reps).astype(np.float32)
    parts = [np.arange(i * reps, (i + 1) * reps) for i in range(n_clients)]
    return fed_data.from_numpy_partition(x, y, parts)


def sq_loss(params, xb, yb):
    pred = xb @ params["w"]
    return 0.5 * jnp.mean((pred - yb) ** 2)


N, D = 6, 10
DATA = quadratic_setup(N, D)
P0 = {"w": jnp.zeros((D,), jnp.float32)}
DROP_SCHED = ClientSchedule(
    profile=ClientProfile.lognormal(N, speed_sigma=1.0, seed=3),
    deadline=3.0, drop_stragglers=True)


def run_fedcomloc(wire_mode, comp, R=4, policy=None, schedule=None, **cfg_kw):
    cfg = FedComLocConfig(gamma=0.05, p=0.25, n_clients=N,
                          clients_per_round=4, batch_size=4,
                          variant="com", **cfg_kw)
    alg = FedComLoc(sq_loss, DATA, cfg, comp, schedule=schedule,
                    policy=policy, wire=wire_mode)
    state, metrics = alg.run_rounds(alg.init(P0), jax.random.PRNGKey(7), R)
    return np.asarray(state.x["w"]), metrics


def assert_round_equivalence(ma, mw, wa, ww):
    np.testing.assert_allclose(wa, ww, rtol=1e-6, atol=1e-7)
    for key in ("uplink_bits", "downlink_bits", "client_uplink_bits",
                "sim_time", "clients_aggregated"):
        np.testing.assert_array_equal(ma[key], mw[key], err_msg=key)
    pad = mw["uplink_payload_bytes"] * 8 - mw["uplink_bits"]
    assert (pad >= 0).all()
    return pad


class TestWireRounds:
    @pytest.mark.parametrize("comp,extra", [
        (TopK(density=0.3), {}),
        (QuantQr(r=6), {}),
        (Compose(TopK(0.3), QuantQr(4)), {}),
        (Int8Sync(), {}),
        (TopK(density=0.3), {"error_feedback": True}),
    ])
    def test_fedcomloc_matches_account(self, comp, extra):
        wa, ma = run_fedcomloc("account", comp, **extra)
        ww, mw = run_fedcomloc("packed", comp, **extra)
        pad = assert_round_equivalence(ma, mw, wa, ww)
        if isinstance(comp, (TopK, Int8Sync)):
            np.testing.assert_array_equal(pad, 0)   # byte-exact payloads

    @pytest.mark.parametrize("alg_cls", [FedAvg, Scaffold, FedDyn])
    def test_baselines_match_account(self, alg_cls):
        cfg = FedConfig(gamma=0.05, local_steps=4, n_clients=N,
                        clients_per_round=4, batch_size=4)
        outs = {}
        for mode in ("account", "packed"):
            if alg_cls is FedAvg:
                alg = alg_cls(sq_loss, DATA, cfg, TopK(0.25), wire=mode)
            else:
                alg = alg_cls(sq_loss, DATA, cfg, wire=mode)
            st, m = alg.run_rounds(alg.init(P0), jax.random.PRNGKey(5), 4)
            outs[mode] = (np.asarray(st.x["w"]), m)
        wa, ma = outs["account"]
        ww, mw = outs["packed"]
        assert_round_equivalence(ma, mw, wa, ww)
        if alg_cls is Scaffold:     # model + control variate, both dense
            np.testing.assert_array_equal(
                mw["uplink_payload_bytes"] * 8, mw["uplink_bits"])

    def test_meter_and_goldens_unchanged_in_account_mode(self):
        """Account mode is the constructor default and its graph/metrics
        carry no wire keys — golden traces stay valid byte-for-byte."""
        _, m = run_fedcomloc("account", TopK(density=0.3))
        assert "uplink_payload_bytes" not in m
        assert "client_payload_bytes" not in m


class TestStragglerPayloads:
    """Satellite: a deadline-dropped (or policy-excluded) client contributes
    a zero-length, fully masked payload — not packed zeros counted as
    transmitted — under both semi_sync and async_buffered."""

    @pytest.mark.parametrize("policy", [
        aggregation.AggregationPolicy.semi_sync(2),
        aggregation.AggregationPolicy.async_buffered(2, 1.0),
    ])
    def test_dropped_clients_send_nothing(self, policy):
        comp = TopK(density=0.3)
        wa, ma = run_fedcomloc("account", comp, policy=policy,
                               schedule=DROP_SCHED, R=6)
        ww, mw = run_fedcomloc("packed", comp, policy=policy,
                               schedule=DROP_SCHED, R=6)
        assert_round_equivalence(ma, mw, wa, ww)
        cpb = np.asarray(mw["client_payload_bytes"])
        cub = np.asarray(mw["client_uplink_bits"])
        # at this deadline the lognormal tail drops clients in some rounds
        assert (cub == 0).any(), "expected dropped clients in this setup"
        # zero accounted bits <-> zero measured bytes, per client per round
        np.testing.assert_array_equal(cpb == 0, cub == 0)
        # non-excluded clients all ship the same static packed size
        assert np.unique(cpb[cpb > 0]).size == 1

    def test_masked_payload_buffers_are_zero(self):
        """mask_payload zeroes every buffer of a non-participant, and the
        masked payload decodes to an all-zero tree."""
        comp = Compose(TopK(0.25), QuantQr(4))
        tree = tree_of(1, SHAPES)
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.stack([x, 2 * x, -x]), tree)
        keys = jax.random.split(jax.random.PRNGKey(2), 3)
        payload, _ = jax.vmap(
            lambda t, k: wire.encode(comp, t, k))(stacked, keys)
        partf = jnp.asarray([1.0, 0.0, 1.0])
        masked = mask_payload(payload, partf)
        for unit in masked.data:
            for buf in unit:
                assert np.all(np.asarray(buf)[1] == 0)      # dropped client
        dec = jax.vmap(wire.decode)(masked)
        keep = jax.vmap(wire.decode)(payload)
        for k, v in dec.items():
            assert np.all(np.asarray(v)[1] == 0)            # decodes to 0
            # participants' lanes are untouched by the masking
            np.testing.assert_array_equal(np.asarray(v)[[0, 2]],
                                          np.asarray(keep[k])[[0, 2]])


class TestValidation:
    def test_quantile_topk_rejected(self):
        with pytest.raises(ValueError, match="static capacity"):
            FedComLoc(sq_loss, DATA,
                      FedComLocConfig(n_clients=N, clients_per_round=4,
                                      variant="com"),
                      TopK(density=0.1, impl="quantile"), wire="packed")

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="wire must be"):
            FedComLoc(sq_loss, DATA,
                      FedComLocConfig(n_clients=N, clients_per_round=4),
                      wire="bytes")

    def test_per_client_overrides_rejected(self):
        sched = ClientSchedule(
            profile=ClientProfile.homogeneous(N).with_comp_param(
                "density", jnp.full((N,), 0.2)))
        with pytest.raises(ValueError, match="overrides"):
            FedComLoc(sq_loss, DATA,
                      FedComLocConfig(n_clients=N, clients_per_round=4,
                                      variant="com"),
                      TopK(density=0.2), schedule=sched, wire="packed")

    def test_unsupported_compose_rejected(self):
        with pytest.raises(ValueError, match="Compose"):
            wire.check_supported(Compose(QuantQr(4), TopK(0.2)))
        with pytest.raises(ValueError, match="matching scopes"):
            wire.check_supported(
                Compose(TopK(0.2, scope="global"), QuantQr(4)))

    def test_set_wire_rebinds_and_clears_caches(self):
        cfg = FedComLocConfig(gamma=0.05, p=0.25, n_clients=N,
                              clients_per_round=4, batch_size=4,
                              variant="com")
        alg = FedComLoc(sq_loss, DATA, cfg, TopK(0.3))
        st, m = alg.round(alg.init(P0), jax.random.PRNGKey(0))
        assert "uplink_payload_bytes" not in m
        assert alg.set_wire("packed") is alg
        _, m2 = alg.round(alg.init(P0), jax.random.PRNGKey(0))
        assert m2["uplink_payload_bytes"] > 0
        assert m2["uplink_bits"] == m["uplink_bits"]
        alg.set_wire("packed")          # rebind same mode: no-op


# --------------------------------------------------------------------------- #
# 5. packed uplink over a >1-shard client mesh (CI's 8-device leg)
# --------------------------------------------------------------------------- #

@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 devices for a sharded client mesh")
class TestShardedWire:
    @pytest.mark.parametrize("comp", [TopK(0.3), QuantQr(r=6)])
    def test_packed_uplink_multi_shard(self, comp):
        """The §8 contract on a real >1-shard mesh: the gathered packed
        buffers reproduce the single-device wire round — accounted bits
        AND measured payload bytes bit-identical, params allclose."""
        from repro.launch.mesh import make_client_mesh

        shards = 2
        ww, mw = run_fedcomloc("packed", comp)
        cfg = FedComLocConfig(gamma=0.05, p=0.25, n_clients=N,
                              clients_per_round=4, batch_size=4,
                              variant="com")
        alg = FedComLoc(sq_loss, DATA, cfg, comp, wire="packed")
        alg.use_mesh(make_client_mesh(shards))
        assert alg._mesh is not None
        st, ms = alg.run_rounds(alg.init(P0), jax.random.PRNGKey(7), 4)
        for key in ("uplink_bits", "uplink_payload_bytes",
                    "client_payload_bytes", "client_uplink_bits"):
            np.testing.assert_array_equal(mw[key], ms[key], err_msg=key)
        np.testing.assert_allclose(ww, np.asarray(st.x["w"]),
                                   rtol=1e-6, atol=1e-7)
