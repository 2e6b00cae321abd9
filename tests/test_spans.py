"""The round's named spans (``repro.core.spans``) reach the program, and
change nothing in it.

The device spans are ``jax.named_scope``s: they must appear in the
lowered round's name stacks (``as_text(debug_info=True)``) and leave the
program itself, printed without debug info, exactly as it was.  The host
spans wrap the engine's steps in both drivers.  The golden traces
(``test_golden.py``) pin the numbers the scoped rounds compute.
"""

import contextlib

import jax
import pytest

from repro.compress import TopK
from repro.core import engine, fedcomloc, spans
from repro.core.fedcomloc import FedComLoc, FedComLocConfig
from test_wire import DATA, N, P0, sq_loss

jax.config.update("jax_platform_name", "cpu")

BASE = (spans.ROUND_SAMPLE, spans.ROUND_STATE_GATHER,
        spans.ROUND_LOCAL_PHASE, spans.ROUND_ENCODE, spans.ROUND_AGGREGATE,
        spans.ROUND_STATE_UPDATE)
SETUPS = {
    "packed_topk": (dict(), dict(), BASE),
    "packed_topk_ef_downlink": (
        dict(error_feedback=True),
        dict(downlink="packed", downlink_compressor=TopK(0.5)),
        BASE + (spans.ROUND_DOWNLINK,)),
}


def _alg(cfg_kw, alg_kw):
    cfg = FedComLocConfig(gamma=0.05, p=0.25, n_clients=N,
                          clients_per_round=4, batch_size=4, variant="com",
                          **cfg_kw)
    return FedComLoc(sq_loss, DATA, cfg, TopK(0.3), wire="packed", **alg_kw)


def _lowered(alg):
    return alg._fused(2).lower(alg.init(P0), jax.random.PRNGKey(7))


@pytest.mark.parametrize("setup", sorted(SETUPS))
def test_round_spans_reach_the_program(setup):
    cfg_kw, alg_kw, expected = SETUPS[setup]
    text = _lowered(_alg(cfg_kw, alg_kw)).as_text(debug_info=True)
    for name in expected:
        assert f"{name}/" in text, name
    absent = set(spans.ROUND_SPANS) - set(expected)
    assert not any(f"{name}/" in text for name in absent)


@pytest.mark.parametrize("setup", sorted(SETUPS))
def test_round_spans_leave_the_program_unchanged(setup, monkeypatch):
    cfg_kw, alg_kw, _ = SETUPS[setup]
    scoped = _lowered(_alg(cfg_kw, alg_kw)).as_text()
    monkeypatch.setattr(fedcomloc, "span",
                        lambda name: contextlib.nullcontext())
    plain = _lowered(_alg(cfg_kw, alg_kw)).as_text()
    assert scoped == plain


@pytest.mark.parametrize("driver", ["run_rounds", "round"])
def test_engine_host_spans_wrap_each_step(driver, monkeypatch):
    opened = []

    @contextlib.contextmanager
    def record(name):
        opened.append(name)
        yield

    monkeypatch.setattr(engine, "host_span", record)
    alg = _alg({}, {})
    state = alg.init(P0)
    if driver == "run_rounds":
        alg.run_rounds(state, jax.random.PRNGKey(7), 2)
    else:
        alg.round(state, jax.random.PRNGKey(7))
    assert opened == list(spans.ENGINE_SPANS)
