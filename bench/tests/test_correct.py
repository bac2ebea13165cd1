"""The correctness comparison: the reduced runs agree with the reference,
and a run with its timed path broken underneath, or the reference in a
lower precision in the program's place, comes out not correct."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct
from bench.reference import asgd
from bench.reference import llama
from bench.tests import cells


def _unchanged(monkeypatch):
    import repro.launch.train as T

    real = T.setup

    def setup(*a, **k):
        run = real(*a, **k)
        step = run.step_fn

        def frozen(params, *rest):
            return (params, *step(params, *rest)[1:])
        run.step_fn = frozen
        return run
    monkeypatch.setattr(T, "setup", setup)


def _half_batch(monkeypatch):
    from repro.models import model as M

    real = M.loss_fn

    def loss_fn(cfg, params, batch, **k):
        half = batch["tokens"].shape[-1] // 2
        return real(cfg, params, {"tokens": batch["tokens"][..., :half]}, **k)
    monkeypatch.setattr(M, "loss_fn", loss_fn)


def _no_exchange(monkeypatch):
    import repro.core.gossip as G

    real = G.initiate_exchange_packed

    def initiate(*a, **k):
        sent, scales, idx = real(*a, **k)
        return sent * 0, scales * 0, idx
    monkeypatch.setattr(G, "initiate_exchange_packed", initiate)


def _wrong_peer(monkeypatch):
    import jax.numpy as jnp

    import repro.core.gossip as G

    real = G.initiate_exchange_packed

    def initiate(*a, **k):
        sent, scales, idx = real(*a, **k)
        return jnp.roll(sent, 1, axis=0), jnp.roll(scales, 1, axis=0), idx
    monkeypatch.setattr(G, "initiate_exchange_packed", initiate)


def _token_altered(monkeypatch):
    import repro.launch.train as T

    real = T.setup

    def setup(*a, **k):
        run = real(*a, **k)
        nxt = run.next_wbatch

        def altered():
            b = nxt()
            return {**b, "tokens": b["tokens"].at[0, 0, 5].add(1)}
        run.next_wbatch = altered
        return run
    monkeypatch.setattr(T, "setup", setup)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _no_exchange,
                                   _token_altered, _wrong_peer])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    result = cells.run(cells.reduced_cell("smollm-w4-int8"), seed=5,
                       monkeypatch=monkeypatch)
    assert not result["correct"], result["checks"]


REDUCED_ST = asgd.Settings(workers=4, batch=1, seq=cells.SEQ, eps=0.05,
                           partial_blocks=4, delay=1, block_rows=64,
                           lane=512)


@pytest.fixture(scope="module")
def reduced_reference():
    cell = cells.reduced_cell("smollm-w4-int8")
    ref, _ = asgd.run(llama, cell["cfg"], REDUCED_ST, 3,
                      steps=cell["check_steps"])
    return cell, ref


@pytest.mark.parametrize("variant", ["control_bf16", "half_batch",
                                     "no_exchange", "unchanged",
                                     "wrong_peer"])
def test_the_reference_in_the_programs_place_fails(variant,
                                                   reduced_reference):
    """The control (bfloat16 forward and backward) and each planted fault
    fail at least one of the limits that the program meets at this size."""
    cell, ref = reduced_reference
    kw = ({"dtype": jnp.bfloat16} if variant == "control_bf16"
          else {"fault": variant})
    got, _ = asgd.run(llama, cell["cfg"], REDUCED_ST, 3,
                      steps=cell["check_steps"], **kw)
    ok, checks = correct.judge(correct.compare(got, ref), cell["limits"])
    assert not ok, checks


def test_the_reference_agrees_with_itself():
    cell = cells.reduced_cell("smollm-w4-int8")
    st = asgd.Settings(workers=2, batch=1, seq=cells.SEQ, eps=0.05,
                       partial_blocks=4, delay=1, block_rows=64, lane=512)
    a, _ = asgd.run(llama, cell["cfg"], st, 9)
    b, _ = asgd.run(llama, cell["cfg"], st, 9)
    numbers = correct.compare(a, b)
    assert all(v == 0 for v in numbers.values()), numbers
    assert np.all(a.change_norms > 0)
