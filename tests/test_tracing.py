"""The trainer's own spans and scopes, read back from a real profiler trace
on the CPU at the reduced size (README.md §Tracing)."""
import jax
import pytest

from bench import program_trace as pt
from repro.launch.train import setup, train

STEPS = 5
ARGV = ["--arch", "smollm-135m", "--reduced", "--workers", "4", "--batch",
        "1", "--seq", "32", "--pipelined", "--wire-format", "int8",
        "--steps", str(STEPS), "--log-every", "2", "--seed", "3"]
CHILDREN = ("train.batch.generate", "train.batch.place", "train.dispatch")


def _setup(*extra):
    return setup(ARGV + list(extra), devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(losses, program spans) of a run whose first step compiles the step
    and whose other steps and checkpoint write run under the profiler."""
    d = tmp_path_factory.mktemp("trace")
    run = _setup("--save", str(d / "ckpt"))
    run.args.steps = 1
    losses = train(run)
    run.args.steps = STEPS
    with jax.profiler.trace(str(d)):
        losses += train(run)
    return losses, pt.load(d).spans


def test_one_step_span_per_step_with_its_children_nested(traced):
    _, spans = traced
    steps = pt.steps_in(spans, 0, 2 ** 63)
    assert len(steps) == STEPS - 1
    for step, reads in zip(steps, (1, 2, 1, 2)):
        # the loss every step; n_good on log steps (2) and the last (4)
        kids = pt.children(spans, step)
        names = sorted(k[0] for k in kids)
        assert names == sorted(CHILDREN + ("train.host_read",) * reads)
        for _, s, d in kids:
            assert step[1] <= s and s + d <= step[1] + step[2]
        assert 0 <= pt.self_ns(step, kids) < step[2]
    totals = pt.per_step(spans, 0, 2 ** 63)
    assert totals["train.step"][0] == STEPS - 1
    assert totals["train.host_read"][0] == STEPS - 1 + 2


def test_the_save_span_follows_the_last_step(traced):
    _, spans = traced
    save = [s for s in spans if s[0] == "train.save"]
    assert len(save) == 1
    last = pt.steps_in(spans, 0, 2 ** 63)[-1]
    assert save[0][1] >= last[1] + last[2]


def test_losses_are_the_same_with_the_profiler_on_and_off(traced):
    losses, _ = traced
    assert train(_setup()) == losses


@pytest.mark.parametrize("engine", [[], ["--packed-resident"],
                                    ["--pipelined"]])
def test_every_step_variant_carries_both_scopes(engine):
    argv = [a for a in ARGV if a != "--pipelined"] + engine
    run = setup(argv, devices=jax.devices()[:1])
    s = run.state
    text = run.step_fn.lower(
        s["params"], s["gossip"], s["opt"], run.next_wbatch(),
        jax.random.fold_in(run.key, 0)).as_text(debug_info=True)
    assert "step.fwd_bwd" in text
    assert "transpose(jvp(" in text
    assert "step.gossip" in text
