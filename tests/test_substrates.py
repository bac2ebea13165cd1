"""Tests for the substrate layers: data pipeline, optimizers, checkpointing,
and the end-to-end trainer integration (loss decreases)."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.data.synthetic import lm_batch_iterator, synthetic_tokens
from repro.optim import (adam_init, adam_update, lr_schedule, momentum_init,
                         momentum_update, sgd_update)


class TestData:
    def test_tokens_in_range_and_deterministic(self):
        rng1 = np.random.default_rng(7)
        rng2 = np.random.default_rng(7)
        t1 = synthetic_tokens(rng1, 4096, 100)
        t2 = synthetic_tokens(rng2, 4096, 100)
        np.testing.assert_array_equal(t1, t2)
        assert t1.min() >= 0 and t1.max() < 100

    def test_tokens_have_learnable_structure(self):
        """Motif copying must create repeated n-grams (a pure-noise stream
        would make loss-decrease tests meaningless)."""
        rng = np.random.default_rng(0)
        t = synthetic_tokens(rng, 50_000, 1000)
        bigrams = set()
        repeats = 0
        for i in range(len(t) - 1):
            bg = (int(t[i]), int(t[i + 1]))
            if bg in bigrams:
                repeats += 1
            bigrams.add(bg)
        assert repeats / len(t) > 0.3  # plenty of repeated bigrams

    def test_iterator_frontends(self):
        it = lm_batch_iterator(0, 2, 16, 100, frontend="audio",
                               d_model=32, encoder_seq=10)
        b = next(it)
        assert b["tokens"].shape == (2, 16)
        assert b["frames"].shape == (2, 10, 32)
        it = lm_batch_iterator(0, 2, 16, 100, frontend="vision",
                               d_model=32, prefix_len=4)
        assert next(it)["patches"].shape == (2, 4, 32)


class TestOptimizers:
    def _quad(self):
        target = {"a": jnp.array([1.0, -2.0]), "b": jnp.array(3.0)}

        def loss(p):
            return sum(jnp.sum((x - t) ** 2) for x, t in
                       zip(jax.tree.leaves(p), jax.tree.leaves(target)))
        p0 = jax.tree.map(jnp.zeros_like, target)
        return loss, p0, target

    def test_sgd_converges_on_quadratic(self):
        loss, p, target = self._quad()
        for _ in range(200):
            p = sgd_update(p, jax.grad(loss)(p), 0.1)
        assert loss(p) < 1e-4

    def test_momentum_converges(self):
        loss, p, target = self._quad()
        m = momentum_init(p)
        for _ in range(200):
            p, m = momentum_update(p, jax.grad(loss)(p), m, 0.05)
        assert loss(p) < 1e-4

    def test_adam_converges(self):
        loss, p, target = self._quad()
        s = adam_init(p)
        for _ in range(300):
            p, s = adam_update(p, jax.grad(loss)(p), s, 0.05)
        assert loss(p) < 1e-3

    def test_lr_schedules(self):
        for kind in ("const", "cosine", "linear"):
            f = lr_schedule(kind, 1.0, warmup=10, total=100)
            assert float(f(0)) == 0.0
            assert float(f(10)) == pytest.approx(1.0, abs=0.2)
            if kind != "const":
                assert float(f(100)) < 0.1


class TestCheckpoint:
    def test_roundtrip_mixed_dtypes(self, tmp_path):
        tree = {
            "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": jnp.ones((5,), jnp.bfloat16) * 1.5,
            "step": jnp.int32(7),
            "nested": {"k": jnp.zeros((2, 2, 2), jnp.int8)},
        }
        p = tmp_path / "ckpt.msgpack"
        save_checkpoint(p, tree)
        like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
        out = load_checkpoint(p, like)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_shape_mismatch_raises(self, tmp_path):
        p = tmp_path / "c.msgpack"
        save_checkpoint(p, {"w": jnp.zeros((2, 2))})
        with pytest.raises(ValueError):
            load_checkpoint(p, {"w": jnp.zeros((3, 3))})

    def test_early_termination_resume(self, tmp_path):
        """Paper §1/§4: stop-and-continue must be exact — resumed state
        equals the state that was saved."""
        from repro.core.gossip import GossipConfig, init_gossip_state
        params = {"w": jnp.arange(8.0).reshape(2, 4)[None].repeat(2, 0)}
        g = init_gossip_state(params, GossipConfig(partial_blocks=2))
        state = {"params": params, "gossip": g, "step": jnp.int32(41)}
        p = tmp_path / "resume.msgpack"
        save_checkpoint(p, state)
        like = jax.tree.map(jnp.zeros_like, state)
        out = load_checkpoint(p, like)
        assert int(out["step"]) == 41
        np.testing.assert_array_equal(out["params"]["w"], params["w"])


@pytest.mark.slow
class TestTrainerIntegration:
    def test_lm_training_reduces_loss(self):
        """End-to-end: ASGD-train a reduced smollm for 40 steps; next-token
        loss must decrease materially (synthetic data has structure)."""
        from repro.launch.train import setup, train
        losses = train(setup([
            "--arch", "smollm-135m", "--reduced", "--steps", "40",
            "--workers", "2", "--batch", "2", "--seq", "64",
            "--eps", "0.1", "--log-every", "100"]))
        assert losses[-1] < losses[0] * 0.85, (losses[0], losses[-1])

    def test_checkpoint_resume_continues(self, tmp_path):
        from repro.launch.train import setup, train
        ck = str(tmp_path / "t.msgpack")
        train(setup(["--arch", "smollm-135m", "--reduced", "--steps", "6",
                     "--workers", "2", "--batch", "1", "--seq", "32",
                     "--save", ck, "--log-every", "100"]))
        losses = train(setup(
            ["--arch", "smollm-135m", "--reduced", "--steps", "10",
             "--workers", "2", "--batch", "1", "--seq", "32",
             "--restore", ck, "--log-every", "100"]))
        assert len(losses) == 4  # resumed at step 6, ran to 10

    def test_serve_generates(self):
        from repro.launch.serve import main as serve_main
        toks = serve_main(["--arch", "smollm-135m", "--reduced",
                           "--batch", "2", "--prompt-len", "16",
                           "--new-tokens", "4"])
        assert toks.shape == (2, 4)
