"""`correct` at a size a test can hold: the sound program passes the cells'
own limits, the control (the program's int8 path) and each planted fault do
not.  Each case drives the rest of a run, with the timed path broken
underneath, without the harness's look for a chip."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.tests import tiny


def _run(name, monkeypatch, **kw):
    tiny.patch(monkeypatch)
    ctx = tiny.ctx(name, **kw)
    runner = importlib.import_module(
        "benchmark.runners." + ctx["cell_file"]["runner"])
    runner.run(ctx)
    return ctx["compared"]


def _break_train(monkeypatch, plant):
    """Plant a fault in the recipe right after its own setup()."""
    from automodel_tpu.recipes.llm import train_ft

    cls = train_ft.TrainFinetuneRecipeForNextTokenPrediction
    real = cls.setup

    def setup(self):
        real(self)
        plant(self)
        return self

    monkeypatch.setattr(cls, "setup", setup)


def state_unchanged(recipe):
    step = recipe.step_fns.train_step

    def frozen(params, opt_state, batch):
        copy = lambda t: jax.tree.map(jnp.copy, t)
        _, _, metrics = step(copy(params), copy(opt_state), batch)
        return params, opt_state, metrics

    recipe.step_fns.train_step = frozen


def half_batch_left_out(recipe):
    """Half of the step's batch gets no label, and the mean is taken over
    the rest: the later rows, or, where a step is one row, the later half
    of its tokens."""
    shard = recipe.step_fns.shard_batch

    def halved(stacked, **kw):
        labels = np.array(stacked["labels"])        # [microbatch, row, token]
        if labels.shape[1] > 1:
            labels[:, labels.shape[1] // 2:] = -100
        else:
            labels[..., labels.shape[-1] // 2:] = -100
        return shard(dict(stacked, labels=labels), **kw)

    recipe.step_fns.shard_batch = halved


def test_sound_training_is_correct(monkeypatch):
    compared = _run(tiny.TRAIN, monkeypatch)
    assert compared.correct, compared.rows


@pytest.mark.parametrize("plant", [state_unchanged, half_batch_left_out],
                         ids=lambda f: f.__name__)
def test_broken_training_is_not_correct(monkeypatch, plant):
    _break_train(monkeypatch, plant)
    compared = _run(tiny.TRAIN, monkeypatch)
    assert not compared.correct, compared.rows


def test_training_control_is_not_correct(monkeypatch):
    compared = _run(tiny.TRAIN, monkeypatch, control=True)
    assert not compared.correct, compared.rows


@pytest.mark.parametrize("cell", [tiny.CHAT, tiny.ROLLOUT])
def test_sound_serving_is_correct(monkeypatch, cell):
    compared = _run(cell, monkeypatch, seconds=1.0)
    assert compared.correct, compared.rows


def alter_tokens(monkeypatch):
    """Every greedy token altered where it is produced: in the step
    program's output, before the engine reads it."""
    from automodel_tpu.serving import engine as eng

    real = eng.DecodeEngine.step_fn

    def step_fn(self, width):
        fn = real(self, width)

        def altered(*a, **k):
            greedy, last, pools = fn(*a, **k)
            return (greedy + 1) % self.model.config.vocab_size, last, pools

        return altered

    monkeypatch.setattr(eng.DecodeEngine, "step_fn", step_fn)


def test_an_altered_token_is_not_correct(monkeypatch):
    alter_tokens(monkeypatch)
    compared = _run(tiny.ROLLOUT, monkeypatch, seconds=1.0)
    assert not compared.correct, compared.rows


def test_serving_control_is_not_correct(monkeypatch):
    compared = _run(tiny.ROLLOUT, monkeypatch, seconds=1.0, control=True)
    assert not compared.correct, compared.rows
