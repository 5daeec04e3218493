"""A whole training run of the harness on the CPU at a tiny size, with the
look for a chip skipped: sound, then with the timed step broken."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench_tiny import tiny  # noqa: F401  (fixture)
from bench import run
from repro.core import trainer

real_iteration = trainer.lda_iteration


def unchanged(cfg, shard, state, key, *a, **k):
    """A step that returns its state unchanged (the counter moves on)."""
    _, stats = real_iteration(cfg, shard, state, key, *a, **k)
    return state._replace(iteration=state.iteration + 1), stats


def half_left_out(cfg, shard, state, key, *a, **k):
    """Half of the tiles keep their old topics; counts as the program's."""
    new, stats = real_iteration(cfg, shard, state, key, *a, **k)
    h = state.z.shape[0] // 2
    return new._replace(z=new.z.at[h:].set(state.z[h:])), stats


def token_altered(cfg, shard, state, key, *a, **k):
    """One tile's draws altered where they are produced."""
    new, stats = real_iteration(cfg, shard, state, key, *a, **k)
    z = new.z.at[0].set(((new.z[0].astype(jnp.int32) + 1)
                         % cfg.num_topics).astype(new.z.dtype))
    return new._replace(z=z), stats


def test_sound_run_is_correct(tiny):
    r = run.run_cell(tiny, "tiny.train", 2**33 + 17, 1.0, False,
                     require_tpu=False)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", [unchanged, half_left_out, token_altered])
def test_broken_step_is_not_correct(tiny, monkeypatch, fault):
    monkeypatch.setattr(trainer, "lda_iteration", fault)
    r = run.run_cell(tiny, "tiny.train", 41, 1.0, False, require_tpu=False)
    assert not r["correct"], r["checks"]


def test_control_fails_the_check(tiny):
    """The reference in bfloat16, in the program's place, reads above the
    draw limit (the control of the training cells)."""
    from bench import spec

    cell = spec.resolve(tiny, "tiny.train")
    ctx = run.Context(cell.config, cell.traffic, 5, 1.0, False,
                      spec.load_json(tiny / "bench" / "limits" /
                                     "tiny.train.json")["limits"], 1)
    st = cell.driver.setup(ctx)
    cell.driver.window(st, ctx, 0.5)
    cell.driver.release(st)
    (name, value), = cell.driver.control(st, ctx)
    assert value > ctx.limits[name]
    sound = dict(cell.driver.check(st, ctx))
    assert sound[name] <= ctx.limits[name]
