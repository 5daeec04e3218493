"""A whole serving run of the harness on the CPU at a tiny size, with the
look for a chip skipped: sound, then with the fold-in's answers broken."""
from __future__ import annotations

import pytest

from bench_tiny import tiny  # noqa: F401  (fixture)
from bench import run
from repro.serve import engine

real_fold_in = engine.fold_in_request


def answer_altered(snap, buf, cfg, **kw):
    """Every answer's theta moved by one topic where it is produced."""
    import jax.numpy as jnp

    res = real_fold_in(snap, buf, cfg, **kw)
    return res._replace(theta=jnp.roll(res.theta, 1, axis=-1))


def half_left_out(snap, buf, cfg, **kw):
    """The second half of each batch folded in as empty documents."""
    B, L = buf.shape[0] - 1, buf.shape[1] - 1
    return real_fold_in(snap, buf.at[B // 2:B, L].set(0), cfg, **kw)


def test_sound_run_is_correct(tiny):
    r = run.run_cell(tiny, "tiny.serve", 2**34 + 3, 1.0, False,
                     require_tpu=False)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"serve_p95_ms", "serve_docs_per_s",
                                 "setup_s"}
    assert r["attempted"] == 40 and r["failed"] == 0


@pytest.mark.parametrize("fault", [answer_altered, half_left_out])
def test_broken_answers_are_not_correct(tiny, monkeypatch, fault):
    monkeypatch.setattr(engine, "fold_in_request", fault)
    r = run.run_cell(tiny, "tiny.serve", 77, 1.0, False, require_tpu=False)
    assert not r["correct"], r["checks"]


def test_control_fails_the_check(tiny):
    """The reference in bfloat16, in the program's place, reads above the
    answer limit (the control of the serving cell)."""
    from bench import spec

    cell = spec.resolve(tiny, "tiny.serve")
    ctx = run.Context(cell.config, cell.traffic, 6, 1.0, False,
                      spec.load_json(tiny / "bench" / "limits" /
                                     "tiny.serve.json")["limits"], 1)
    st = cell.driver.setup(ctx)
    cell.driver.window(st, ctx, 1.0)
    cell.driver.release(st)
    (name, value), = cell.driver.control(st, ctx)
    assert value > ctx.limits[name]
    assert dict(cell.driver.check(st, ctx))[name] <= ctx.limits[name]
