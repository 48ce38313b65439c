"""A run whose timed path is broken underneath comes out not correct: once
for each fault a one-chip cell can have (the partitioned cell's own fault,
the exchange left out, is in ``test_bench_p4.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny import run, tiny_cell  # puts the simulator's src on the path

import repro.core.step as step_mod  # noqa: E402
import repro.exp  # noqa: E402


@pytest.fixture
def fresh_programs():
    """Programs compiled before or during a fault must not be reused."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _patch_step(monkeypatch, change):
    real = step_mod.sim_step

    def broken(carry, t, **kw):
        new, rec = real(carry, t, **kw)
        return change(carry, new, t), rec

    monkeypatch.setattr(step_mod, "sim_step", broken)


def test_step_returns_its_state_unchanged(monkeypatch, fresh_programs):
    _patch_step(monkeypatch, lambda old, new, t: old)
    r = run(tiny_cell("q19_sugar"))
    assert r["correct"] is False and r["checks"]["count_mismatch"]["value"]


def test_answer_altered_where_produced(monkeypatch, fresh_programs):
    _patch_step(monkeypatch, lambda old, new, t: new._replace(
        counts=new.counts.at[0].add(jnp.where(t == 0, 1, 0))))
    r = run(tiny_cell("q19_sugar"))
    assert r["correct"] is False
    assert r["checks"]["count_mismatch"]["value"] == 1


def test_half_the_batch_left_out(monkeypatch, fresh_programs):
    real = repro.exp.run_trials

    def half(c, cfg, t_steps, seeds, **kw):
        keep = list(seeds)[: len(seeds) // 2]
        r = real(c, cfg, t_steps, seeds=keep, **kw)

        def fill(x):
            x = np.asarray(x)
            mean = x.mean(axis=0, keepdims=True).astype(x.dtype)
            return np.concatenate(
                [x, np.repeat(mean, len(seeds) - len(keep), 0)])

        return r._replace(counts=fill(r.counts), dropped=fill(r.dropped),
                          state=type(r.state)(*map(fill, r.state)))

    monkeypatch.setattr(repro.exp, "run_trials", half)
    r = run(tiny_cell("f32_trials4"))
    assert r["correct"] is False
