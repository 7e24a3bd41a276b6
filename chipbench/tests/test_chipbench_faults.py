"""The check catches a broken timed path, and the control fails it.

Each fault is planted underneath a tiny run, which then proceeds as the
harness drives it (the look for a chip skipped); `correct` must come out
false:

  state     a step that returns its state unchanged;
  half      half of the batch left out, its answers taken from the rest;
  altered   a token, or an answer, altered where it is produced.

The control is the reference in the program's place at the nearest lower
precision (fleet: bfloat16 for float32; pool: float8 for bfloat16).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, harness
from tiny import bench, tiny

SEED = 2 ** 31 + 4242


def run(cell, plant=None):
    c = tiny(cells.resolve(cell, bench()))
    d = harness.make_driver(c, SEED)
    d.setup()
    if plant is not None:
        plant(d)
    d.window(0.5)
    d.free()
    return d, c


# ------------------------------------------------------------------ pool
def _state_unchanged(d):
    """decode_chunk hands back the cache it was given: tokens advance, the
    model's state does not."""
    for r in d.cloud.replicas:
        decode = r.engine.decode_chunk

        def frozen(state, steps, decode=decode):
            keep = jax.tree.map(jnp.copy, state.cache)
            return decode(state, steps)._replace(cache=keep)
        r.engine.decode_chunk = frozen


def _half_batch(d):
    """prefill computes the first half of the rows and copies them over
    the second half."""
    for r in d.cloud.replicas:
        prefill = r.engine.prefill

        def halved(prompts, prefill=prefill):
            p = np.asarray(prompts)
            h = p.shape[0] // 2
            return prefill(np.concatenate([p[:h], p[:p.shape[0] - h]]))
        r.engine.prefill = halved


def _token_altered(d):
    """every generated token is one id higher than the model's choice."""
    for r in d.cloud.replicas:
        decode = r.engine.decode_chunk
        vocab = r.engine.cfg.vocab
        eos = r.engine.eos_id

        def shifted(state, steps, decode=decode, vocab=vocab, eos=eos):
            st = decode(state, steps)
            out = jnp.where(st.out == eos, eos, (st.out + 1) % vocab)
            return st._replace(out=out)
        r.engine.decode_chunk = shifted


# tiny widths give smaller bfloat16 and float8 errors than the published
# ones; this limit sits between the two at this size, as the cell's do at
# its own (the program reads under 0.002 here, the control over 0.02)
TINY_GAP_LIMIT = 0.01


def tiny_limits(c):
    """The configuration's limits, with each gap's at this size."""
    return {k: TINY_GAP_LIMIT if k.startswith("gap.") else v
            for k, v in harness.limits_for(c).items()}


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state", "half", "altered"])
def test_pool_fault_is_not_correct(plant):
    d, c = run("pool-awc-short", plant)
    sound, _ = run("pool-awc-short")
    assert harness.is_correct(harness.judge(sound.check(), tiny_limits(c)))
    checks = harness.judge(d.check(), tiny_limits(c))
    assert not harness.is_correct(checks), checks


def test_pool_control_is_not_correct():
    d, c = run("pool-awc-short")
    prog, ctl = d.check(), d.control()
    for name, value in ctl.items():
        assert prog[name] <= TINY_GAP_LIMIT < value, (name, prog, ctl)


# ----------------------------------------------------------------- fleet
def _fleet_state_unchanged(d, monkeypatch):
    from repro.core import confidence
    monkeypatch.setattr(confidence, "update_stats",
                        lambda stats, *a: stats)
    jax.clear_caches()


def _fleet_half_batch(d, monkeypatch):
    """the scan's answers for the second half of the tenants are copied
    from the first half's."""
    sim = d.fleet.simulate_fleet

    def halved(*a, **kw):
        res = sim(*a, **kw)
        h = res.action.shape[0] // 2
        dup = lambda x: np.concatenate([x[:h], x[:len(x) - h]])  # noqa: E731
        res.reward, res.cost = dup(res.reward), dup(res.cost)
        res.action, res.observed = dup(res.action), dup(res.observed)
        res.state = jax.tree.map(dup, res.state)
        return res
    monkeypatch.setattr(d.fleet, "simulate_fleet", halved)


def _fleet_answer_altered(d, monkeypatch):
    """one arm of one tenant-round is flipped in every call's actions."""
    sim = d.fleet.simulate_fleet

    def flipped(*a, **kw):
        res = sim(*a, **kw)
        res.action = res.action.copy()
        res.action[1, 0, 0] = 1.0 - res.action[1, 0, 0]
        return res
    monkeypatch.setattr(d.fleet, "simulate_fleet", flipped)


def _fleet_relax_altered(d, monkeypatch):
    """the relaxed solve's answer is scaled down where it is produced."""
    from repro.core import relax
    solve = relax.solve_relaxed_ix
    monkeypatch.setattr(relax, "solve_relaxed_ix",
                        lambda *a, **kw: 0.9 * solve(*a, **kw))
    jax.clear_caches()


@pytest.mark.parametrize("plant", [_fleet_state_unchanged, _fleet_half_batch,
                                   _fleet_answer_altered,
                                   _fleet_relax_altered],
                         ids=["state", "half", "altered", "relax"])
def test_fleet_fault_is_not_correct(plant, monkeypatch):
    d, c = run("fleet-suc",
               functools.partial(plant, monkeypatch=monkeypatch))
    checks = harness.judge(d.check(), harness.limits_for(c))
    monkeypatch.undo()
    jax.clear_caches()
    assert not harness.is_correct(checks), checks


def test_fleet_control_is_not_correct():
    d, c = run("fleet-suc")
    checks = harness.judge(d.control(), harness.limits_for(c))
    assert not harness.is_correct(checks), checks
