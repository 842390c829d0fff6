"""Property-based tests for the Algorithm-1 strategy engine.

The invariants below are the paper's stated guarantees:
  * the selected frequency never makes the recovered process wait
    (comp_time <= T_failed);
  * intervention never consumes more energy than the reference (saving >= 0);
  * the selection is the argmin over feasible ladder levels;
  * vectorized evaluation == per-node evaluation.
"""
import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core import energy_model as em
from repro.core import strategies
from repro.core.characterization import paper_machine_profile, tpu_v5e_like_profile

PROFILES = [paper_machine_profile(), tpu_v5e_like_profile()]

node_inputs = st.tuples(
    st.floats(min_value=1.0, max_value=5000.0),     # t_comp_fa
    st.floats(min_value=0.0, max_value=10000.0),    # extra slack -> t_failed
    st.integers(min_value=0, max_value=3),          # n_ckpt
    st.sampled_from([em.WaitMode.ACTIVE, em.WaitMode.IDLE]),
    st.integers(min_value=0, max_value=1),          # profile index
)


def _decide(t_comp, slack, n_ckpt, wait_mode, profile):
    t_ckpt = 120.0
    # by construction fa is feasible: t_failed >= comp_time(fa)
    t_failed = t_comp + n_ckpt * t_ckpt + slack
    return (
        strategies.evaluate_strategies_profile(
            profile, t_comp, t_failed, float(n_ckpt), t_ckpt, int(wait_mode)
        ),
        t_failed,
    )


@settings(max_examples=200, deadline=None)
@given(node_inputs)
def test_never_delays_recovered_process(inp):
    t_comp, slack, n_ckpt, wait_mode, pidx = inp
    d, t_failed = _decide(t_comp, slack, n_ckpt, wait_mode, PROFILES[pidx])
    assert bool(d.feasible_any)
    assert float(d.comp_time) <= t_failed * (1 + 1e-5)
    assert float(d.wait_time) >= -1e-3


@settings(max_examples=200, deadline=None)
@given(node_inputs)
def test_saving_nonnegative(inp):
    t_comp, slack, n_ckpt, wait_mode, pidx = inp
    d, _ = _decide(t_comp, slack, n_ckpt, wait_mode, PROFILES[pidx])
    assert float(d.saving) >= -0.1  # float32 ULP tolerance at ~1e5 J scale
    assert float(d.energy_intervened) <= float(d.energy_reference) + 0.1


@settings(max_examples=100, deadline=None)
@given(node_inputs)
def test_selection_is_argmin(inp):
    t_comp, slack, n_ckpt, wait_mode, pidx = inp
    profile = PROFILES[pidx]
    d, t_failed = _decide(t_comp, slack, n_ckpt, wait_mode, profile)
    ladder = em.LadderArrays.from_table(profile.power_table)
    sleep = em.SleepArrays.from_spec(profile.sleep)
    out = em.intervention_energy(
        jnp.asarray(t_comp, jnp.float32), jnp.asarray(t_failed, jnp.float32),
        jnp.asarray(float(n_ckpt), jnp.float32), 120.0, ladder, sleep,
        jnp.asarray(int(wait_mode), jnp.int32), profile.p_idle_wait, mu1=6.0,
    )
    totals = np.asarray(out["total"])
    # fused-jit vs eager differ by a couple of float32 ULPs
    assert float(d.energy_intervened) <= np.min(totals) * (1 + 1e-5) + 1e-2


@settings(max_examples=50, deadline=None)
@given(st.lists(node_inputs, min_size=2, max_size=16))
def test_vectorized_matches_scalar(batch):
    """One batched call == N scalar calls (the scale-out claim)."""
    pidx = batch[0][4]
    profile = PROFILES[pidx]
    t_comp = np.array([b[0] for b in batch], np.float32)
    n_ckpt = np.array([float(b[2]) for b in batch], np.float32)
    t_failed = t_comp + n_ckpt * 120.0 + np.array([b[1] for b in batch], np.float32)
    modes = np.array([int(b[3]) for b in batch], np.int32)
    d = strategies.evaluate_strategies_profile(
        profile, t_comp, t_failed, n_ckpt, 120.0, modes
    )
    for i in range(len(batch)):
        di = strategies.evaluate_strategies_profile(
            profile, t_comp[i], t_failed[i], n_ckpt[i], 120.0, modes[i]
        )
        assert int(np.asarray(d.level)[i]) == int(di.level)
        assert int(np.asarray(d.wait_action)[i]) == int(di.wait_action)
        np.testing.assert_allclose(
            np.asarray(d.saving)[i], float(di.saving), rtol=5e-4, atol=0.5
        )


def test_monte_carlo_grid_shape():
    """Failure-time sweeps batch along leading axes (T, N)."""
    profile = paper_machine_profile()
    t_comp = np.linspace(10, 1000, 8)[:, None] * np.ones((1, 5))
    t_failed = t_comp + np.linspace(0, 4000, 5)[None, :]
    d = strategies.evaluate_strategies_profile(
        profile, t_comp, t_failed, 0.0, 120.0, em.WaitMode.ACTIVE
    )
    assert d.level.shape == (8, 5)
    assert np.all(np.asarray(d.saving) >= -1e-2)


def test_mu1_band_and_defaults():
    """The Table-4 decisions pin mu1 to the open band (110/30, 230/30) ~=
    (3.67, 7.67); both evaluation entry points must default inside it (the
    docstring's derivation, regression-pinned here against the defaults)."""
    import inspect

    lo, hi = 110.0 / 30.0, 230.0 / 30.0
    for fn in (strategies.evaluate_strategies, strategies.evaluate_strategies_profile):
        default = inspect.signature(fn).parameters["mu1"].default
        assert default == 6.0, fn.__name__
        assert lo < default < hi, fn.__name__

    profile = paper_machine_profile()

    def sleeps(t_comp, t_failed, n_ckpt, mu1):
        d = strategies.evaluate_strategies_profile(
            profile, t_comp, t_failed, n_ckpt, 120.0, int(em.WaitMode.ACTIVE),
            mu1=mu1,
        )
        return int(d.wait_action) == em.WaitAction.SLEEP

    # scenario 1 node 1 (110 s wait, must NOT sleep) fixes the lower edge;
    # nodes 2-3 (230 s wait, MUST sleep) fix the upper edge.  Decisions hold
    # for every mu1 inside the band — including the defaults and all four
    # integers — and flip just outside it.
    for mu1 in (lo + 1e-3, 4.0, 5.0, 6.0, 7.0, hi - 1e-3):
        assert not sleeps(972.0, 1202.0, 1.0, mu1), mu1   # wait = 110 s
        assert sleeps(103.8, 333.8, 0.0, mu1), mu1        # wait = 230 s
    assert sleeps(972.0, 1202.0, 1.0, lo - 0.1)           # gate too loose
    assert not sleeps(103.8, 333.8, 0.0, hi + 0.1)        # gate too tight


def test_known_decisions_table4():
    """Spot-check the four decision regimes of Table 4 (one per scenario
    family); the full rows are covered in test_scenarios.py."""
    profile = paper_machine_profile()
    # scenario 1 node 1: wait 110 s -> min-freq, no comp change
    d = strategies.evaluate_strategies_profile(
        profile, 972.0, 1202.0, 1.0, 120.0, em.WaitMode.ACTIVE
    )
    assert int(d.level) == 0 and int(d.wait_action) == em.WaitAction.MIN_FREQ
    np.testing.assert_allclose(float(d.saving), 4400.0, rtol=1e-4)
    # scenario 2 node 1: long wait -> sleep, no comp change
    d = strategies.evaluate_strategies_profile(
        profile, 481.2, 2521.2, 1.0, 120.0, em.WaitMode.ACTIVE
    )
    assert int(d.level) == 0 and int(d.wait_action) == em.WaitAction.SLEEP
    np.testing.assert_allclose(float(d.saving), 294310.0, rtol=1e-4)
    # scenario 4 node 2: 1.7 GHz comp + min-freq wait
    d = strategies.evaluate_strategies_profile(
        profile, 166.0, 325.8, 0.0, 120.0, em.WaitMode.ACTIVE
    )
    np.testing.assert_allclose(float(d.freq_ghz), 1.7, rtol=1e-6)
    assert int(d.wait_action) == em.WaitAction.MIN_FREQ
    # scenario 5 node 1: idle waits -> 2.1 GHz comp, no wait action
    d = strategies.evaluate_strategies_profile(
        profile, 141.0, 300.8, 0.0, 120.0, em.WaitMode.IDLE
    )
    np.testing.assert_allclose(float(d.freq_ghz), 2.1, rtol=1e-6)
    assert int(d.wait_action) == em.WaitAction.NONE


def _fold_margins(profile, t_comp, t_failed, n_ckpt, wait_mode):
    ladder = em.LadderArrays.from_table(profile.power_table)
    sleep = em.SleepArrays.from_spec(profile.sleep)
    cols = [n_ckpt] * ladder.num_levels
    args = (t_comp, t_failed, cols, 120.0, ladder, sleep, wait_mode,
            profile.p_idle_wait)
    return (strategies.evaluate_strategies_fold(*args),
            np.asarray(strategies.decision_margins(*args)))


@settings(max_examples=50, deadline=None)
@given(st.lists(node_inputs, min_size=2, max_size=16))
def test_decision_margins_hold_decisions(batch):
    """Moving T_failed by less than a node's margin changes none of its
    Algorithm-1 decisions (the margin is in seconds of T_failed: every
    energy comparison moves by at most the largest power per second)."""
    profile = PROFILES[batch[0][4]]
    t_comp = np.array([b[0] for b in batch], np.float32)
    n_ckpt = np.array([float(b[2]) for b in batch], np.float32)
    t_failed = t_comp + n_ckpt * 120.0 + np.array([b[1] for b in batch],
                                                  np.float32)
    modes = np.array([int(b[3]) for b in batch], np.int32)
    d, margin = _fold_margins(profile, t_comp, t_failed, n_ckpt, modes)
    assert margin.shape == t_comp.shape and np.all(margin >= 0.0)
    # float32 T_failed carries ~1e-3 s of rounding at 1e4 s: look only at
    # nodes whose margin is well clear of it
    keep = margin > 1e-2
    for sign in (-0.4, 0.4):
        moved = (t_failed + sign * np.where(keep, np.minimum(margin, 1e3),
                                            0.0)).astype(np.float32)
        dm, _ = _fold_margins(profile, t_comp, moved, n_ckpt, modes)
        for field in ("level", "wait_action", "feasible_any"):
            a = np.asarray(getattr(d, field))[keep]
            b = np.asarray(getattr(dm, field))[keep]
            assert np.array_equal(a, b), (field, sign)


def test_decision_margins_find_the_sleep_gate_tie():
    """Scenario 1 node 1 with its wait at exactly mu1 x the sleep transition
    time (6 x 30 s = 180 s): the margin is zero, and moving T_failed by a
    hundredth of a second either way flips the sleep decision."""
    profile = paper_machine_profile()
    gate = 6.0 * profile.sleep.transition_time
    tie = 972.0 + 120.0 + gate
    _, margin = _fold_margins(profile, np.float32(972.0), np.float32(tie),
                              np.float32(1.0), int(em.WaitMode.ACTIVE))
    assert float(margin) <= 1e-3
    below, m_below = _fold_margins(profile, np.float32(972.0),
                                   np.float32(tie - 0.01), np.float32(1.0),
                                   int(em.WaitMode.ACTIVE))
    above, _ = _fold_margins(profile, np.float32(972.0),
                             np.float32(tie + 0.01), np.float32(1.0),
                             int(em.WaitMode.ACTIVE))
    assert int(below.wait_action) != int(above.wait_action)
    assert int(above.wait_action) == em.WaitAction.SLEEP
    assert 5e-3 < float(m_below) < 2e-2
