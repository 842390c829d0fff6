"""Precision-regime regression tests.

Two bugfix families pinned here:

* the device-input cache (``sweep._renewal_device_inputs``) keys on the
  *effective* dtype regime as well as config content — toggling x64
  around a cached call, or interleaving the f32 Pallas engine with the
  x64 scan, must never serve stale-dtype stacked inputs;
* the float32 casts the Pallas engine applies to float64-built inputs
  (``sweep._pack_pallas_inputs``, the policy-stack cast in
  ``renewal_monte_carlo_policies``) are *bit-exact* for every value the
  configs carry, so the policy path and the scenario path feed the
  kernel identical bits — the CRN cross-validation in
  tests/test_renewal_pallas.py rests on this.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import optimize, sweep
from repro.core import scenarios as scen_mod
from repro.core.scenarios import paper_scenarios


def _float_leaves(tree):
    return [a for a in jax.tree.leaves(tree)
            if jnp.issubdtype(a.dtype, jnp.floating)]


def _int_leaves(tree):
    return [a for a in jax.tree.leaves(tree)
            if not jnp.issubdtype(a.dtype, jnp.floating)]


# ---------------------------------------------------------------------------
# the device-input cache vs the x64 regime
# ---------------------------------------------------------------------------

def test_cache_keys_on_effective_dtype_regime():
    """The regression: a content-keyed cache would serve the float32 entry
    to the x64 scan engine (or the float64 entry to the Pallas engine)
    once both run in one process.  The key must include the regime, and
    repeated same-regime calls must still hit."""
    sweep._renewal_inputs_cache.clear()
    cfgs = list(paper_scenarios().values())

    _, s32 = sweep._renewal_device_inputs(cfgs, jnp.float32)
    assert all(a.dtype == jnp.float32 for a in _float_leaves(s32))

    with jax.enable_x64():
        _, s64 = sweep._renewal_device_inputs(cfgs, jnp.float64)
        assert all(a.dtype == jnp.float64 for a in _float_leaves(s64))

    # same content, both regimes resident: each regime hits its own entry
    with jax.enable_x64():
        _, again64 = sweep._renewal_device_inputs(cfgs, jnp.float64)
    _, again32 = sweep._renewal_device_inputs(cfgs, jnp.float32)
    assert again64 is s64 and again32 is s32
    assert all(a.dtype == jnp.float32 for a in _float_leaves(again32))


def test_cache_f64_request_outside_x64_is_the_f32_regime():
    """A float64 request outside ``enable_x64`` *builds float32 arrays*
    (JAX demotes), so it must share the float32 entry — and must NOT
    poison the real float64 regime, which still gets fresh x64 arrays."""
    sweep._renewal_inputs_cache.clear()
    cfgs = list(paper_scenarios().values())

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)    # JAX demotion notice
        _, demoted = sweep._renewal_device_inputs(cfgs, jnp.float64)  # no x64
    assert all(a.dtype == jnp.float32 for a in _float_leaves(demoted))

    _, s32 = sweep._renewal_device_inputs(cfgs, jnp.float32)
    assert s32 is demoted                     # one entry, correctly shared

    with jax.enable_x64():
        _, s64 = sweep._renewal_device_inputs(cfgs, jnp.float64)
    assert s64 is not demoted
    assert all(a.dtype == jnp.float64 for a in _float_leaves(s64))


# ---------------------------------------------------------------------------
# float32 casts of float64-built inputs are bit-exact (the Pallas feed)
# ---------------------------------------------------------------------------

def test_scenario_inputs_f32_cast_of_f64_is_bit_exact():
    """Every float leaf of the six-scenario stack: building in float64 and
    casting to float32 gives bit-for-bit the direct float32 build — the
    config values (durations, powers, fractions) all round-trip."""
    sweep._renewal_inputs_cache.clear()
    cfgs = list(paper_scenarios().values())
    _, s32 = sweep._renewal_device_inputs(cfgs, jnp.float32)
    with jax.enable_x64():
        _, s64 = sweep._renewal_device_inputs(cfgs, jnp.float64)
    for a32, a64 in zip(_float_leaves(s32), _float_leaves(s64)):
        np.testing.assert_array_equal(np.asarray(a32),
                                      np.asarray(a64, np.float32))
    for i32, i64 in zip(_int_leaves(s32), _int_leaves(s64)):
        np.testing.assert_array_equal(np.asarray(i32), np.asarray(i64))


def test_policy_lane_f32_cast_matches_direct_f32_build():
    """Lane ``p`` of the float64 policy stack (``optimize.policy_inputs``),
    cast to float32 the way the Pallas policy path does, equals the direct
    float32 ``sweep_inputs`` of that policy's config — so the policy grid
    and standalone scenario calls feed the kernel identical bits (the CRN
    bit-identity test in tests/test_renewal_pallas.py observes this from
    the outside; this pins the mechanism)."""
    cfg = paper_scenarios()["scenario2_long_reexec"]
    table = optimize.default_policy_table(cfg, 12000.0)
    stacked = optimize.policy_inputs(cfg, table)
    cast = (lambda a: a.astype(jnp.float32)
            if jnp.issubdtype(a.dtype, jnp.floating) else a)
    stacked32 = jax.tree.map(cast, stacked)
    for p in (0, 3, len(table) - 1):
        lane = jax.tree.map(lambda a, p=p: a[p], stacked32)
        cfg_p = scen_mod.apply_policy(cfg, **table.policy(p))
        direct = sweep.sweep_inputs(cfg_p, jnp.float32)
        for a, b in zip(jax.tree.leaves(lane), jax.tree.leaves(direct)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"policy {p}")


def test_pallas_pack_identical_from_either_regime():
    """The packed kernel operands (params row, node block, ladder) are
    bit-identical whether built from the float32 stack or the float64
    stack cast down — the two engine entry paths."""
    sweep._renewal_inputs_cache.clear()
    cfgs = list(paper_scenarios().values())
    _, s32 = sweep._renewal_device_inputs(cfgs, jnp.float32)
    with jax.enable_x64():
        _, s64 = sweep._renewal_device_inputs(cfgs, jnp.float64)
    cast = (lambda a: a.astype(jnp.float32)
            if jnp.issubdtype(a.dtype, jnp.floating) else a)
    a_pack = sweep._pack_pallas_inputs(s32, 12345.0)
    b_pack = sweep._pack_pallas_inputs(jax.tree.map(cast, s64), 12345.0)
    for a, b in zip(a_pack, b_pack):
        assert a.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
