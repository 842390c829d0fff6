"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than present, so these tests catch what interpret mode
cannot: block shapes off the (8, 128) tiling, primitives Mosaic cannot
lower, programs that do not fit the device.  Four programs of the main path,
at the sizes ``chip_smoke.py`` runs:

  * the float32 renewal kernel at 6 scenarios x 4096 runs x 64 epochs, and
    at the advisor's policy grid (42 lanes x 128 runs x 32 epochs);
  * the x64 scan engine at the same shape, and a study's program (either
    engine's Monte-Carlo reduced over runs on the device), also at a
    whole-machine job's shape (1,023 survivors, folded inside the epoch
    scan);
  * the fleet core at a 64-cluster bucket;
  * the SSD kernel at mamba2-370m widths;
  * the flash-attention kernel at a GQA shape (8 query heads over 4 kv
    heads, 2048 tokens, head dim 128, bfloat16).

The topology is described inside a module fixture (never at import: only
one process at a time may load the TPU library, and every test worker
imports this file).  The persistent compilation cache is off around these
compiles, since an entry written for a described chip cannot be read back.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import fleet
from repro.core import failures, optimize, sweep
from repro.core.scenarios import paper_scenarios
from repro.core.simulator import NodeStart

N_RUNS, MAX_FAILURES = 4096, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                       sharding=sharding), tree)


def _scenarios():
    return [paper_scenarios()[n] for n in sorted(paper_scenarios())]


@pytest.mark.parametrize("lanes,n_runs,max_failures", [
    (6, N_RUNS, MAX_FAILURES),       # the six Table-4 scenarios
    (42, 128, 32),                   # FleetAdvisor's default policy grid
], ids=["scenarios", "policy_grid"])
def test_renewal_kernel_compiles(one_chip, lanes, n_runs, max_failures):
    from repro.kernels import renewal_scan as rs

    cfgs = _scenarios()
    cfgs = (cfgs * lanes)[:lanes]
    _, stacked = sweep._renewal_device_inputs(cfgs, jnp.float32)
    params, nodes, ladder = sweep._pack_pallas_inputs(stacked, 1.0)
    n = nodes.shape[-1]
    args = _abstract((params, nodes, ladder,
                      np.zeros((max_failures, n_runs), np.float32),
                      np.zeros((max_failures, n, n_runs), np.float32)),
                     one_chip)
    compiled = jax.jit(functools.partial(
        rs.renewal_scan_pallas, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_x64_scan_engine_compiles(one_chip):
    with jax.enable_x64():
        _, stacked = sweep._renewal_device_inputs(_scenarios())
        compiled = sweep._renewal_mc_jit.lower(
            _abstract(stacked, one_chip),
            _abstract(jax.random.PRNGKey(0), one_chip),
            _abstract(jnp.float64(30 * 24 * 3600.0), one_chip),
            _abstract(failures.Exponential(7 * 24 * 3600.0), one_chip),
            n_runs=N_RUNS, max_failures=MAX_FAILURES, stats=True).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


@pytest.mark.parametrize("engine", ["scan", "pallas"])
def test_study_program_compiles(one_chip, engine, monkeypatch):
    """A study's one program, Monte-Carlo and reduction over runs, for the
    engine's stacked inputs: what leaves the chip holds no run axis.  The
    kernel is compiled, not interpreted, as on the chip."""
    monkeypatch.setattr(sweep, "_pallas_interpret", lambda: False)
    process = failures.Exponential(7 * 24 * 3600.0)
    with sweep._staged(_scenarios(), process, None, engine) as (stacked,
                                                                proc):
        makespan = (jnp.float32 if engine == "pallas" else jnp.float64)(
            30 * 24 * 3600.0)
        compiled = sweep._renewal_study_jit.lower(
            _abstract(stacked, one_chip),
            _abstract(jax.random.PRNGKey(0), one_chip),
            _abstract(makespan, one_chip), _abstract(proc, one_chip),
            n_runs=N_RUNS, max_failures=MAX_FAILURES,
            engine=engine).compile()
    assert compiled.memory_analysis().output_size_in_bytes < 16e3
    assert ("tpu_custom_call" in compiled.as_text()) == (engine == "pallas")


def test_whole_machine_study_compiles_in_tiles(one_chip):
    """The study of one job over a 1,024-node machine (1,023 survivors,
    4,096 runs x 48 epochs, Weibull nodes): folded inside the epoch scan,
    one epoch's survivors at a time, its temporaries fit half the chip;
    folded over the stacked epochs they need 18.3 GB."""
    cfg = dataclasses.replace(
        paper_scenarios()["scenario1_short_reexec"], t_reexec=0.0,
        survivors=tuple(NodeStart(3600.0 * (i + 1) / 1024, 3600.0, 0.0)
                        for i in range(1023)))
    process = failures.Weibull.from_mtbf(0.7, 365 * 24 * 3600.0)
    with sweep._staged([cfg], process, None, "scan") as (stacked, proc):
        compiled = sweep._renewal_study_jit.lower(
            _abstract(stacked, one_chip),
            _abstract(jax.random.PRNGKey(0), one_chip),
            _abstract(jnp.float64(24 * 3600.0), one_chip),
            _abstract(proc, one_chip), n_runs=N_RUNS, max_failures=48,
            engine="scan").compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8e9


def test_fleet_core_compiles(one_chip):
    profiles = fleet.synthetic_fleet(64, node_buckets=(4,), weibull_frac=0.0)
    advisor = fleet.FleetAdvisor()
    specs = [p.spec() for p in profiles]
    with jax.enable_x64():
        stacked = optimize.fleet_policy_inputs([s.cfg for s in specs],
                                               advisor.table)
        makespans = np.stack([
            optimize.wall_makespan(s.work_s, advisor.table.ckpt_interval,
                                   s.cfg.ckpt_duration) for s in specs])
        core = jax.jit(functools.partial(
            sweep._renewal_fleet_mc_core, n_runs=advisor.n_runs,
            max_failures=advisor.max_failures))
        compiled = core.lower(
            _abstract(stacked, one_chip),
            _abstract(advisor.key, one_chip),
            _abstract(makespans, one_chip),
            _abstract(failures.stack_processes([s.process for s in specs]),
                      one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def test_ssd_kernel_compiles_at_mamba2_370m_widths(one_chip):
    from repro.configs import get_config
    from repro.kernels.ssd_scan import ssd_scan_pallas

    cfg = get_config("mamba2-370m")
    s = cfg.ssm
    heads = s.expand * cfg.d_model // s.head_dim
    b, seq = 4, 2048
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(functools.partial(
        ssd_scan_pallas, chunk=s.chunk_size, interpret=False)).lower(
        sds((b, heads, seq, s.head_dim), jnp.bfloat16),
        sds((b, heads, 1, seq), jnp.float32),
        sds((heads,), jnp.float32),
        sds((b, s.n_groups, seq, s.state_dim), jnp.bfloat16),
        sds((b, s.n_groups, seq, s.state_dim), jnp.bfloat16)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention_bhsd

    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                             sharding=one_chip)
    compiled = jax.jit(functools.partial(
        flash_attention_bhsd, group=2, causal=True, interpret=False)).lower(
        sds((8, 2048, 128)), sds((4, 2048, 128)), sds((4, 2048, 128))).compile()
    assert "tpu_custom_call" in compiled.as_text()
