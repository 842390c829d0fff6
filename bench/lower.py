"""Lower a configuration file's numbers onto the program's public
constructors (``ScenarioConfig``, ``MachineProfile``, the failure processes,
the rack topology), so that the deployment is data in ``bench/configs`` and
not whatever the program's presets say today."""
from __future__ import annotations

DAY_S = 24 * 3600.0


def machine_profile(m: dict):
    from repro.core.characterization import MachineProfile, PowerTable, SleepSpec
    return MachineProfile(
        name=m["name"], power_table=PowerTable(**m["ladder"]),
        sleep=SleepSpec(**m["sleep"]), p_base=m["p_base"],
        p_idle_wait=m["p_idle_wait"])


def scenarios(config: dict) -> list:
    """The configuration's scenarios as ``ScenarioConfig``s, in file order."""
    from repro.core import energy_model as em
    from repro.core.simulator import NodeStart, ScenarioConfig
    machines = {m["name"]: machine_profile(m) for m in config["machines"]}
    out = []
    for s in config["scenarios"]:
        out.append(ScenarioConfig(
            name=s["name"],
            survivors=tuple(NodeStart(**sv) for sv in s["survivors"]),
            t_down=s["t_down"], t_restart=s["t_restart"],
            t_reexec=s["t_reexec"], profile=machines[s["machine"]],
            ckpt_interval=s["ckpt_interval"],
            ckpt_duration=s["ckpt_duration"],
            wait_mode=em.WaitMode(s["wait_mode"]),
            move_ahead=s["move_ahead"], move_ahead_frac=s["move_ahead_frac"],
            mu1=s["mu1"], mu2=s["mu2"]))
    return out


def failure_process(failure: dict, mtbf_s: float):
    from repro.core import failures
    if failure["family"] == "exponential":
        return failures.Exponential(mtbf_s)
    if failure["family"] == "weibull":
        return failures.Weibull.from_mtbf(failure["k"], mtbf_s)
    raise ValueError(f"unknown failure family {failure['family']!r}")


def rack_topology(topology: dict, n_nodes: int):
    from repro.core import topology as node_topology
    return node_topology.rack_topology(
        n_nodes, topology["rack_size"],
        shock_mtbs_s=topology["shock_mtbs_days"] * DAY_S,
        p_kill=topology["p_kill"], age_boost_s=topology["age_boost_s"])
