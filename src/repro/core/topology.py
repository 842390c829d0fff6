"""Correlated failures over a node topology: shared shocks + trace ingestion.

Every failure process in ``core.failures`` samples i.i.d. per-node gaps;
real clusters fail in spatially correlated bursts — a PSU trip fells a
whole rack, a cooling event age-advances every node under it.  This module
adds the correlation axis as a **marked point process over a node tree**:

  * ``Topology`` — a static node -> group mapping per level (rack, PSU,
    room, ...), each level carrying per-group *shared-shock* clocks
    (exponential, mean ``shock_mtbs_s``), a per-node kill probability
    ``p_kill``, and an ``age_boost_s`` applied to the failure clocks of
    group members the shock spares (partial damage: the survivor's
    conditional-residual draw is conditioned on the boosted age, so
    non-memoryless marginals stay coherent — see docs/failures.md).
  * ``sample_correlated_renewal_gaps`` — the competing-risks recursion of
    ``failures.sample_renewal_gaps`` extended with the shock clocks: one
    jit-traceable scan emitting ``(gaps, failed_mask, primary)`` where
    ``failed_mask`` marks *every* node felled in the epoch (a shock fells
    several at once) and ``primary`` is the node whose lost work anchors the
    epoch's re-execution bookkeeping.  Both renewal engines trace this one
    function, so fixed-key correlated histories are bit-identical host vs
    device (the PR 4 contract, extended).
  * LANL-style trace ingestion — ``parse_lanl_csv`` / ``to_lanl_csv``,
    burst detection (``find_bursts``), correlation-preserving replay
    (``burst_replay_gaps``: whole bursts are resampled, never individual
    gaps), the marginal view (``trace_to_empirical``), and
    ``fit_shock_rates`` estimating per-level shock MTBS from inter-failure
    clustering.

Shock semantics (exact under the quiesce policy)
------------------------------------------------
Epoch gaps are measured in *balanced* time from the renewal anchor, and all
clocks — individual failure clocks and shock clocks — freeze during the
recovery epoch itself.  Shock clocks are exponential, so redrawing each
group's shock time fresh at every anchor is exact (memorylessness), while
the per-node processes keep their age-conditioned residual draws.  The
epoch event is the minimum over all individual residuals and all group
shock clocks:

  * an **individual** event fells exactly the argmin node (the iid path);
  * a **shock** at group ``g`` kills each member independently with
    probability ``p_kill``; if no member draw kills, the member with the
    smallest kill draw is felled anyway (every epoch ends in at least one
    failure — the renewal engines' epoch grammar requires it, and the
    conditioning is documented rather than hidden); members the shock
    spares get ``age_boost_s`` added to their failure clocks.

Survivor clocks advance by the epoch gap as usual, felled clocks reset —
``failed_mask`` is exactly the set of clocks that reset, which keeps the
conditional-residual recursion correct for shocked-but-spared nodes.
"""
from __future__ import annotations

import dataclasses
import io
import pathlib
from typing import Any, Iterable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import failures
from repro.core.planning import _ns

__all__ = [
    "TopologyLevel",
    "Topology",
    "rack_topology",
    "sample_correlated_renewal_gaps",
    "correlated_renewal_gaps",
    "survivor_slot_mask",
    "FailureTraceLog",
    "parse_lanl_csv",
    "to_lanl_csv",
    "history_to_log",
    "find_bursts",
    "trace_to_empirical",
    "burst_replay_gaps",
    "fit_shock_rates",
    "dispersion_index",
]


# ---------------------------------------------------------------------------
# the topology tree
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TopologyLevel:
    """One level of shared-shock structure (e.g. "rack").

    ``group_of`` maps node index -> group index at this level (static
    metadata: it shapes the traced program).  ``shock_mtbs_s`` is the mean
    time between shocks *per group* (scalar or per-group array);
    ``p_kill`` the per-member kill probability when the group's shock
    fires; ``age_boost_s`` the failure-clock advance applied to members the
    shock spares.
    """

    name: str
    group_of: tuple
    shock_mtbs_s: Any
    p_kill: Any = 1.0
    age_boost_s: Any = 0.0

    def __post_init__(self):
        groups = tuple(int(g) for g in self.group_of)
        if not groups:
            raise ValueError(f"level {self.name!r}: empty group_of")
        n_groups = max(groups) + 1
        if min(groups) < 0 or set(groups) != set(range(n_groups)):
            raise ValueError(
                f"level {self.name!r}: group ids must cover 0..G-1, "
                f"got {sorted(set(groups))}")
        object.__setattr__(self, "group_of", groups)
        object.__setattr__(self, "shock_mtbs_s",
                           failures._param(self.shock_mtbs_s))
        object.__setattr__(self, "p_kill", failures._param(self.p_kill))
        object.__setattr__(self, "age_boost_s",
                           failures._param(self.age_boost_s))
        failures._check_positive("shock_mtbs_s", self.shock_mtbs_s)
        for nm, v in (("p_kill", self.p_kill),
                      ("age_boost_s", self.age_boost_s)):
            # numbers are arrays by now; tracers and the placeholders that
            # pytree unflattening passes (as in ``jax.jit(...).lower``) are not
            if isinstance(v, np.ndarray):
                a = np.asarray(v, np.float64)
                if nm == "p_kill" and (np.any(a <= 0.0) or np.any(a > 1.0)):
                    raise ValueError(f"p_kill must be in (0, 1], got {a}")
                if nm == "age_boost_s" and np.any(a < 0.0):
                    raise ValueError(f"age_boost_s must be >= 0, got {a}")

    @property
    def n_groups(self) -> int:
        return max(self.group_of) + 1


@dataclasses.dataclass(frozen=True)
class Topology:
    """A stack of shock levels over ``n_nodes`` physical nodes."""

    n_nodes: int
    levels: tuple

    def __post_init__(self):
        levels = tuple(self.levels)
        if not levels:
            raise ValueError("topology needs at least one level")
        for lv in levels:
            if not isinstance(lv, TopologyLevel):
                raise TypeError(f"not a TopologyLevel: {lv!r}")
            if len(lv.group_of) != self.n_nodes:
                raise ValueError(
                    f"level {lv.name!r} maps {len(lv.group_of)} nodes, "
                    f"topology has {self.n_nodes}")
        object.__setattr__(self, "levels", levels)

    def label(self) -> str:
        parts = ",".join(f"{lv.name}x{lv.n_groups}" for lv in self.levels)
        return f"topology(n={self.n_nodes};{parts})"


jax.tree_util.register_dataclass(
    TopologyLevel, data_fields=["shock_mtbs_s", "p_kill", "age_boost_s"],
    meta_fields=["name", "group_of"])
jax.tree_util.register_dataclass(
    Topology, data_fields=["levels"], meta_fields=["n_nodes"])


def rack_topology(n_nodes: int, rack_size: int, *, shock_mtbs_s,
                  p_kill=1.0, age_boost_s=0.0) -> Topology:
    """The common case: consecutive nodes grouped into racks of
    ``rack_size`` (the last rack may be short), one shock level."""
    if rack_size < 1:
        raise ValueError("rack_size must be >= 1")
    group_of = tuple(i // rack_size for i in range(n_nodes))
    return Topology(n_nodes=n_nodes, levels=(
        TopologyLevel(name="rack", group_of=group_of,
                      shock_mtbs_s=shock_mtbs_s, p_kill=p_kill,
                      age_boost_s=age_boost_s),))


def _group_ids(topo: Topology) -> np.ndarray:
    """Static (L, n_nodes) int32 table: row ``l`` holds each node's group
    at level ``l`` as a global group id (levels' groups concatenated in
    order, so a global id names one level's group).  Node ``n`` is a member
    of global group ``g`` iff some row holds ``g`` at ``n``."""
    rows, offset = [], 0
    for lv in topo.levels:
        rows.append(offset + np.asarray(lv.group_of, np.int32))
        offset += lv.n_groups
    return np.stack(rows)


def _group_params(topo: Topology):
    """Concatenated per-total-group (mtbs, p_kill, age_boost) data leaves."""
    mtbs, pk, boost = [], [], []
    for lv in topo.levels:
        g = lv.n_groups
        mtbs.append(jnp.broadcast_to(
            jnp.asarray(lv.shock_mtbs_s, jnp.float32), (g,)))
        pk.append(jnp.broadcast_to(
            jnp.asarray(lv.p_kill, jnp.float32), (g,)))
        boost.append(jnp.broadcast_to(
            jnp.asarray(lv.age_boost_s, jnp.float32), (g,)))
    return (jnp.concatenate(mtbs), jnp.concatenate(pk),
            jnp.concatenate(boost))


# ---------------------------------------------------------------------------
# the correlated renewal-epoch sampler
# ---------------------------------------------------------------------------

def sample_correlated_renewal_gaps(
    topology: Topology,
    process: failures.FailureProcess,
    key: jax.Array,
    n_runs: int,
    max_failures: int,
    n_nodes: int,
):
    """Correlated renewal-epoch histories: ``(gaps, failed_mask, primary)``
    of shapes ``(R, K) f32``, ``(R, K, N) bool``, ``(R, K) int32``.

    The competing-risks recursion of ``failures.sample_renewal_gaps`` with
    the topology's group shock clocks racing the individual residuals (see
    the module docstring for the exact event semantics).  Jit-friendly with
    static shape args; traced by the fused device engine and jitted
    standalone for the host oracle (``correlated_renewal_gaps``), so the
    two see bit-identical histories for the same key.
    """
    with jax.named_scope("renewal_sample"):
        if topology.n_nodes != n_nodes:
            raise ValueError(f"topology has {topology.n_nodes} nodes, "
                             f"sampler asked for {n_nodes}")
        gid = jnp.asarray(_group_ids(topology))               # (L, N) int32
        mtbs, pkill, boost = _group_params(topology)          # (G,) each
        n_groups = mtbs.shape[0]
        group_ids = jnp.arange(n_groups)
        k_res, k_shock, k_kill = jax.random.split(key, 3)
        v = jax.random.uniform(
            k_res, (max_failures, n_runs, n_nodes), dtype=jnp.float32)
        w = jax.random.uniform(
            k_kill, (max_failures, n_runs, n_nodes), dtype=jnp.float32)
        su = jax.random.uniform(
            k_shock, (max_failures, n_runs, n_groups), dtype=jnp.float32)
        node_ids = jnp.arange(n_nodes)

        def step(ages, xs):
            v_k, w_k, su_k = xs
            t = process.residual(v_k, ages)                   # (R, N)
            gap_ind = jnp.min(t, axis=-1)
            i_ind = jnp.argmin(t, axis=-1)
            # fresh exponential shock clocks per anchor (exact: memoryless)
            s_times = mtbs * (-jnp.log1p(-su_k))              # (R, G)
            gap_shk = jnp.min(s_times, axis=-1)
            g_shk = jnp.argmin(s_times, axis=-1)
            # ties -> individual
            shock = gap_shk < gap_ind
            gap = jnp.where(shock, gap_shk, gap_ind)
            # the struck group's members and parameters by compare-and-select
            # over the static level and group axes: a gather with computed
            # indices runs element by element on the TPU
            member_g = jnp.any(gid == g_shk[:, None, None], axis=1)  # (R, N)
            struck = group_ids == g_shk[:, None]              # (R, G)
            # one term of each sum is nonzero, so both are exact
            pkill_g = jnp.where(struck, pkill, 0.0).sum(-1)
            boost_g = jnp.where(struck, boost, 0.0).sum(-1)
            killed = member_g & (w_k < pkill_g[:, None])
            # condition on >= 1 kill: the member with the smallest kill draw
            # falls even when every Bernoulli spares (the epoch grammar needs a
            # failure; the bias is documented and vanishes as p_kill -> 1)
            w_m = jnp.where(member_g, w_k, jnp.inf)
            forced = node_ids == jnp.argmin(w_m, axis=-1)[:, None]
            killed = jnp.where(jnp.any(killed, axis=-1, keepdims=True),
                               killed, forced)
            mask = jnp.where(shock[:, None],
                             killed, node_ids == i_ind[:, None])
            primary = jnp.where(
                shock, jnp.argmin(jnp.where(killed, w_k, jnp.inf), axis=-1),
                i_ind).astype(jnp.int32)
            spared = shock[:, None] & member_g & ~killed
            ages = jnp.where(
                mask, 0.0,
                ages + gap[:, None]
                + jnp.where(spared, boost_g[:, None], 0.0))
            return ages, (gap, mask, primary)

        init = jnp.zeros((n_runs, n_nodes), jnp.float32)
        _, (gaps, mask, primary) = jax.lax.scan(step, init, (v, w, su))
        return (jnp.transpose(gaps), jnp.transpose(mask, (1, 0, 2)),
                jnp.transpose(primary))


_sample_correlated_jit = jax.jit(
    sample_correlated_renewal_gaps,
    static_argnames=("n_runs", "max_failures", "n_nodes"))


def correlated_renewal_gaps(
    topology: Topology,
    process: failures.FailureProcess,
    key: jax.Array,
    n_runs: int,
    n_nodes: int,
    max_failures: int,
):
    """Host entry point: numpy ``(gaps float64, failed_mask bool, primary
    int64)`` from the same jitted sampler the device engine fuses — the
    float64 cast of the float32 gaps, so histories match the device engine
    bit-for-bit (the ``failures.renewal_gaps`` contract, correlated)."""
    gaps, mask, primary = _sample_correlated_jit(
        topology, process, key, n_runs=n_runs, max_failures=max_failures,
        n_nodes=n_nodes)
    return (np.asarray(gaps, np.float64), np.asarray(mask, bool),
            np.asarray(primary, np.int64))


def survivor_slot_mask(failed_mask, primary):
    """Map a physical-node felled mask to *survivor-slot* space.

    The renewal engines describe an epoch as one primary failed node (the
    re-execution role) plus ``n_nodes - 1`` survivor slots; slot ``i``
    is physical node ``i + (i >= primary)`` (the nodes in order, skipping
    the primary), so it is a select between the mask shifted by one and
    the mask itself: two static slices, no gather.  Works on numpy and
    traced jnp arrays; shapes ``(..., N) -> (..., N - 1)`` with
    ``primary`` shaped ``(...)``.
    """
    with jax.named_scope("renewal_sample"):
        xp = _ns(failed_mask)
        idx = xp.arange(failed_mask.shape[-1] - 1)
        return xp.where(idx >= primary[..., None],
                        failed_mask[..., 1:], failed_mask[..., :-1])


# ---------------------------------------------------------------------------
# LANL-style trace ingestion
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FailureTraceLog:
    """A parsed failure trace: one row per node failure, time-sorted."""

    node: np.ndarray          # (E,) int64 node ids in [0, n_nodes)
    t_s: np.ndarray           # (E,) float64 failure timestamps, ascending
    downtime_s: np.ndarray    # (E,) float64 repair durations
    n_nodes: int

    def __post_init__(self):
        node = np.asarray(self.node, np.int64).ravel()
        t = np.asarray(self.t_s, np.float64).ravel()
        down = np.asarray(self.downtime_s, np.float64).ravel()
        if not (node.size == t.size == down.size):
            raise ValueError("node/t_s/downtime_s must be equal length")
        if node.size == 0:
            raise ValueError("empty failure trace")
        order = np.argsort(t, kind="stable")
        node, t, down = node[order], t[order], down[order]
        n_nodes = int(self.n_nodes) if self.n_nodes else int(node.max()) + 1
        if node.min() < 0 or node.max() >= n_nodes:
            raise ValueError(f"node ids outside [0, {n_nodes})")
        object.__setattr__(self, "node", node)
        object.__setattr__(self, "t_s", t)
        object.__setattr__(self, "downtime_s", down)
        object.__setattr__(self, "n_nodes", n_nodes)

    def __len__(self) -> int:
        return int(self.node.size)

    @property
    def span_s(self) -> float:
        return float(self.t_s[-1] - self.t_s[0])


def parse_lanl_csv(source, *, n_nodes: Optional[int] = None) -> FailureTraceLog:
    """Parse a LANL-style failure trace CSV: ``node,timestamp,downtime``
    rows (a header line is skipped when the first field is non-numeric).

    ``source`` is a path, a string of CSV text, or an iterable of lines.
    Node ids are dense integers; ``n_nodes`` overrides the inferred count
    (``max id + 1``) when the trace does not mention every node.
    """
    if isinstance(source, (str, pathlib.Path)) and "\n" not in str(source):
        lines = pathlib.Path(source).read_text().splitlines()
    elif isinstance(source, str):
        lines = source.splitlines()
    else:
        lines = [str(l) for l in source]
    node, t, down = [], [], []
    for i, line in enumerate(lines):
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        parts = [p.strip() for p in line.split(",")]
        if len(parts) < 3:
            raise ValueError(f"line {i + 1}: expected node,timestamp,"
                             f"downtime — got {line!r}")
        try:
            n_id = int(float(parts[0]))
        except ValueError:
            if not node:                 # header row
                continue
            raise ValueError(f"line {i + 1}: bad node id {parts[0]!r}")
        node.append(n_id)
        t.append(float(parts[1]))
        down.append(float(parts[2]))
    return FailureTraceLog(node=np.asarray(node), t_s=np.asarray(t),
                           downtime_s=np.asarray(down),
                           n_nodes=n_nodes or 0)


def to_lanl_csv(log: FailureTraceLog) -> str:
    """Serialize a trace back to the ``node,timestamp,downtime`` format."""
    buf = io.StringIO()
    buf.write("node,timestamp,downtime\n")
    for n, t, d in zip(log.node, log.t_s, log.downtime_s):
        buf.write(f"{int(n)},{t:.6f},{d:.6f}\n")
    return buf.getvalue()


def history_to_log(gaps, failed_mask, *, downtime_s: float = 600.0,
                   run: int = 0) -> FailureTraceLog:
    """Flatten one sampled renewal history (``correlated_renewal_gaps``
    output) into an absolute-timestamp trace: epoch anchors are the
    cumulative balanced gaps, and every felled node of an epoch fails at
    that anchor (the synthetic twin of a real burst)."""
    gaps = np.atleast_2d(np.asarray(gaps, np.float64))[run]
    mask = np.asarray(failed_mask, bool)
    mask = mask[run] if mask.ndim == 3 else mask
    t_abs = np.cumsum(gaps)
    node, t = [], []
    for k in range(gaps.shape[0]):
        for i in np.nonzero(mask[k])[0]:
            node.append(int(i))
            t.append(float(t_abs[k]))
    return FailureTraceLog(
        node=np.asarray(node), t_s=np.asarray(t),
        downtime_s=np.full(len(node), float(downtime_s)),
        n_nodes=mask.shape[-1])


def find_bursts(log: FailureTraceLog, burst_window_s: float) -> list:
    """Group trace events into bursts: an event within ``burst_window_s``
    of the previous event joins its burst.  Returns a list of
    ``(t0, node_tuple)`` with nodes in event order (repeats kept)."""
    bursts = []
    cur_nodes, cur_t0, last_t = [], None, None
    for n, t in zip(log.node, log.t_s):
        if last_t is None or t - last_t > burst_window_s:
            if cur_nodes:
                bursts.append((cur_t0, tuple(cur_nodes)))
            cur_nodes, cur_t0 = [], float(t)
        cur_nodes.append(int(n))
        last_t = t
    if cur_nodes:
        bursts.append((cur_t0, tuple(cur_nodes)))
    return bursts


def trace_to_empirical(log: FailureTraceLog) -> failures.EmpiricalTrace:
    """The *marginal* view of a trace: per-node inter-failure gaps pooled
    into one ``EmpiricalTrace`` (node correlation is dropped — that is what
    ``burst_replay_gaps`` preserves)."""
    pooled = []
    for n in range(log.n_nodes):
        t_n = log.t_s[log.node == n]
        if t_n.size >= 2:
            pooled.extend(np.diff(t_n).tolist())
    pooled = np.asarray([g for g in pooled if g > 0.0], np.float64)
    if pooled.size < 2:
        raise ValueError("trace has fewer than 2 positive per-node gaps")
    return failures.EmpiricalTrace(pooled)


def burst_replay_gaps(
    log: FailureTraceLog,
    key: jax.Array,
    n_runs: int,
    max_failures: int,
    *,
    burst_window_s: float,
    n_nodes: Optional[int] = None,
):
    """Correlation-preserving replay: resample whole bursts, never
    individual gaps.

    The trace is cut into bursts (``find_bursts``); each replayed epoch
    draws one (inter-burst start gap, felled node set) pair uniformly with
    replacement, so within-burst simultaneity and the burst-size
    distribution survive resampling.  Returns ``(gaps (R, K) float64,
    failed_mask (R, K, N) bool, primary (R, K) int64)`` — the same triple
    ``correlated_renewal_gaps`` emits, feedable to both engines.
    Deterministic for a fixed jax key.
    """
    n = int(n_nodes or log.n_nodes)
    bursts = find_bursts(log, burst_window_s)
    if len(bursts) < 2:
        raise ValueError("need >= 2 bursts to resample inter-burst gaps")
    starts = np.asarray([t0 for t0, _ in bursts], np.float64)
    inter = np.diff(starts)                      # start-to-start gaps
    inter = inter[inter > 0.0]
    if inter.size == 0:
        raise ValueError("all inter-burst gaps are zero")
    node_sets = [tuple(sorted(set(ns))) for _, ns in bursts]
    seed = np.asarray(jax.random.key_data(key)).ravel()
    rng = np.random.default_rng(seed)
    gap_idx = rng.integers(0, inter.size, size=(n_runs, max_failures))
    set_idx = rng.integers(0, len(node_sets), size=(n_runs, max_failures))
    gaps = inter[gap_idx]
    mask = np.zeros((n_runs, max_failures, n), bool)
    primary = np.zeros((n_runs, max_failures), np.int64)
    for r in range(n_runs):
        for k in range(max_failures):
            ns = node_sets[set_idx[r, k]]
            mask[r, k, list(ns)] = True
            primary[r, k] = ns[0]
    return gaps, mask, primary


def fit_shock_rates(log: FailureTraceLog, topology: Topology, *,
                    burst_window_s: float) -> dict:
    """Estimate per-level shock MTBS from inter-failure clustering.

    Bursts (>= 2 distinct nodes within ``burst_window_s``) are attributed
    to the *finest* topology level whose single group contains every burst
    node; singleton bursts count as individual failures.  A level with
    ``G`` groups observed over span ``T`` with ``B`` attributed bursts has
    shock MTBS estimated by ``G * T / B`` (each group runs its own clock).
    Returns ``{level_name: {"shock_mtbs_s", "n_bursts"}, ...,
    "individual": {"mtbf_s", "n_events"}, "unattributed": count}``.
    """
    bursts = find_bursts(log, burst_window_s)
    span = max(log.span_s, 1e-9)
    # finest level first: most groups = most specific attribution
    order = sorted(range(len(topology.levels)),
                   key=lambda i: -topology.levels[i].n_groups)
    counts = {lv.name: 0 for lv in topology.levels}
    n_single = 0
    n_unattributed = 0
    for _, nodes in bursts:
        uniq = sorted(set(nodes))
        if len(uniq) < 2:
            n_single += 1
            continue
        for i in order:
            lv = topology.levels[i]
            if len({lv.group_of[n] for n in uniq}) == 1:
                counts[lv.name] += 1
                break
        else:
            n_unattributed += 1
    out = {}
    for lv in topology.levels:
        b = counts[lv.name]
        out[lv.name] = {
            "n_bursts": b,
            "shock_mtbs_s": (lv.n_groups * span / b) if b else np.inf,
        }
    out["individual"] = {
        "n_events": n_single,
        "mtbf_s": (log.n_nodes * span / n_single) if n_single else np.inf,
    }
    out["unattributed"] = n_unattributed
    return out


def dispersion_index(event_times, *, span_s: Optional[float] = None,
                     n_windows: int = 64) -> float:
    """Index of dispersion (variance/mean of counts per equal window) of a
    point process: ~1 for Poisson, > 1 for clustered (bursty) arrivals.
    The clustering statistic the shock-on vs shock-off tests separate on."""
    t = np.sort(np.asarray(event_times, np.float64).ravel())
    if t.size < 2:
        raise ValueError("need >= 2 events")
    t0 = t[0]
    span = float(span_s) if span_s else float(t[-1] - t0)
    if span <= 0.0:
        raise ValueError("zero time span")
    w = np.minimum((((t - t0) / span) * n_windows).astype(np.int64),
                   n_windows - 1)
    counts = np.bincount(w, minlength=n_windows).astype(np.float64)
    mean = counts.mean()
    return float(counts.var() / mean) if mean > 0 else 0.0
