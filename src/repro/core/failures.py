"""Pluggable failure processes: the inter-failure-gap distribution axis.

The paper's model — and every engine built on it so far — hard-codes a
memoryless exponential failure process, which is what makes the renewal
engines' quiesce/deferral policy exact *for free* (deferring a failure to
the renewal anchor is equivalent to redrawing it there).  Real HPC failure
logs are markedly non-exponential: Weibull-shaped hazards (infant mortality
at k < 1, wear-out at k > 1), heavy-tailed log-normal gaps, and empirical
traces that fit no named family.  This module opens that axis:

  * ``Exponential``      — the paper's process; closed-form special case.
  * ``Weibull``          — ``Weibull(k, scale_s)``; ``from_mtbf`` scales to
                           a target mean via Gamma(1 + 1/k).
  * ``LogNormal``        — ``LogNormal(mu, sigma)`` of the log-gap.
  * ``Gamma``            — shape/scale; inverse CDF by bisection on
                           ``gammaincc`` (no closed form).
  * ``EmpiricalTrace``   — resampling from a supplied gap array (a failure
                           log), age-conditioned on the sorted trace.

Every process supports **per-node heterogeneous parameters**: parameter
arrays broadcast against a trailing node axis, so a 4-node cluster can mix
an infant-mortality node (k = 0.6) with wear-out nodes (k = 1.5) in one
sampler.

Conditional residuals (the quiesce policy without memorylessness)
-----------------------------------------------------------------
The renewal engines defer any failure arriving during an open recovery
epoch to the renewal anchor (docs/sweep.md).  For the exponential that
deferral is *equivalent* to redrawing each node's time-to-failure at the
anchor.  For every other process it is not: a node that has survived to
failure-clock age ``a`` fails according to the **conditional residual**
distribution

    P(T > t | age a)  =  S(a + t) / S(a),          S = survival function,

so the sampler must track per-node clock ages across epochs and draw each
residual by age-conditioned inverse CDF:

    T  =  S^{-1}(u * S(a)) - a,       u ~ U(0, 1].

``residual(v, age)`` implements exactly that transform per process (``v``
is the raw uniform draw, ``u = 1 - v``); the exponential's closed form
``T = -mtbf * log1p(-v)`` drops the age (``Exponential.sample`` is the
package's one exponential sampler).  ``sample_renewal_gaps`` runs the competing-risks recursion —
residuals for all nodes, the epoch gap is the minimum, the failing node the
argmin, survivor clocks advance by the gap, the failed clock resets — as a
``lax.scan`` that both the host oracle (``sweep.renewal_failure_gaps``) and
the fused device engine (``sweep._renewal_mc_core``) trace, so fixed-key
failure histories are bit-identical across engines.

Precision contract (shared with the renewal engines): draws and the
inverse-CDF transforms are float32 — ``jax.random`` emits identical float32
bits with and without x64 enabled — and the composition geometry consumes
the float64 cast of those float32 gaps.  Parameters are stored as concrete
float32 at construction so tracing under ``enable_x64`` cannot silently
promote the transform.

Statistical validation lives in tests/test_failures.py (KS goodness-of-fit
at n = 50k per process, a memorylessness property that *fails* for Weibull
k != 1, probability-integral-transform validation of the age-conditioned
renewal sampler); the derivations and Weibull-fitting guidance are in
docs/failures.md.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy import special as jsp

__all__ = [
    "FailureProcess",
    "Exponential",
    "Weibull",
    "LogNormal",
    "Gamma",
    "EmpiricalTrace",
    "as_process",
    "stack_processes",
    "sample_renewal_gaps",
    "renewal_gaps",
    "failure_clock_ages",
    "ks_statistic",
    "ks_critical",
    "fit_weibull",
]

_GAMMA_BISECT_ITERS = 46    # bisection steps for the gamma inverse CDF; the
                            # bracket shrinks ~2^-46, far below f32 resolution

_lgamma_u = np.frompyfunc(math.lgamma, 1, 1)
_erfc_u = np.frompyfunc(math.erfc, 1, 1)


def _gamma_fn(x) -> np.ndarray:
    """Elementwise Gamma function in float64 (numpy carries no gamma)."""
    return np.exp(np.asarray(_lgamma_u(np.asarray(x, np.float64)), np.float64))


def _ndtr_np(x) -> np.ndarray:
    """Standard-normal CDF in float64 via math.erfc."""
    return 0.5 * np.asarray(
        _erfc_u(-np.asarray(x, np.float64) / math.sqrt(2.0)), np.float64)


def _param(x):
    """Normalize a process parameter to concrete float32.

    Concrete at construction keeps the sampling transform float32 even when
    traced under ``enable_x64`` (python-float leaves would promote to
    float64 there, breaking the cross-engine bit-identity of histories).
    Non-numeric leaves pass through untouched: pytree unflattening re-runs
    the constructor with traced leaves (jit/vmap over process parameters),
    and transform plumbing (``jax.vmap``'s in_axes resolution) unflattens
    with opaque placeholder objects.
    """
    if isinstance(x, jax.core.Tracer):
        return x
    try:
        return np.asarray(x, np.float32)
    except (TypeError, ValueError):
        return x


def _check_positive(name: str, x) -> None:
    if not isinstance(x, np.ndarray):
        return
    if np.any(np.asarray(x, np.float64) <= 0.0):
        raise ValueError(f"{name} must be positive, got {x}")


class FailureProcess:
    """Base: one node's inter-failure gap distribution.

    Subclasses are frozen pytree dataclasses whose parameter leaves
    broadcast against a trailing node axis.  The contract is three views of
    the same law:

      * ``residual(v, age)`` — float32, jittable: the age-conditioned
        inverse-CDF transform of a raw uniform draw ``v`` in [0, 1)
        (survival draw ``u = 1 - v``); ``age = 0`` is an unconditional
        draw.  This is the only method the engines call.
      * ``survival(t)`` / ``cdf(t)`` — float64 host numpy, broadcasting:
        the analytic law the statistical tests validate samples against.
      * ``mean_s()`` — float64 mean gap (the process's MTBF), per node.
    """

    def residual(self, v, age):
        raise NotImplementedError

    def survival(self, t) -> np.ndarray:
        raise NotImplementedError

    def cdf(self, t) -> np.ndarray:
        return 1.0 - self.survival(t)

    def mean_s(self) -> np.ndarray:
        raise NotImplementedError

    def label(self) -> str:
        raise NotImplementedError

    def sample(self, key: jax.Array, shape) -> jax.Array:
        """Unconditional (age-0) float32 gap draws of the given shape.

        For per-node parameter arrays the trailing axis of ``shape`` is the
        node axis.  Every exponential draw of the package comes from here
        (``Exponential.residual`` on one float32 uniform), so the sampling
        sites agree bit for bit by construction.
        """
        v = jax.random.uniform(key, shape, jnp.float32)
        return self.residual(v, jnp.zeros_like(v))


@dataclasses.dataclass(frozen=True)
class Exponential(FailureProcess):
    """Memoryless gaps, mean ``mtbf_s`` — the paper's failure process."""

    mtbf_s: Any

    def __post_init__(self):
        object.__setattr__(self, "mtbf_s", _param(self.mtbf_s))
        _check_positive("mtbf_s", self.mtbf_s)

    def residual(self, v, age):
        # memoryless: the age drops out
        del age
        return jnp.asarray(self.mtbf_s, jnp.float32) * (-jnp.log1p(-v))

    def survival(self, t):
        return np.exp(-np.asarray(t, np.float64) / np.asarray(self.mtbf_s, np.float64))

    def mean_s(self):
        return np.asarray(self.mtbf_s, np.float64)

    def label(self):
        return f"exponential(mtbf={np.mean(self.mean_s()):g}s)"


@dataclasses.dataclass(frozen=True)
class Weibull(FailureProcess):
    """Weibull(k, scale): S(t) = exp(-(t/scale)^k).

    k < 1 — decreasing hazard (infant mortality: surviving nodes are
    *good*, so conditional residuals are stochastically longer than fresh
    draws); k > 1 — increasing hazard (wear-out); k = 1 — exponential.
    """

    k: Any
    scale_s: Any

    def __post_init__(self):
        object.__setattr__(self, "k", _param(self.k))
        object.__setattr__(self, "scale_s", _param(self.scale_s))
        _check_positive("k", self.k)
        _check_positive("scale_s", self.scale_s)

    @classmethod
    def from_mtbf(cls, k, mtbf_s) -> "Weibull":
        """Shape ``k`` with the scale chosen so the mean gap is ``mtbf_s``
        (mean = scale * Gamma(1 + 1/k)) — equal-MTBF comparisons against
        the exponential isolate the *shape* effect."""
        k64 = np.asarray(k, np.float64)
        scale = np.asarray(mtbf_s, np.float64) / _gamma_fn(1.0 + 1.0 / k64)
        return cls(k=k, scale_s=scale)

    def residual(self, v, age):
        k = jnp.asarray(self.k, jnp.float32)
        lam = jnp.asarray(self.scale_s, jnp.float32)
        e = -jnp.log1p(-v)                       # unit exponential draw
        # S(a+T)/S(a) = u  <=>  ((a+T)/lam)^k = (a/lam)^k + e
        za = (age / lam) ** k
        return jnp.maximum(lam * (za + e) ** (1.0 / k) - age, 0.0)

    def survival(self, t):
        t = np.asarray(t, np.float64)
        k = np.asarray(self.k, np.float64)
        lam = np.asarray(self.scale_s, np.float64)
        return np.exp(-(t / lam) ** k)

    def mean_s(self):
        k = np.asarray(self.k, np.float64)
        return np.asarray(self.scale_s, np.float64) * _gamma_fn(1.0 + 1.0 / k)

    def label(self):
        return (f"weibull(k={np.mean(np.asarray(self.k, np.float64)):g},"
                f"mtbf={np.mean(self.mean_s()):g}s)")


@dataclasses.dataclass(frozen=True)
class LogNormal(FailureProcess):
    """log(gap) ~ Normal(mu, sigma^2): heavy right tail, non-monotone hazard."""

    mu: Any
    sigma: Any

    def __post_init__(self):
        object.__setattr__(self, "mu", _param(self.mu))
        object.__setattr__(self, "sigma", _param(self.sigma))
        _check_positive("sigma", self.sigma)

    @classmethod
    def from_mtbf(cls, mtbf_s, sigma) -> "LogNormal":
        """Spread ``sigma`` with the location chosen so the mean gap is
        ``mtbf_s`` (mean = exp(mu + sigma^2 / 2))."""
        s64 = np.asarray(sigma, np.float64)
        mu = np.log(np.asarray(mtbf_s, np.float64)) - 0.5 * s64 * s64
        return cls(mu=mu, sigma=sigma)

    def residual(self, v, age):
        mu = jnp.asarray(self.mu, jnp.float32)
        sigma = jnp.asarray(self.sigma, jnp.float32)
        u = 1.0 - v
        s_a = jnp.where(age > 0.0, jsp.ndtr((mu - jnp.log(age)) / sigma), 1.0)
        # floor keeps ndtri finite when age pushes the survival mass below
        # f32 tiny (the draw then lands ~13 sigma out instead of at +inf)
        uc = jnp.maximum(u * s_a, jnp.float32(1e-37))
        return jnp.maximum(jnp.exp(mu - sigma * jsp.ndtri(uc)) - age, 0.0)

    def survival(self, t):
        t = np.asarray(t, np.float64)
        mu = np.asarray(self.mu, np.float64)
        sigma = np.asarray(self.sigma, np.float64)
        with np.errstate(divide="ignore"):
            z = np.where(t > 0.0, (mu - np.log(np.maximum(t, 1e-300))) / sigma,
                         np.inf)
        return _ndtr_np(z)

    def mean_s(self):
        mu = np.asarray(self.mu, np.float64)
        sigma = np.asarray(self.sigma, np.float64)
        return np.exp(mu + 0.5 * sigma * sigma)

    def label(self):
        return (f"lognormal(sigma={np.mean(np.asarray(self.sigma, np.float64)):g},"
                f"mtbf={np.mean(self.mean_s()):g}s)")


@dataclasses.dataclass(frozen=True)
class Gamma(FailureProcess):
    """Gamma(k, scale): S(t) = Q(k, t/scale) (regularized upper incomplete).

    No closed-form inverse: the residual solves ``Q(k, z) = u * Q(k, z_a)``
    by fixed-count bisection on ``jax.scipy.special.gammaincc`` —
    deterministic, jittable, and identical on host and device.  Shapes up
    to k ~ 30 keep the bracket ``z_a + 32 (1 + k)`` conservative.
    """

    k: Any
    scale_s: Any

    def __post_init__(self):
        object.__setattr__(self, "k", _param(self.k))
        object.__setattr__(self, "scale_s", _param(self.scale_s))
        _check_positive("k", self.k)
        _check_positive("scale_s", self.scale_s)

    @classmethod
    def from_mtbf(cls, k, mtbf_s) -> "Gamma":
        """Shape ``k`` with the scale chosen so the mean gap is ``mtbf_s``
        (mean = k * scale)."""
        scale = np.asarray(mtbf_s, np.float64) / np.asarray(k, np.float64)
        return cls(k=k, scale_s=scale)

    def residual(self, v, age):
        k = jnp.asarray(self.k, jnp.float32)
        scale = jnp.asarray(self.scale_s, jnp.float32)
        za = age / scale
        target = (1.0 - v) * jsp.gammaincc(k, za)
        lo = jnp.broadcast_to(za, target.shape)
        hi = lo + 32.0 * (1.0 + k)

        def step(_, bracket):
            lo, hi = bracket
            mid = 0.5 * (lo + hi)
            right = jsp.gammaincc(k, mid) > target   # survival still above
            return jnp.where(right, mid, lo), jnp.where(right, hi, mid)

        lo, hi = jax.lax.fori_loop(0, _GAMMA_BISECT_ITERS, step, (lo, hi))
        return jnp.maximum(scale * (0.5 * (lo + hi)) - age, 0.0)

    def survival(self, t):
        z = np.asarray(t, np.float64) / np.asarray(self.scale_s, np.float64)
        k = np.asarray(self.k, np.float64)
        with jax.enable_x64():
            return np.asarray(jsp.gammaincc(jnp.asarray(k), jnp.asarray(z)),
                              np.float64)

    def mean_s(self):
        return (np.asarray(self.k, np.float64)
                * np.asarray(self.scale_s, np.float64))

    def label(self):
        return (f"gamma(k={np.mean(np.asarray(self.k, np.float64)):g},"
                f"mtbf={np.mean(self.mean_s()):g}s)")


@dataclasses.dataclass(frozen=True)
class EmpiricalTrace(FailureProcess):
    """Gaps resampled from a supplied failure log.

    ``gaps`` is a 1-D array (one trace shared by all nodes) or 2-D
    ``(n_nodes, L)`` (per-node traces); it is sorted ascending at
    construction.  Unconditional draws resample uniformly; an
    age-conditioned residual resamples uniformly from the sub-trace
    ``{g - age : g > age}`` — the exact conditional law of the empirical
    distribution.  A clock age beyond the trace's largest gap has no
    conditional mass; the sampler then falls back to an *unconditional*
    resample (hazard restarts), documented in docs/failures.md.
    """

    gaps: Any

    def __post_init__(self):
        g = self.gaps
        if not isinstance(g, jax.core.Tracer):
            g = np.sort(np.asarray(g, np.float32), axis=-1)
            if g.ndim not in (1, 2) or g.shape[-1] < 2:
                raise ValueError(
                    f"trace must be (L,) or (n_nodes, L) with L >= 2, "
                    f"got shape {np.shape(g)}")
            if np.any(g <= 0.0):
                raise ValueError("trace gaps must be positive")
        object.__setattr__(self, "gaps", g)

    @staticmethod
    def _residual_1d(trace, v, age):
        n = trace.shape[0]
        start = jnp.searchsorted(trace, age, side="right")  # first gap > age
        exhausted = start >= n
        start = jnp.where(exhausted, 0, start)
        n_avail = (n - start).astype(jnp.float32)
        off = jnp.floor(v * n_avail).astype(start.dtype)
        idx = start + jnp.minimum(off, n - 1 - start)
        raw = jnp.take(trace, idx)
        return jnp.where(exhausted, raw, jnp.maximum(raw - age, 0.0))

    def residual(self, v, age):
        trace = jnp.asarray(self.gaps, jnp.float32)
        age = jnp.asarray(age, jnp.float32)
        if trace.ndim == 1:
            return self._residual_1d(trace, v, age)
        # per-node traces: vmap the 1-D case over the trailing node axis
        return jax.vmap(self._residual_1d, in_axes=(0, -1, -1), out_axes=-1)(
            trace, v, age)

    def survival(self, t):
        trace = np.asarray(self.gaps, np.float64)
        t = np.asarray(t, np.float64)
        if trace.ndim == 1:
            return 1.0 - np.searchsorted(trace, t, side="right") / trace.shape[-1]
        t_b = np.broadcast_to(t, np.broadcast_shapes(t.shape, trace.shape[:1]))
        cols = [np.searchsorted(trace[i], t_b[..., i], side="right")
                for i in range(trace.shape[0])]
        return 1.0 - np.stack(cols, axis=-1) / trace.shape[-1]

    def mean_s(self):
        return np.mean(np.asarray(self.gaps, np.float64), axis=-1)

    def label(self):
        g = np.asarray(self.gaps, np.float64)
        return f"trace(n={g.shape[-1]},mtbf={np.mean(g):g}s)"


for _cls, _fields in (
    (Exponential, ["mtbf_s"]),
    (Weibull, ["k", "scale_s"]),
    (LogNormal, ["mu", "sigma"]),
    (Gamma, ["k", "scale_s"]),
    (EmpiricalTrace, ["gaps"]),
):
    jax.tree_util.register_dataclass(_cls, data_fields=_fields, meta_fields=[])


def as_process(process: Optional[FailureProcess], mtbf_s=None) -> FailureProcess:
    """Normalize the (process, mtbf_s) calling convention the engines share:
    ``process=None`` means the paper's exponential at ``mtbf_s``."""
    if process is None:
        if mtbf_s is None:
            raise ValueError("provide a FailureProcess or an mtbf_s")
        return Exponential(mtbf_s)
    if not isinstance(process, FailureProcess):
        raise TypeError(f"not a FailureProcess: {process!r}")
    return process


def stack_processes(processes) -> FailureProcess:
    """Stack same-family processes into ONE process with a leading cluster
    axis on every parameter leaf.

    This is the failure-process half of the fleet dispatch
    (``sweep.renewal_monte_carlo_policies`` with a cluster axis): the
    stacked object is a single pytree the fused program can ``vmap`` over,
    and each cluster lane then sees exactly the scalar (or per-node)
    parameters its standalone process carries — so per-cluster histories
    sampled at a shared key are bit-identical to standalone
    ``sample_renewal_gaps`` calls on each member (tests/test_fleet.py).

    All members must be the same concrete class (the sampler's control flow
    — exponential closed form vs conditional-residual scan — is static per
    dispatch) with identically shaped parameter leaves (``EmpiricalTrace``
    members need equal trace lengths).  A single-member stack is valid and
    yields leaves of shape ``(1, ...)``.
    """
    procs = [as_process(p) for p in processes]
    if not procs:
        raise ValueError("no processes to stack")
    fam = type(procs[0])
    if any(type(p) is not fam for p in procs):
        raise ValueError(
            "stack_processes needs one process family per dispatch bucket, "
            f"got {sorted({type(p).__name__ for p in procs})}; route "
            "mixed-family fleets through per-family buckets (repro.fleet)")
    try:
        return jax.tree.map(
            lambda *ls: np.stack([np.asarray(l, np.float32) for l in ls]),
            *procs)
    except ValueError as e:
        raise ValueError(
            f"{fam.__name__} parameter leaves do not stack (unequal "
            f"shapes across clusters): {e}") from e


# ---------------------------------------------------------------------------
# the renewal-epoch gap sampler (competing risks with per-node clock ages)
# ---------------------------------------------------------------------------

def sample_renewal_gaps(
    process: FailureProcess,
    key: jax.Array,
    n_runs: int,
    max_failures: int,
    n_nodes: int,
):
    """Renewal-epoch gaps under the quiesce policy: ``(gaps, failed_node)``
    of shape ``(n_runs, max_failures)``, gaps float32.

    Jit-friendly (shape args static); traced by the fused device engine and
    jitted standalone for the host oracle (``renewal_gaps``), so the two
    see bit-identical histories for the same key.

    Exponential processes take the legacy closed form — fresh draws per
    epoch, the gap is the min and the failing node the argmin (memoryless
    deferral == redraw), reproducing ``sweep.renewal_failure_gaps``'s
    histories bit-for-bit.  Every other process runs the conditional-
    residual recursion: per-node failure-clock ages start at zero
    (the run starts a fresh, progress-synchronized cluster), each epoch
    draws every node's age-conditioned residual, survivors' clocks advance
    by the epoch gap while the failed node's clock resets, and — matching
    the quiesce policy — clocks freeze during the recovery epoch itself
    (failure exposure accrues over balanced execution, which is also the
    time the makespan meters).
    """
    with jax.named_scope("renewal_sample"):
        if isinstance(process, Exponential):
            draws = process.sample(key, (n_runs, max_failures, n_nodes))
            return jnp.min(draws, axis=-1), jnp.argmin(draws, axis=-1)

        v = jax.random.uniform(
            key, (max_failures, n_runs, n_nodes), dtype=jnp.float32)

        def step(ages, v_k):
            t = process.residual(v_k, ages)                      # (R, N)
            gap = jnp.min(t, axis=-1)
            failed = jnp.argmin(t, axis=-1)
            ages = jnp.where(jnp.arange(n_nodes) == failed[:, None],
                             0.0, ages + gap[:, None])
            return ages, (gap, failed)

        init = jnp.zeros((n_runs, n_nodes), jnp.float32)
        _, (gaps, failed) = jax.lax.scan(step, init, v)
        return gaps.T, failed.T


_sample_renewal_gaps_jit = jax.jit(
    sample_renewal_gaps,
    static_argnames=("n_runs", "max_failures", "n_nodes"))


def renewal_gaps(
    process: FailureProcess,
    key: jax.Array,
    n_runs: int,
    n_nodes: int,
    max_failures: int,
):
    """Host entry point: numpy ``(gaps float64, failed_node int64)`` from
    the same jitted sampler the device engine fuses — the float64 cast of
    the float32 gaps, so histories match the device engine bit-for-bit."""
    gaps, failed = _sample_renewal_gaps_jit(
        process, key, n_runs=n_runs, max_failures=max_failures,
        n_nodes=n_nodes)
    return np.asarray(gaps, np.float64), np.asarray(failed, np.int64)


def failure_clock_ages(gaps, failed_node, n_nodes: int) -> np.ndarray:
    """Reconstruct per-node *failure-clock* ages at each renewal anchor.

    ``sample_renewal_gaps`` conditions every non-memoryless draw on how
    long each node's failure clock has been running: clocks start at zero
    (a fresh, progress-synchronized cluster), survivors' clocks advance by
    each epoch gap, the failing node's clock resets, and — per the quiesce
    policy — clocks freeze during the recovery epoch itself.  Given a
    sampled history ``(gaps, failed_node)`` of shape ``(R, K)`` (or
    ``(K,)``), this replays that recursion (it must mirror ``step`` in
    ``sample_renewal_gaps`` exactly) and returns the ``(R, K, n_nodes)``
    float64 ages *at* each anchor — the exact ages the sampler conditioned
    epoch ``k``'s residual draws on.

    These are the sampling-side twin of the checkpoint/lost-work sawtooth
    ages the composition engines carry (re-exported from
    ``core.scenarios``, which owns that failure-state view); both restart
    on their own events (checkpoints vs failures).
    tests/test_failures.py uses this replay to validate the conditional-
    residual law by probability integral transform.
    """
    gaps = np.atleast_2d(np.asarray(gaps, np.float64))
    failed = np.atleast_2d(np.asarray(failed_node, np.int64))
    if gaps.shape != failed.shape:
        raise ValueError(f"gaps {gaps.shape} and failed_node {failed.shape} "
                         "must share their (R, K) shape")
    if failed.size and (failed.min() < 0 or failed.max() >= n_nodes):
        raise ValueError(f"failed_node entries outside [0, {n_nodes})")
    n_runs, max_failures = gaps.shape
    ages = np.zeros((n_runs, max_failures, n_nodes))
    a = np.zeros((n_runs, n_nodes))
    rows = np.arange(n_runs)
    for k in range(max_failures):
        ages[:, k] = a
        a = a + gaps[:, k][:, None]
        a[rows, failed[:, k]] = 0.0
    return ages


# ---------------------------------------------------------------------------
# statistical helpers (shared by tests/test_failures.py and docs/failures.md)
# ---------------------------------------------------------------------------

def ks_statistic(samples, cdf, discrete: bool = False) -> float:
    """Two-sided Kolmogorov-Smirnov statistic of ``samples`` against the
    callable ``cdf``.

    ``discrete=False`` (continuous laws): the exact empirical sup,
    ``max_i max(i/n - F(x_i), F(x_i) - (i-1)/n)`` over sorted samples.
    That formula *overstates* the sup for a discrete law — with ties the
    ``F(x_i) - (i-1)/n`` term compares the atom-inclusive CDF against the
    pre-atom empirical step, inflating D by up to one atom's mass — so
    ``discrete=True`` (e.g. ``EmpiricalTrace``) instead compares the two
    right-continuous steps at the sampled atoms, ``max |F_n(x) - F(x)|``
    over unique values; the usual critical values stay valid (DKW is
    distribution-free and conservative for discrete laws).
    """
    x = np.sort(np.asarray(samples, np.float64).ravel())
    n = x.size
    if discrete:
        uniq, counts = np.unique(x, return_counts=True)
        cum = np.cumsum(counts) / n
        f = np.asarray(cdf(uniq), np.float64)
        return float(np.abs(cum - f).max())
    f = np.asarray(cdf(x), np.float64)
    i = np.arange(1, n + 1, dtype=np.float64)
    return float(np.maximum(i / n - f, f - (i - 1.0) / n).max())


def ks_critical(n: int, alpha: float = 1e-3) -> float:
    """Asymptotic two-sided KS critical value at level ``alpha``:
    sqrt(-ln(alpha/2) / 2) / sqrt(n)."""
    return math.sqrt(-0.5 * math.log(alpha / 2.0)) / math.sqrt(n)


def fit_weibull(gaps, iters: int = 200, censored=None) -> tuple:
    """Maximum-likelihood Weibull fit of a gap sample: ``(k, scale_s)``.

    The profile-likelihood fixed point in the shape,

        1/k  =  sum(x^k ln x) / sum(x^k)  -  mean(ln x),

    iterated from k = 1, then the scale from the k-moment.  Standard MLE
    for complete (uncensored) failure logs; see docs/failures.md for usage
    on a real log (and for why equal-MTBF comparisons should re-scale via
    ``Weibull.from_mtbf`` afterwards).

    ``censored`` (optional) are Type-I right-censored observations: ages of
    nodes that have *not yet* failed (an online fitter mid-run sees one per
    surviving clock).  They contribute survival mass only, extending the
    fixed point to

        1/k  =  sum_all(t^k ln t) / sum_all(t^k)  -  mean(ln x_complete)
        scale^k  =  sum_all(t^k) / n_complete

    where the ``all`` sums run over complete AND censored observations.
    With ``censored=None`` (or empty) both reduce to the complete-sample
    formulas above, bit for bit.  Non-positive censored entries are
    dropped (a zero age carries no information).

    Degenerate inputs get a documented fallback instead of NaN (the burst
    detector feeds this short, sometimes pathological windows):

      * no complete gaps, no censored mass — ``ValueError`` (nothing to
        fit); any *non-positive* complete gap is also a ``ValueError``
        (corrupt input, not a small sample);
      * all-censored (no complete gaps) — ``(1.0, sum(censored))``: the
        exponential total-exposure bound with zero events;
      * a single complete gap — ``(1.0, sum(t))``: the exponential MLE,
        the one-parameter family a one-event sample can support;
      * zero spread (all observations equal — the fixed point diverges
        upward) — the shape saturates at ``k = 100`` and the scale comes
        from the same k-moment, ~the common value.  The fixed-point
        iteration itself is clamped to ``k in [1e-2, 1e2]`` and the
        k-moment is evaluated in log-space, so heavy censoring or extreme
        spread cannot overflow ``t**k``.
    """
    x = np.asarray(gaps, np.float64).ravel()
    if np.any(x <= 0.0):
        raise ValueError("complete gaps must be positive")
    c = np.asarray([] if censored is None else censored, np.float64).ravel()
    c = c[c > 0.0]
    if x.size == 0 and c.size == 0:
        raise ValueError("need at least one positive gap or censored age")
    if x.size == 0:
        return 1.0, float(c.sum())
    t = np.concatenate([x, c])          # every observation carries t^k mass
    lt = np.log(t)
    ml = np.log(x).mean()               # only complete gaps carry ln-density

    k_lo, k_hi = 1e-2, 1e2

    def _scale(k: float) -> float:
        # scale^k = sum(t^k) / n_complete, evaluated in log-space so large
        # k (the zero-spread saturation) cannot overflow t**k
        m = float(np.max(k * lt))
        s = m + math.log(float(np.sum(np.exp(k * lt - m)))) - math.log(x.size)
        return float(math.exp(s / k))

    if x.size == 1 and c.size == 0:
        return 1.0, float(t.sum())
    if np.ptp(lt) < 1e-12:              # zero spread: fixed point diverges
        return k_hi, _scale(k_hi)
    k = 1.0
    for _ in range(iters):
        tk = np.exp(np.clip(k * lt - np.max(k * lt), -745.0, 0.0))
        denom = np.sum(tk * lt) / np.sum(tk) - ml
        k_new = math.inf if denom <= 0.0 else 1.0 / denom
        if not np.isfinite(k_new):
            k = k_hi
            break
        k_new = min(max(k_new, k_lo), k_hi)
        if abs(k_new - k) < 1e-12:
            k = k_new
            break
        k = k_new
    return float(k), _scale(float(k))
