"""Operations and bytes the renewal Monte-Carlo needs, from its shapes:
lower bounds that hold whatever engine does the work (the x64 scan, the
float32 Pallas kernel, or a successor).

Unit of work.  A *decision* is one (lane, run, epoch, survivor) point,
where a lane is a scenario of a study.  A *lane epoch* is one (lane,
run, epoch).  A *node epoch* is one (run, epoch, node) draw of the failure
sampler, which a study shares across its scenarios.  A *lane run* is one
(lane, run) of output.

Operations.  Every elementwise arithmetic operation, comparison, select
and transcendental counts as one; F = 4 ladder levels.  Per decision:

===============================================  =====
the survivor's checkpoint sawtooth to the failure    21
  (first fire, fired test, clamp, floor quotient
  with its remainder, inside-checkpoint test, fire
  count, age, moved instant, work)
work to the next rendezvous (mod, wrap) and T_failed  7
balanced energy up to the failure (split, 2 powers)  13
checkpoints per level: ceil((w*beta+age-T)/T), x F    28
move-ahead decision on the top-frequency timeline    12
Algorithm 1 per level (comp time, wait, comp          120
  energy, awake and sleep energy, feasibility, sleep
  gate and cost test, total, running argmin), x F
reference energy, the selected decision's selects    10
trailing spans to the epoch end, both runs, sums     10
action counts (4 masked adds)                          8
renewal: next rendezvous (mod, wrap), reset age       8
===============================================  =====
total                                                237

Per lane epoch (the failed node and the epoch): its sawtooth (21),
balanced energy (13), re-execution race, recovery and epoch end (6),
failed-node energy (5), the occurrence test and carry updates (8): 53.

Per node epoch of the sampler: exponential 5 (uniform to float, log1p,
scale, the min and argmin over nodes); Weibull 14 (adds the age-conditioned
inverse: divide, two powers, add, multiply, subtract, clamp, and the age
update); rack shocks add 8 (the rack clock, kill draw and test, forced
kill, spared boost).

Bytes.  The histories are drawn and consumed on the device, so what a
call must move is its inputs (tens of bytes a lane) and its outputs: per
lane run 5 float64 energies and times and 7 int32 counts, 68 bytes.
"""
from __future__ import annotations

FLOPS_PER_DECISION = 237
FLOPS_PER_LANE_EPOCH = 53
FLOPS_PER_NODE_EPOCH = {"exponential": 5, "weibull": 14, "rack": 22}
BYTES_PER_LANE_RUN = 68


def counts(work: dict) -> tuple:
    """``(flops, bytes)`` of the work a driver reports: ``decisions``,
    ``lane_epochs``, ``lane_runs`` and ``node_epochs`` (sampler draws by
    family)."""
    flops = (FLOPS_PER_DECISION * work["decisions"]
             + FLOPS_PER_LANE_EPOCH * work["lane_epochs"]
             + sum(FLOPS_PER_NODE_EPOCH[f] * n
                   for f, n in work["node_epochs"].items()))
    return float(flops), float(BYTES_PER_LANE_RUN * work["lane_runs"])


def roofline(work: dict, peaks: dict) -> dict:
    """The least time the chip could take for ``work``: the larger of
    operations over peak operations per second and bytes over peak
    bandwidth, and which of the two bounds it."""
    flops, nbytes = counts(work)
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return dict(flops=flops, bytes=nbytes, seconds=max(t_flops, t_bytes),
                bound="compute" if t_flops >= t_bytes else "memory")
