"""Share of the decision points an advisory flush dispatched that lay in
padded cluster lanes (the advisor's counters, through the driver's work)."""


def read(run):
    dispatched = run.work.get("decisions_dispatched")
    if not dispatched:
        return None
    return 100.0 * run.work["decisions_padded"] / dispatched
