"""The cell's plan build (ordering, IC(0), packing, transfer), as the
plan's own ``SetupBreakdown.total`` records it on the host clock."""


def read(run):
    return run.plan_build_s or None
