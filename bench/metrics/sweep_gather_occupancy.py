"""Share of the preconditioner sweep's gathers that read a real entry,
in %.

The round-major sweep packs each segment's rounds at one lane width and
one K per half, so an apply gathers ``SolverPlan.sweep_gather_slots``
values, of which ``sweep_live_entries`` point at a real position (the
factor's off-diagonal entries); the rest are padding lanes and padding
slots.  Both are program counters fixed at plan build, so the reading
is the same in every window of a configuration.

The run's records carry no plan, so the reader builds the cell's plan
once more from the run's configuration, after the window: the same
matrix and knobs give the same tables.  A program without the counters
reads None.  ``sweep_gather_occupancy.step`` reads the time-stepping
cell the same way."""
from bench import spec
from bench.drivers.common import plan_knobs


def occupancy(plan_or_report):
    """live / slots in %, or None where the counters are missing."""
    slots = getattr(plan_or_report, "sweep_gather_slots", None)
    live = getattr(plan_or_report, "sweep_live_entries", None)
    if not slots or live is None:
        return None
    return 100.0 * live / slots


def read(run):
    from repro.core import build_plan
    cfg = run.config
    module = spec.load_module(spec.PACKAGE / "configs" / f"{cfg['name']}.py",
                              f"bench_config_{cfg['name']}")
    return occupancy(build_plan(module.matrix(cfg), **plan_knobs(cfg)))
