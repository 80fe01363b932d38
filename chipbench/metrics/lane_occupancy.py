"""Scheduler: mean share of the engine's lanes busy at each decode tick of
the window (EngineMetrics occupancy)."""


def read(run):
    occ = run.engine_metrics.occupancy
    return 100.0 * sum(occ) / (len(occ) * run.n_slots) if occ else None
