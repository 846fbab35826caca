def read(ctx, m, spec):
    counters = m.get("counters") or {}
    lanes = counters.get("dispatches", 0) * ctx["config"]["geometry"]["slots"]
    if not lanes:
        return None
    return 100.0 * counters["occupancy_sum"] / lanes
