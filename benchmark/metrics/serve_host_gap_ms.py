import statistics


def read(ctx, m, spec):
    trace = m.get("trace")
    if not trace or not trace.get("program_gaps_s"):
        return None
    return 1e3 * statistics.median(trace["program_gaps_s"])
