from benchmark.lib.common import note


def read(ctx, m, spec):
    span = m.get("trace_span")
    if not span:
        return None
    try:
        from kubeml_tpu.utils.trace import phases
    except ImportError:
        return None             # a program from before the phase ring
    steps = [r.args for r in phases(*span)
             if r.name == "serve.step.emit" and "window_rows_read" in r.args]
    if not steps:
        return None             # a program that keeps no window ring
    from benchmark.lib import flops_exaone_moe as closed
    cfg = ctx["config"]
    rows = sum(a["window_rows_read"] for a in steps)
    per_row = closed.window_row_bytes(cfg, cfg["geometry"]["kv_itemsize"])
    note(phase="metric", name="window_kv_mb_per_step",
         decode_steps=len(steps), window_rows_read=rows,
         rows_a_step=rows / len(steps), bytes_a_row=per_row,
         ring_bytes_a_slot=closed.ring_bytes_per_slot(
             cfg, cfg["geometry"]["kv_itemsize"]),
         engine_slot_state_bytes=sum(a.get("slot_state_bytes", 0)
                                     for a in steps))
    return rows * per_row / len(steps) / 1e6
