"""Share of the traced window's device time spent in ops that carry no
`wharf/` phase in their metadata (percent): the unscoped self time over the
device's busy time (xplane_ops.py, trace_reduce.py)."""


def read(ctx):
    trace = ctx.get("trace") or {}
    phase_s = trace.get("phase_s")
    if not phase_s or not trace.get("busy_s"):
        return None
    return 100.0 * phase_s.get("unscoped", 0.0) / trace["busy_s"]
