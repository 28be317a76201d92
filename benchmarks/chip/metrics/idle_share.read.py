"""Share of the traced read window in which no operation ran on the device
(percent): 1 - busy / window, busy being the union of the device's op
intervals (trace_reduce.py)."""
import trace_reduce


def read(ctx):
    return trace_reduce.idle_share(ctx["trace"])
