"""Device seconds per window batch in the graph update (`wharf/graph_update`:
StreamingGraph.apply_batch, core/graph.py), from the traced window's op
metadata (xplane_ops.py)."""
import xplane_ops


def read(ctx):
    return xplane_ops.per_batch(ctx, "graph_update")
