"""Device seconds per window batch in the FINDNEXT traversal (`wharf/findnext`:
the order-2 prefix traversal and the packed search,
kernels/range_search.py), from the traced window's op metadata
(xplane_ops.py)."""
import xplane_ops


def read(ctx):
    return xplane_ops.per_batch(ctx, "findnext")
