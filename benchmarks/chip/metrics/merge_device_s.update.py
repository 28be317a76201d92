"""Device seconds per window batch in the pending merge (`wharf/merge`:
merge_interleave, core/update.py), from the traced window's op metadata
(xplane_ops.py)."""
import xplane_ops


def read(ctx):
    return xplane_ops.per_batch(ctx, "merge")
