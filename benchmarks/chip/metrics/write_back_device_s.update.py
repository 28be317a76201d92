"""Device seconds per window batch in the version-block write-back
(`wharf/write_back`: triplet encode, block append, slot epochs,
core/update.py), from the traced window's op metadata (xplane_ops.py)."""
import xplane_ops


def read(ctx):
    return xplane_ops.per_batch(ctx, "write_back")
