"""Device seconds per window batch in the sampler (`wharf/sample`, with
`wharf/intersect` nested in it: core/walkers.py, kernels/intersect.py), from
the traced window's op metadata (xplane_ops.py)."""
import xplane_ops


def read(ctx):
    return xplane_ops.per_batch(ctx, "sample", "intersect")
