"""Device seconds per window batch in the MAV gather (`wharf/mav`: touched
mask, segment gather, unpair, p_min reduction, core/mav.py), from the traced
window's op metadata (xplane_ops.py)."""
import xplane_ops


def read(ctx):
    return xplane_ops.per_batch(ctx, "mav")
