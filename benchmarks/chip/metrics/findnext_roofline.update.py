"""FINDNEXT kernel's share of the HBM roofline in the traced update window
(percent), as roofline.findnext_share counts it."""
from roofline import findnext_share as read  # noqa: F401
