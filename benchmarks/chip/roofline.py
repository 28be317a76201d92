"""Least bytes a FINDNEXT batch must move, and how the trace names the
FINDNEXT kernel.

A FINDNEXT query (v, w, p) -> (v_next, found) reads its three u32 operands
and writes a u32 answer and a found flag, whatever the implementation.
Each query must also read the code that answers it: at least one byte, the
narrowest delta a packed chunk holds, and never more in all than the
store's code bytes, which reading the whole store once would cover. So no
kernel can move fewer bytes, and a roofline share from this count cannot
pass 100 %.
"""
from __future__ import annotations

import re

QUERY_BYTES = 3 * 4       # v, w, p as u32
ANSWER_BYTES = 4 + 1      # v_next u32, found flag
CODE_BYTES_MIN = 1        # the narrowest packed delta


def findnext_min_bytes(queries: int, store_code_bytes: int) -> int:
    return (queries * (QUERY_BYTES + ANSWER_BYTES)
            + min(queries * CODE_BYTES_MIN, store_code_bytes))


def roofline_share(min_bytes: float, hbm_bytes_per_s: float,
                   kernel_s: float):
    """Percent of the HBM roofline: least time / kernel time."""
    if not kernel_s or kernel_s <= 0:
        return None
    return 100.0 * (min_bytes / hbm_bytes_per_s) / kernel_s


# The trace names a Pallas call after its enclosing jitted function
# (find_next_packed, or closed_call inside a loop), so the FINDNEXT kernel
# is told apart by its signature: a TPU custom call that returns two s32
# vectors (v_next, found), one entry per query of the call, and reads the
# packed store, u32[chunks, 256]
_FINDNEXT = re.compile(r"^%\S+ = \(s32\[(\d+)\]\{[^}]*\}, "
                       r"s32\[\d+\]\{[^}]*\}\) custom-call\(.*u32\[\d+,256\]")


def findnext_queries(hlo_text: str):
    """The queries one call of the FINDNEXT Pallas kernel
    (kernels/range_search.py) answers, read from its signature; None for
    any other device op."""
    if 'custom_call_target="tpu_custom_call"' not in hlo_text:
        return None
    m = _FINDNEXT.match(hlo_text)
    return int(m.group(1)) if m else None


def findnext_share(ctx: dict):
    """FINDNEXT kernel's share of the HBM roofline in a traced window
    (percent): the least bytes of the queries the trace's FINDNEXT calls
    answered over the chip's HBM bandwidth, divided by the kernel's summed
    device time. Nothing when the trace shows no FINDNEXT kernel."""
    trace = ctx["trace"] or {}
    queries = trace.get("kernel_items", {}).get("findnext")
    kernel_s = trace.get("kernel_s", {}).get("findnext")
    if not queries or not kernel_s:
        return None
    need = findnext_min_bytes(queries, ctx["counters"]["store_codes"])
    return roofline_share(need, ctx["peaks"]["hbm_bytes_per_s"], kernel_s)
