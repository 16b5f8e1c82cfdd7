"""What several per-layer readers share.  Each reader gets ``ctx``: the
window's record, the reduced trace (None when nothing was traced), the
drive's counters at the trace's two ends, the chip's peaks and the cell's
files."""
from __future__ import annotations


def traced(ctx: dict, key: str):
    """The growth of a drive counter over the traced part of the window."""
    c = ctx.get("counters") or {}
    if "start" not in c or "end" not in c:
        return None
    return c["end"][key] - c["start"][key]


def idle_share(ctx: dict):
    """Percent of the traced window in which no operation ran on the
    device, averaged over the chips."""
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

