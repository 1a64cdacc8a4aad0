"""Share of the HBM roofline that a whole prefix-sum call reaches.

The least time a call can take is its algorithmic bytes (every element
read once, its running sum written once) over the chip's HBM bandwidth;
the time it took is the device busy time of the traced calls, every op
of the call counted, whatever implements it.
"""


def read(ctx):
    calls = len(ctx.trace.span_events("scan.call"))
    busy_s = ctx.trace.busy_ns() / 1e9
    if not calls or busy_s <= 0:
        return None
    need_s = calls * ctx.facts["bytes_per_call"] / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / busy_s
