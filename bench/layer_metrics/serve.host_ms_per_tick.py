"""Host milliseconds per engine tick: the wall time of the benchmark's
span around each ``Engine.step`` less the device busy time inside it,
averaged over the ticks in the traced window."""


def read(ctx):
    ticks = ctx.trace.span_events("serve.tick")
    if not ticks:
        return None
    host = [d - ctx.trace.busy_ns(s, s + d) for _, s, d in ticks]
    return sum(host) / len(host) / 1e6
