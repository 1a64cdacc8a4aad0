"""Device milliseconds per decode step: the mean run of the compiled
decode program (the configuration's ``modules.decode``)."""


def read(ctx):
    runs = ctx.trace.module_events(ctx.facts["modules"]["decode"])
    if not runs:
        return None
    return sum(d for _, _, d in runs) / len(runs) / 1e6
