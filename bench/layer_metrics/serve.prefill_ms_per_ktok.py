"""Device milliseconds of the prefill programs (the configuration's
``modules.prefill``) per 1,024 prompt tokens prefilled in the traced
window."""


def read(ctx):
    runs = ctx.trace.module_events(ctx.facts["modules"]["prefill"])
    tokens = sum(ctx.facts.get("prefill_lengths", ()))
    if not runs or not tokens:
        return None
    return sum(d for _, _, d in runs) / 1e6 / tokens * 1024
