"""Device milliseconds per prefix-sum call spent outside the Pallas
kernels: the decoupled schedule's chain loop, pads, reshapes, and the
whole call where the policy picks a plain-jnp algorithm."""


def read(ctx):
    calls = len(ctx.trace.span_events("scan.call"))
    if not calls:
        return None
    kernels = set(ctx.facts["kernels"])
    ns = sum(v for k, v in ctx.trace.op_ns().items()
             if k.split("/", 1)[1] not in kernels)
    return ns / 1e6 / calls
