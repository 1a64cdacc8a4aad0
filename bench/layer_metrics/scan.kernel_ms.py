"""Device milliseconds per prefix-sum call spent in the Pallas kernels
(the program's ``tpu_custom_call`` ops, named from its compiled text)."""


def read(ctx):
    calls = len(ctx.trace.span_events("scan.call"))
    kernels = set(ctx.facts["kernels"])
    if not calls or not kernels:
        return None
    ns = sum(v for k, v in ctx.trace.op_ns().items()
             if k.split("/", 1)[1] in kernels)
    return ns / 1e6 / calls
