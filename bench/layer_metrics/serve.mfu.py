"""Model FLOP/s utilisation of serving: the operations of every token
prefilled and decoded in the whole measured window (``bench/flops.py``:
matrix products of the routed experts and attention over the live
context; no padded capacity, masked cache slots or recomputation) over
the window's seconds on the host clock and the chip's bf16 peak."""

from bench import flops


def read(ctx):
    f = ctx.facts
    if f.get("window_s", 0) <= 0:
        return None
    m = f["model"]
    work = (sum(flops.prefill_flops(m, s)
                for s in f["window_prefill_lengths"])
            + sum(flops.decode_flops(m, k) for k in f["window_decode_keys"]))
    if not work:
        return None
    return 100.0 * work / f["window_s"] / ctx.peaks["flops_bf16"]
