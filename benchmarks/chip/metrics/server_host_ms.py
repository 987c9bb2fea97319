"""Host milliseconds the server spends on one stack, outside the device
wait: stack assembly (with its guards), the host-to-device copy, the
launch (program fetch, a compile if one is needed, the async call), the
device-to-host fetch of the answers, retire (slicing, checks, futures) and
any per-request fallback solves.  Source: the program's own span counters
(``<span>_ns`` in ``EeiServer.stats()``, deltas over the window), over the
stacks dispatched in the window.  ``None`` where the program has no such
counters or dispatched no stack."""

SPANS = ("assemble", "copy_in", "launch", "fetch", "retire", "fallback")


def read(ctx):
    counters = ctx.record.counters
    keys = [f"{span}_ns" for span in SPANS]
    stacks = counters.get("stacks_dispatched", 0)
    if stacks <= 0 or any(key not in counters for key in keys):
        return None
    return sum(counters[key] for key in keys) / stacks / 1e6
