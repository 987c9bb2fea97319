"""Milliseconds the server's host spends blocked waiting on the device, per
stack: from retire's first sync on a launched stack until its program has
finished (the ``device_wait`` span).  A host clock reading of the server
layer, not device time: it follows the device's time only while the host
has nothing to overlap with the wait, and time the program ran while the
host was busy elsewhere is not in it.  Source: the program's span counter
``device_wait_ns`` (delta over the window) over the stacks dispatched in
the window.  ``None`` where the program has no such counter or dispatched
no stack."""


def read(ctx):
    counters = ctx.record.counters
    stacks = counters.get("stacks_dispatched", 0)
    if stacks <= 0 or "device_wait_ns" not in counters:
        return None
    return counters["device_wait_ns"] / stacks / 1e6
