"""Share of the traced stretch in which no operation ran on the device:
``1 - busy / window``, busy being the union of the device ops' intervals
(averaged over the chips used).  Source: the profiler trace."""


def read(ctx):
    trace = ctx.trace
    if trace is None or trace["window_s"] <= 0:
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]
