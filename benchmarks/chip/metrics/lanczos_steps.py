"""Lanczos steps the Krylov reduce took per answered request: the
program's ``lanczos_steps`` counter (steps of the real rows of each
stack; batch padding not counted), delta over the window, over the
requests completed in it.  The band caps it at the configuration's
``krylov_m``; a Ritz check every 32 steps may stop it earlier.  ``None``
where the program has no such counter, completed no request, or counted
no step: a Krylov row takes at least one step, so a zero delta means no
Krylov reduce was counted (another plan, or verify off), not a fast one."""


def read(ctx):
    counters = ctx.record.counters
    done = counters.get("requests_completed", 0)
    steps = counters.get("lanczos_steps", 0)
    if done <= 0 or steps <= 0:
        return None
    return steps / done
