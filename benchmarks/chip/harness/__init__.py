"""The chip benchmark's yardstick: cell lookup, device checks, traffic data,
window arithmetic, trace reduction and the float64 reference check.

Nothing here imports the system under test; drivers receive it as an
argument (see ``run.py``).
"""
