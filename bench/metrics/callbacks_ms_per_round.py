"""Adapter boundary and DPS write-through: milliseconds per window round
spent in the adapter's callbacks (every adapter call but ``schedule()``,
and the DPS calls the environment makes beside them)."""


def read(run: dict) -> float | None:
    if not run["rounds"]:
        return None
    return run["callbacks_s"] * 1e3 / run["rounds"]
