"""Simulator: window time outside the adapter, in milliseconds per event.
Only cells driven through the simulator have it."""


def read(run: dict) -> float | None:
    if not run["simulated"] or not run["events"]:
        return None
    return (run["window_s"] - run["adapter_s"]) * 1e3 / run["events"]
