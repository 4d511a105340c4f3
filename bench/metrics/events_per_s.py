"""Events delivered through the adapter's input entry points in the
window's rounds, over the window's wall seconds: all the work and all the
time of the window, the simulator's included."""


def read(run: dict) -> float | None:
    if not run["window_s"] > 0:
        return None
    return run["events"] / run["window_s"]
