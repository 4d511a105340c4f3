"""Set-up: process start to the window's first round (imports, the TPU
check, cluster and traffic build, the traffic's ramp)."""


def read(run: dict) -> float | None:
    return run["setup_s"]
