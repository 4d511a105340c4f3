"""Step-1 solver: milliseconds per window round in the scheduler's
``_step1_start_prepared`` span."""


def read(run: dict) -> float | None:
    if "step1" not in run["spans"] or not run["rounds"]:
        return None
    return run["spans"]["step1"] * 1e3 / run["rounds"]
