"""Step-2/3 drain: milliseconds per window round in the scheduler's
``_step2_prepare_for_free_compute`` and ``_step3_speculative_prepare``
spans."""


def read(run: dict) -> float | None:
    spans = run["spans"]
    if ("step2" not in spans and "step3" not in spans) or not run["rounds"]:
        return None
    return (spans.get("step2", 0.0) + spans.get("step3", 0.0)) * 1e3 \
        / run["rounds"]
