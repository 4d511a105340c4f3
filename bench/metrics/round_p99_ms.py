"""99th percentile, nearest rank, of the adapter time of the window's
rounds, in milliseconds."""
from harness import percentile


def read(run: dict) -> float | None:
    p = percentile(run["round_s"], 99)
    return None if p is None else p * 1e3
