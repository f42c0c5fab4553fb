"""Share of the traced window, in %, in which no operation ran on the
busiest chip: 1 - (union of its op intervals / the window), from the
profiler trace (bench/tracing.py)."""


def read(run: dict):
    tr = run.get("trace") or {}
    if not tr.get("window_s"):
        return None
    busy = tr["devices"][tr["busiest"]]["busy_s"]
    return 100.0 * (1.0 - busy / tr["window_s"])
