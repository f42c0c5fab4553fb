"""Milliseconds per traced step in which the busiest chip ran nothing
while the program's `data/batch` span (`SyntheticLM.batch_at`, a profiler
annotation on the device trace's clock) was open (bench/scopes.py)."""
import scopes


def read(run: dict):
    r = scopes.of_run(run)
    if not r.get("program_spans"):
        return None
    return 1e3 * r["input_wait_s"] / run["trace_steps"]
