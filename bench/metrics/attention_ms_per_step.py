"""Device milliseconds per traced step of the busiest chip under the
model's `model/attn` scope (the attention and its norm, forward, backward
and recompute), from the profiler trace and the compiled module it
carries (bench/scopes.py)."""
import scopes


def read(run: dict):
    return scopes.ms_per_step(run, ("model/attn",))
