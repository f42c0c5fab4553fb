"""Device milliseconds per traced step of the busiest chip under the
model's `model/mlp` scope (the MLP or MoE and its norm, forward, backward
and recompute), from the profiler trace (bench/scopes.py)."""
import scopes


def read(run: dict):
    return scopes.ms_per_step(run, ("model/mlp",))
