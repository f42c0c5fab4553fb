"""Device milliseconds per traced step of the busiest chip under the
`model/embed` and `model/head` scopes (the embedding lookup and its
gradient's scatter-add; the final norm, the logits and the loss), from
the profiler trace (bench/scopes.py)."""
import scopes


def read(run: dict):
    return scopes.ms_per_step(run, ("model/embed", "model/head"))
