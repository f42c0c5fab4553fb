"""Device milliseconds per traced step of the busiest chip under the
trainer's `zero3/gather` and `zero3/scatter` scopes (the ZeRO-3 parameter
gather and gradient reduce-scatter, with the schedule executor's
`exec/*` and `bucket/*` scopes nested in them; on one chip the flat-shard
layout moves alone), from the profiler trace (bench/scopes.py)."""
import scopes


def read(run: dict):
    return scopes.ms_per_step(
        run, ("zero3/", "exec/", "bucket/", "sync/", "overlap/"))
