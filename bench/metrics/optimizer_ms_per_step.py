"""Device milliseconds per traced step of the busiest chip under the
`optim/clip` and `optim/adamw` scopes of `adamw_update` (the global norm
and clipping, the AdamW update of the ZeRO-3 shards), from the profiler
trace (bench/scopes.py)."""
import scopes


def read(run: dict):
    return scopes.ms_per_step(run, ("optim/",))
