"""Host milliseconds per window step in the input layer: the bench's own
span around the program's `SyntheticLM.batch_at` and the batch's transfer
to the device (host clock)."""


def read(run: dict):
    xs = run.get("input_s") or []
    return 1e3 * sum(xs) / len(xs) if xs else None
