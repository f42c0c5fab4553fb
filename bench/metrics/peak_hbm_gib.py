"""The highest `peak_bytes_in_use` over the cell's chips after the window,
in GiB (the runtime's memory counter)."""


def read(run: dict):
    b = run.get("memory_peak_bytes") or 0
    return b / (1 << 30) if b else None
