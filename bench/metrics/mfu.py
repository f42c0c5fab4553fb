"""Model FLOP/s utilization of the whole step, in %: the configuration's
forward-and-backward operations per token (bench/counts.py:
model_flops_per_token; recomputation not counted) times the window's
tokens per second, over the chips' bf16 peak (bench/peaks.json)."""


def read(run: dict):
    peak = run.get("peaks")
    if not peak or not run.get("tokens_per_s"):
        return None
    return 100.0 * run["flops_per_token"] * run["tokens_per_s"] / (
        run["chips"] * peak["bf16_flops_per_s"])
