"""Share, in %, of the splash attention kernels' time that the chip's
roofline needs for their work: the least time of one chip's attention
core a step (bench/counts.py: attention_core, forward and backward,
recomputation not counted; the larger of its operations over the bf16
peak and its bytes over the HBM bandwidth, bench/peaks.json) over the
busiest chip's device seconds a traced step in ops whose instruction
starts with `splash_mha` (the forward, its recomputation, dq and dkv),
from the profiler trace (bench/tracing.py). None where no such op ran."""
import counts

KERNEL = "splash_mha"


def read(run: dict):
    tr = run.get("trace")
    core, peak = run.get("attention_core"), run.get("peaks")
    if not tr or not core or not peak:
        return None
    per_op = tr["devices"][tr["busiest"]]["per_op"]
    kernel_s = sum(s for op, s in per_op.items() if op.startswith(KERNEL))
    if not kernel_s:
        return None
    least_s, _ = counts.roofline_s(core["flops"], core["bytes"], peak)
    return 100.0 * least_s / (kernel_s / run["trace_steps"])
