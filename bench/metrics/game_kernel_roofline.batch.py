"""Share of its roofline reached by the game's best-response kernel, in
%: the least time the chip needs for the calls' work (live clusters ×
partitions in float32, ``harness.roofline.game_bestresponse``) over the
device time of the kernel's ops in the trace."""
from harness import roofline, trace

def is_kernel(name: str) -> bool:
    """The best-response kernel's Mosaic custom call: the one Pallas call
    of the partitioner that returns (best choice, its cost) as lane-dense
    (1, M) int32 and float32 rows."""
    head, _, rest = name.partition(" = ")
    return ('custom_call_target="tpu_custom_call"' in rest
            and rest.startswith("(s32[1,") and ", f32[1," in rest[:80])


def read(ctx):
    jobs = ctx.results.get("jobs")
    if ctx.trace is None or not jobs:
        return None
    w = ctx.trace_window
    sec = trace.op_seconds(ctx.trace, w, is_kernel)
    calls = trace.op_count(ctx.trace, w, is_kernel)
    if sec <= 0 or calls <= 0:
        return None
    m = sum(j["stats"]["num_clusters"] for j in jobs) / len(jobs)
    k = ctx.config["partition"]["k"]
    flops, nbytes = roofline.game_bestresponse(m, k)
    least, _ = roofline.least_seconds(flops, nbytes, ctx.peaks)
    return 100.0 * least * calls / sec
