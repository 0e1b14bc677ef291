"""Share of its roofline that the Pallas Thompson choice kernel reaches:
the least time its work needs on this chip (bytes over HBM bandwidth or
operations over peak, whichever is larger, ``bench/stats.py``) over the
device time of its events in the trace."""
from bench import trace
from bench.stats import thompson_bytes, thompson_flops

# the kernel's events carry its name in their HLO text; a recorded v5e
# trace of bdd.q8 matched them with this pattern
KERNEL = r"_thompson_kernel|thompson_choose"
KERNEL_NAME = "%pallas_call.5 = custom-call(), thompson_choose_batched"   # for tests


def value(ctx):
    red, peaks = ctx["trace"], ctx["peaks"]
    if not red or not peaks:
        return None
    calls = trace.op_count(red, KERNEL)
    seconds = trace.op_seconds(red, KERNEL)
    if not calls or seconds <= 0:
        return None
    plan = ctx["mix"]["plan"]
    q, c, m = plan.get("queries", 1), plan["cohorts"], ctx["num_chunks"]
    least = max(thompson_bytes(q, c, m) / peaks["hbm_bytes_per_s"],
                thompson_flops(q, c, m) / peaks["bf16_flops_per_s"])
    return 100.0 * calls * least / seconds
