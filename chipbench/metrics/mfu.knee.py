"""Model FLOP/s utilization (%): the operations of the real rows' prompt
and generated tokens (``work.request``, padding rows not counted), over
the engine programs' device time in the trace times the chip's peak."""
import work
import readings


def read(run):
    calls = readings.traced_calls(run)
    if not calls:
        return None
    flops = sum(c.rows * work.request(run.config, c.prompt_len,
                                      run.traffic["gen_len"]).flops
                for c, _ in calls)
    device = sum(rec["prefill"] + rec["fused_decode"] for _, rec in calls)
    return 100.0 * flops / (device * run.peaks["flops_per_s"])
