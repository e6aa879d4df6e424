"""Share (%) of its roofline the fused decode program reaches: the least
time its work (``work.decode`` at the batch bucket it ran, bytes counted up
to the cache length in use) takes at the chip's peaks, over its device
time in the trace."""
import work
import readings


def read(run):
    calls = readings.traced_calls(run)
    if not calls:
        return None
    steps = run.traffic["gen_len"] - 1
    least = sum(work.decode(run.config, c.bucket, c.prompt_len, steps)
                .least_seconds(run.peaks) for c, _ in calls)
    return 100.0 * least / sum(rec["fused_decode"] for _, rec in calls)
