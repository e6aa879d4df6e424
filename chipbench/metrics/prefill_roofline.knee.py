"""Share (%) of its roofline the prefill program reaches: the least time
its work (``work.prefill`` at the batch bucket it ran) takes at the chip's
peaks, over its device time in the trace."""
import work
import readings


def read(run):
    calls = readings.traced_calls(run)
    if not calls:
        return None
    least = sum(work.prefill(run.config, c.bucket, c.prompt_len)
                .least_seconds(run.peaks) for c, _ in calls)
    return 100.0 * least / sum(rec["prefill"] for _, rec in calls)
