"""50th percentile of first-token time, from when the request was due
(not from `t_submit`) to its first token: queue wait and chunked prefill.
An end-to-end quantity by nature; it stands here, without a bound, while a
window holds too few requests for its tail to be steady (PERF.md)."""
from benchmark import stats


def read(run):
    ttft = run.get("ttft_ms")
    return stats.percentile(ttft, 50) if ttft else None
