"""Time to first token, p90 over every request due in the window: from
its due time to its first token on the host (ms)."""
from bench.harness import percentile


def read(run):
    v = percentile(run.first_token_waits_s(), 90)
    return None if v is None else v * 1e3
