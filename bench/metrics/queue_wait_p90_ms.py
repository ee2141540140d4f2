"""90th percentile, over the window's requests, of the time from a
request's due time to the start of its admission (the benchmark's own
timestamps around the server's admission)."""
from bench import traffic


def read(run):
    waits = run.records["served"].queue_waits()
    if not waits:
        return None
    return traffic.percentile([w * 1e3 for w in waits], 90)
