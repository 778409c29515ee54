"""Median wait in the queue: from when a request was due to the start (host
clock) of the fleet step in which its prefill ran."""
import statistics


def read(rec):
    waits = rec.get("queue_wait_s")
    return statistics.median(waits) * 1e3 if waits else None
