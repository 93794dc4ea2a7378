"""Median host time of a decode() call, from entry to return (plan and
dispatch; before waiting for the bits), read by the benchmark's own clock."""
import statistics


def read(r):
    calls = r.get("host_call_s")
    return 1e3 * statistics.median(calls) if calls else None
