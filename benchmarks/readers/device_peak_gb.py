"""Peak device memory on the fullest chip, as the replica or worker reported it."""


def read(ctx):
    peak = ctx["result"]["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
