"""A statistic of a series the driver recorded (`result["series"][<series>]`)."""
import statistics


def read(ctx, series, stat, scale=1.0):
    values = ctx["result"].get("series", {}).get(series)
    if not values:
        return None
    return scale * {"median": statistics.median, "max": max, "mean": statistics.fmean}[stat](values)
