"""99th percentile of how late the load generator sent each query after
its due time (a starved generator would otherwise read as a fast server)."""

from bench import stats


def read(layer, trace):
    if layer["kind"] != "open_loop":
        return None
    return stats.percentile(layer["lag_ms"], 99)
