"""Share, in %, of the traced window in which no op ran on the device:
1 - (union of the device's op intervals) / window, from the profiler."""

from bench.lib import profile


def reduce(bundle):
    if "profile" not in bundle or not bundle["profile"]["ops"]:
        return None
    lo, hi = profile.window(bundle["profile"])
    return 100.0 * (1.0 - profile.busy_seconds(bundle["profile"]) / ((hi - lo) / 1e9))
