"""Answer-without-device paths: share of the window's requests that no
device window answered (verdict cache, in-window dedup, host twins, host fallback)."""

SOURCE = "program_counter"


def read(ctx):
    a, b = ctx["before"], ctx["after"]
    if not ctx["attempted"]:
        return None

    def grew(*path):
        x, y = a, b
        for k in path:
            x, y = x[k], y[k]
        return y - x

    twin_windows = grew("compile_cache", "host_twin_windows")
    windows = twin_windows + grew("compile_cache", "device_windows")
    twin_requests = grew("batcher", "requests") * twin_windows / windows if windows else 0
    host = (grew("verdict_cache", "hits_total") + grew("verdict_cache", "window_dedup_rows")
            + grew("degraded", "fallback_requests") + twin_requests)
    return 100.0 * host / ctx["attempted"]
