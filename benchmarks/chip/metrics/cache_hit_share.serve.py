"""Share of the window's pose-bucket cache lookups that hit, in %, from
the server's own counters (GSRenderServer.telemetry)."""


def read(run):
    tel = run.telemetry or {}
    looked = tel.get("hits", 0) + tel.get("misses", 0)
    return 100.0 * tel["hits"] / looked if looked else None
