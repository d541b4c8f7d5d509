"""Device idle share of the training window: 1 - busy / window, in %,
busy being the union of device op intervals (profiler trace)."""


def read(run):
    share = run.red.get("idle_share")
    return None if share is None else 100.0 * share
