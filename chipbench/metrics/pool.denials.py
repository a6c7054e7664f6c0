"""KV pool admissions refused in the window: ``pages_denied`` plus
``arenas_denied`` of the pool's own counters."""


def read(run):
    return run.pool_denials
