"""How late the load generator submitted: 99th percentile of submit time
minus due time."""

from chipbench.lib.readers import client, pct


def read(run):
    return pct(client(run)["lag"], 99, 1e3)
