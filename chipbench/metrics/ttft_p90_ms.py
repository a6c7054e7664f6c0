"""90th percentile (nearest rank) of time to first token over every
request due in the window, from its due time to the tick that returned its
first token; a request with none by the window's end counts its wait."""

from chipbench.lib.readers import client, pct


def read(run):
    return pct(client(run)["ttft"], 90, 1e3)
