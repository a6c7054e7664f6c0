"""99th percentile of the gaps between consecutive tokens of a request,
over every gap that closed in the window, as the client saw them."""

from chipbench.lib.readers import client, pct


def read(run):
    return pct(client(run)["gaps"], 99, 1e3)
