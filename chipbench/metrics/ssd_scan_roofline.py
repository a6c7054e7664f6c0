"""Share of the roofline reached by the ssd_scan kernel (``kernels/ssd_scan.py``)."""

from chipbench.lib.readers import roofline


def read(run):
    return roofline(run, "ssd_scan")
