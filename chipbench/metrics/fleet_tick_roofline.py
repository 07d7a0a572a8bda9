"""Share (%) of the HBM roofline of the fused fleet-tick program: 28 B per
parameter the window's ticks applied, at the peak bandwidth, over the
program's device time in the trace."""

from chipbench.readers import fleet_share


def read(run):
    return fleet_share(run, "program")
