"""Share (%) of the HBM roofline of the multi-job Adam kernel: the same
required bytes as fleet_tick_roofline over the kernel's own device time
inside the fleet-tick executions."""

from chipbench.readers import fleet_share


def read(run):
    return fleet_share(run, "kernel")
