"""Share of its roofline that the Pallas histogram kernel reached in the
traced unit, in percent: the least time its dispatches need (every id read
once at HBM bandwidth; ``cost.histogram_cost``) over the device time of
its ``pallas_call`` events in the program ``jit_partition_histogram``."""

from benchlib import cost
from benchlib.kernels import roofline_share


def read(run):
    return roofline_share(run, "kernel/histogram", cost.histogram_cost,
                          "jit_partition_histogram", "%partition_histogram")
