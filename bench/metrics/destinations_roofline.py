"""Share of its roofline that the Pallas destinations kernel reached in the
traced unit, in percent: the least time its dispatches need (every id read
and one destination written; ``cost.destinations_cost``, over the
grouping's buckets plus its sentinel bucket) over the device time of its
``pallas_call`` events in the program ``jit__grouping_pallas``."""

from benchlib import cost
from benchlib.kernels import roofline_share


def read(run):
    return roofline_share(run, "kernel/grouping", cost.destinations_cost,
                          "jit__grouping_pallas", "%partition_destinations",
                          extra_buckets=1)
