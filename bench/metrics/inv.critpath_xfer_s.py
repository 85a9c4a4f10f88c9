"""Mean seconds per query that the invocations on the critical path spent
copying between host and device: inside the program's ``xfer/d2h`` and
``xfer/h2d`` spans, a part of ``inv.critpath_compute_s``. Read from the
window's last traced unit (``benchlib/bodyspans.py``)."""

from benchlib import bodyspans


def read(run):
    return bodyspans.critpath_inside(bodyspans.last_unit_spans(), "xfer")
