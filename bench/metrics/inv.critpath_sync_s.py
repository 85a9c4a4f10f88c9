"""Mean seconds per query that the invocations on the critical path spent
with the host blocked on the device: inside the program's ``sync/*``
spans, a part of ``inv.critpath_compute_s``. Read from the window's last
traced unit (``benchlib/bodyspans.py``)."""

from benchlib import bodyspans


def read(run):
    return bodyspans.critpath_inside(bodyspans.last_unit_spans(), "sync")
