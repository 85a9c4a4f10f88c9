"""Mean seconds per query that the executor spent in the planner between
stages: the program's ``plan/*`` spans (the initial plan, and each
``on_stage_complete`` with its profile feedback), which hold the
``decide/*`` spans of the decision workflow's late bindings. Read from the
window's last traced unit (``benchlib/bodyspans.py``)."""

from benchlib import bodyspans


def read(run):
    return bodyspans.plan_seconds(bodyspans.last_unit_spans())
