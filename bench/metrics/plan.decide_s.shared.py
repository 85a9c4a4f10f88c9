"""``plan.decide_s`` of the cells whose tenants share one runtime (see
``plan.decide_s.py``): the same reading, under its own name so that it
moves the shared cells' end-to-end metric."""

from benchlib.readers import load_reader

read = load_reader("plan.decide_s")
