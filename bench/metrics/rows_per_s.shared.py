"""``rows_per_s`` of the cells whose tenants share one runtime (see
``rows_per_s.py``): the same reading, under its own name so that it has its
own bound and moves the shared cells' end-to-end metric."""

from benchlib.readers import load_reader

read = load_reader("rows_per_s")
