"""``fn_s_per_query`` of the cells whose tenants share one runtime (see
``fn_s_per_query.py``): the same reading, under its own name so that it has its
own bound and moves the shared cells' end-to-end metric."""

from benchlib.readers import load_reader

read = load_reader("fn_s_per_query")
