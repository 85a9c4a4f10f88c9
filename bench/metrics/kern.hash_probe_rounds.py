"""Mean probe depth of the hash joins: the ``probe_rounds`` attribute of
the program's ``kernel/join`` spans with ``method == "hash"``, the rounds
the build placed its keys in and the probe then ran (at most the span's
``max_probes``). Read from the window's last traced unit
(``benchlib/bodyspans.py``). A program that records no depth gives
``None``."""

from benchlib import bodyspans


def probe_rounds(spans):
    vals = [s.attrs["probe_rounds"] for s in spans
            if s.name == "kernel/join" and s.attrs.get("method") == "hash"
            and "probe_rounds" in s.attrs]
    return sum(vals) / len(vals) if vals else None


def read(run):
    return probe_rounds(bodyspans.last_unit_spans())
