"""Mean seconds per query that the critical path spent in shuffle-store
puts and gets (critical path over the program's spans)."""


def read(run):
    vals = [q.spans["cp_store"] for q in run.queries if "cp_store" in q.spans]
    return sum(vals) / len(vals) if vals else None
