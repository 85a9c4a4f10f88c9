"""Mean seconds per query that the critical path spent inside function
bodies outside the store: the invoked functions' own work, host and
device (critical path over the program's spans)."""


def read(run):
    vals = [q.spans["cp_compute"] for q in run.queries
            if "cp_compute" in q.spans]
    return sum(vals) / len(vals) if vals else None
