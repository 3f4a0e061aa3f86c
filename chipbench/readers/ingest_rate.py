"""Reports acknowledged over the seconds the client spent inside POSTs."""


def read(run):
    acked = sum(r.acked for r in run.drive.rounds)
    inside = sum(end - start for r in run.drive.rounds
                 for _nodes, start, end in r.batches)
    return acked / inside if acked and inside > 0 else None
