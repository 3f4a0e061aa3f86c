"""Share of the devices' busy time spent in collective operations, %: the
durations of the device ops whose own name (the text before `` = `` on the
``XLA Ops`` line) is an all-reduce, all-gather, all-to-all,
collective-permute or reduce-scatter (their ``-start``/``-done`` halves
too), summed over the planes, over the planes' busy seconds. The fleet
program shards by node and reduces within a node's row only, so 0 is what
it should read; 0 is printed, not left out. Nothing without a trace."""

from chipbench.trace import op_events, union

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


def is_collective(name: str) -> bool:
    return name.split(" = ", 1)[0].lstrip("%").startswith(COLLECTIVES)


def read(run):
    busy = inside = 0
    for plane in run.planes:
        events = op_events(plane)
        busy += sum(e - s for s, e in union(events))
        inside += sum(d for name, _s, d in events if is_collective(name))
    return 100.0 * inside / busy if busy > 0 else None
