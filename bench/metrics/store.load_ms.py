"""Store: mean duration of the program's ``store.load`` spans, in ms.

A span covers one partition lookup in ``PartitionStore``: a warm hit, or a
cold stage through the host tier (a disk read and sha256 check on a host
miss) and the dispatch of its device transfer.
"""


def read(run):
    d = [s.t1 - s.t0 for s in run.spans or [] if s.name == "store.load"
         and s.t1 is not None]
    return 1e3 * sum(d) / len(d) if d else None
