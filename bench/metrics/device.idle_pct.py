"""Device: share of the traced window in which the TPU ran no operation, %.

100 x (1 - busy / window), where busy is the union of the intervals of the
device's op events inside the window (``bench/tracereduce.py``).
"""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
