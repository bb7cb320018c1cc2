"""answers_per_s: answers back on the host inside the window, divided by
the window's length: the pairs of the requests completed before the
close."""


def read(run):
    pairs = run.traffic.rows * run.traffic.width
    return int((run.record.end <= run.seconds).sum()) * pairs / run.seconds
