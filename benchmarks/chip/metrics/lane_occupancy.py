"""Share of the decode lanes held by a request over the measured window:
each request holds its lane from its first token until it is done (or
the window closes), over ``max_batch`` lanes times the window."""


def read(run):
    w0, w1 = run.window
    held = 0.0
    for r in run.records:
        if r.first is None:
            continue
        end = r.done if r.done is not None else w1
        held += max(0.0, min(end, w1) - max(r.first, w0))
    return 100.0 * held / (run.max_batch * (w1 - w0))
