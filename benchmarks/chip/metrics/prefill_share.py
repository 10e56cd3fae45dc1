"""Device time of the admission (prefill) programs over that of the
admission and decode programs together, in the traced steps whose
launches were told apart."""


def read(run):
    prog = run.program_seconds()
    if prog is None or prog["admit"] + prog["decode"] <= 0:
        return None
    return 100.0 * prog["admit"] / (prog["admit"] + prog["decode"])
