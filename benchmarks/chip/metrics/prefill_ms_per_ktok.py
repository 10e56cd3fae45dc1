"""Admission device time per 1000 real (unpadded) prompt tokens admitted
in the traced steps whose launches were told apart."""


def read(run):
    prog = run.program_seconds()
    if prog is None or prog["admit"] <= 0:
        return None
    tokens = sum(len(r.prompt) for i in prog["steps"] for r in run.admissions(i))
    return 1e3 * prog["admit"] / (tokens / 1000.0) if tokens else None
