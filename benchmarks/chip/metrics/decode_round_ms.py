"""Device time of the decode-block programs per decode round, over the
traced steps whose launches were told apart."""


def read(run):
    prog = run.program_seconds()
    rounds = sum(run.steps[i].rounds for i in prog["steps"]) if prog else 0
    if not rounds or prog["decode"] <= 0:
        return None
    return 1e3 * prog["decode"] / rounds
