"""Share of the prompt positions computed by the admissions in the
traced steps that were padding: one less their real prompt tokens over
bucket x padded rows.  Each admitted request keeps its admission's
record (``Request.admission``, the args of the engine's ``serve.admit``
span); an engine that keeps none gives no reading."""


def read(run):
    admissions = {}
    for i in run.traced_step_indices():
        for r in run.admissions(i):
            a = getattr(r.req, "admission", None)
            if a is not None:
                admissions[id(a)] = a
    computed = sum(a.bucket * a.n_pad for a in admissions.values())
    if not computed:
        return None
    return 100.0 * (1.0 - sum(a.tokens for a in admissions.values()) / computed)
