"""Operations the real prompt tokens need (the architecture's
``prefill_flops``, no padding), at the bf16 peak, over the admission
programs' device time."""


def read(run):
    prog = run.program_seconds()
    if prog is None or prog["admit"] <= 0:
        return None
    flops = sum(
        run.cell.arch.prefill_flops(run.dims, len(r.prompt))
        for i in prog["steps"]
        for r in run.admissions(i)
    )
    return 100.0 * flops / run.peaks["bf16_flop_per_s"] / prog["admit"]
