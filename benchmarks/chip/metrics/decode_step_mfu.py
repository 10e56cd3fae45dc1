"""Model operations of the decode rounds (active lanes only, the
architecture's ``decode_flops``) over the decode-block device time at
the bf16 peak: the whole decode program's share of the chip's peak."""


def read(run):
    prog = run.program_seconds()
    if prog is None or prog["decode"] <= 0:
        return None
    flops = sum(
        run.cell.arch.decode_flops(run.dims, lanes)
        for i in prog["steps"]
        for lanes in run.lane_positions(i)
    )
    return 100.0 * flops / run.peaks["bf16_flop_per_s"] / prog["decode"]
