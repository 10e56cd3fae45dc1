"""Bytes the decode rounds need, at the HBM peak, over the decode-block
device time: per round the weights in bfloat16 once, and the keys and
values of every active lane's live positions (the architecture's
``decode_bytes``)."""


def read(run):
    prog = run.program_seconds()
    if prog is None or prog["decode"] <= 0:
        return None
    need = sum(
        run.cell.arch.decode_bytes(run.dims, lanes)
        for i in prog["steps"]
        for lanes in run.lane_positions(i)
    )
    return 100.0 * need / run.peaks["hbm_byte_per_s"] / prog["decode"]
