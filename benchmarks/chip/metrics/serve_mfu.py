"""Whole-step share of the chip's peak: the model operations of every
prompt admitted and every decode round run in the traced steps, over
the traced window at the bf16 peak."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    arch, idx = run.cell.arch, run.traced_step_indices()
    flops = sum(arch.prefill_flops(run.dims, len(r.prompt)) for i in idx for r in run.admissions(i))
    flops += sum(arch.decode_flops(run.dims, lanes) for i in idx for lanes in run.lane_positions(i))
    return 100.0 * flops / run.peaks["bf16_flop_per_s"] / run.trace.window_s if flops else None
