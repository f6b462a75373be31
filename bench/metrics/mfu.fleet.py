"""Operations the parallel formulation requires for the window's tracks
(real steps x passes x `bench.opcount.per_step_pass`), over the window's
time at the chip's peak FLOP/s (`bench/peaks.json`), in percent."""
from bench import opcount


def read(run):
    out = run.outcome
    if run.peaks is None or out.step_passes is None:
        return None
    p = run.config["problem"]
    ops = out.step_passes * opcount.per_step_pass(
        p["nx"], p["ny"], run.config["spec"]["linearization"])
    return 100.0 * ops / (out.window_s * run.peaks["flops"])
