"""The whole local phase's share of the chips' bf16 peak: the DP-SGD
forward and backward operations of every merged update (its client's
DP steps times B examples, ``bench/flops.py`` over the family's count
per example), over the traced window's seconds, the chips and the
peak.  Eval, padded cohort members and masked steps are not counted."""
from bench import flops


def read(run):
    w, cfg = run.window, run.cell.config
    if run.trace is None or not w.updates or run.trace.window_s <= 0:
        return None
    per_step = flops.dp_step_flops(run.cell.family, cfg["model"],
                                   cfg["testbed"]["batch_size"])
    work = sum(n * run.steps_by_tier[t] * per_step
               for t, n in w.tier_updates.items())
    return 100.0 * work / (run.trace.window_s * run.chips
                           * run.peaks["flops_bf16"])
