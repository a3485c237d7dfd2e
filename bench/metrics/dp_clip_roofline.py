"""The fused ``dp_clip`` kernel's share of its roofline: the least time
the chip could take for every call in the window, over the kernel's
device time in the trace.  The kernel is bound by memory (it reads the
(K*B, D) float32 per-example gradients twice at 0.5 operations per
byte), so the least time is its bytes (``bench/flops.py``, over the
family's parameter count) over the HBM bandwidth.  Every cohort of K
members runs the padded step count, each step one call over all K
members."""
from bench import flops


def read(run):
    t, cfg = run.trace, run.cell.config
    if t is None or run.cell.traffic["dp_path"] != "pallas":
        return None
    spent = t.kernel_seconds()
    if spent <= 0:
        return None
    d = run.cell.family.param_count(cfg["model"])
    b = cfg["testbed"]["batch_size"]
    least = 0.0
    for k in run.window.cohort_sizes:
        fl, nbytes = flops.dp_clip_cost(k, b, d)
        least += run.s_max * max(nbytes / run.peaks["hbm_bytes_per_s"],
                                 fl / run.peaks["flops_bf16"])
    return 100.0 * least / (spent * run.chips)
