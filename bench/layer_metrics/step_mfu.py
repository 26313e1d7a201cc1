"""Model FLOPs of the prompt and output tokens processed in the traced
segment (``counts.py``), over device busy time times the bf16 peak."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    work = run.traced_work()
    if not work:
        return None
    flops = sum(w.flops for w in work)
    return 100.0 * flops / (run.trace["busy_s"] * run.peak["bf16_flops_per_s"])
