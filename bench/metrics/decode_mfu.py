"""The decode step's share of the chip's peak: the least time the step
needs at the published peaks (the family module's ``decode_step``: the
weights one token multiplies, its routed experts only, and the keys and
values it reads, at the mean context of the decode steps served in the
traced part) over the mean device time of a decode program run."""

from devtrace import step_programs
from harness import reference_module
from work import least_time


def read(run):
    if run.trace is None:
        return None
    runs = step_programs(run.trace, run.window.records)["decode"]
    if not runs:
        return None
    step_s = sum(e - s for s, e in runs) / len(runs)
    prompt = run.spec["engine"]["prefill_len"]
    contexts = [prompt + j for r in run.window.records
                if r["entry"] == "generate" and r["path"] != "failed"
                and r["start"] < run.window.traced_s
                for j in range(r["new_tokens"] - 1)]
    if not contexts:
        return None
    flops, nbytes = reference_module(run.spec).decode_step(
        run.spec, sum(contexts) / len(contexts))
    return least_time(flops, nbytes, run.peaks) / step_s
