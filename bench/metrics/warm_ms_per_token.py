"""Service time of the warm ``generate`` requests (dispatch start to
return) summed, over the output tokens they served."""


def read(run):
    warm = [r for r in run.window.records
            if r["path"] == "warm" and r["entry"] == "generate"]
    tokens = sum(r["new_tokens"] for r in warm)
    if not tokens:
        return None
    return sum(r["end"] - r["start"] for r in warm) / tokens * 1e3
