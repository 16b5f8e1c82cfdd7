"""Mean idle time on the device between consecutive executions of the train
step program in the traced window (device trace), in milliseconds: what the
trainer runtime's feed and bookkeeping cost between steps."""


def read(ctx):
    tr = ctx.get("trace")
    gaps = [g for g, a, b in (tr or {}).get("module_gaps", [])
            if "train_step" in a and "train_step" in b]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
