"""Mean active slots over n_slots across the window's decode
steps, in percent (the engine's per-step live lengths)."""


def read(rec):
    w0, w1 = rec["window"]
    steps = rec["booking"].steps_in(w0, w1)
    if not steps:
        return None
    return 100.0 * sum(len(s) for s in steps) / (len(steps) * rec["n_slots"])
