"""Output tokens emitted inside the window, by every request
including those still running at its end, over the window length."""


def read(rec):
    w0, w1 = rec["window"]
    n = sum(1 for r in rec["rows"] for t in r["token_times"]
            if w0 <= t < w1)
    return n / rec["seconds"]
