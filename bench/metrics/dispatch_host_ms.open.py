"""Median host time (ms) of one pipeline dispatch in an open window
that is not spent waiting on the device: a ``serve.dispatch`` span's
length less its ``serve.wait`` spans (stacking, upload, enqueue,
de-multiplex), from the program's spans (``repro.serve.obs``)."""

import statistics


def read(r):
    spans = r.get("spans") or {}
    if r.get("loop") != "open" or not spans.get("serve.dispatch"):
        return None
    wait = {}
    for s, e, args in spans.get("serve.wait", []):
        wait[args.get("did")] = wait.get(args.get("did"), 0.0) + (e - s)
    return 1e3 * statistics.median(
        (e - s) - wait.get(args.get("did"), 0.0) for s, e, args in spans["serve.dispatch"])
